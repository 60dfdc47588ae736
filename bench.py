"""Benchmarks: one JSON line per BASELINE config, flagship last.

Configs (BASELINE.md "targets to measure"):
  1. BoxGame host SyncTest, cd=2     — the CPU request-loop reference point
  2. BoxGame device SyncTest, cd=8   — the flagship fused-replay path
  3. BoxGame P2P 4p, 8-branch speculation — speculative rollback vs replay
  4. EcsWorld device SyncTest, cd=16 — entity-world, long rollback window
  5. 256 batched ChipVM sessions     — massed session parallelism on 1 chip

Each line is ``{"metric", "value", "unit", "vs_baseline"}``.  The reference
publishes no numbers (BASELINE.md), so every ``vs_baseline`` is the ratio of
the measured path to the equivalent host/NumPy request loop on this machine
(config 3: ratio to the same P2P loop with speculation disabled).  The
flagship config-2 line prints LAST.

PROCESS ISOLATION: with no argument, this script re-execs itself once per
config (``python bench.py <config>``) and forwards each child's JSON line.
A chip belongs to one process at a time, so the orchestrating parent never
initialises a JAX backend (checked before the first spawn) and each child
owns the device for its own measurement.

WHICH DEVICE: every record carries ``platform`` / ``device_kind`` /
``device_count``.  The device configs (``DEVICE_CONFIGS``) fail without a
TPU of a known kind (``ggrs_tpu.utils.device.require_chip``) — they never
fall back to the CPU and there is no skip.  The host-proxy configs pin
``JAX_PLATFORMS=cpu`` in their child environment and say so in their
records.  A run in which any selected config failed, timed out, or did not
fit the budget exits nonzero.

TIMING: ``jax.block_until_ready`` is the completion fence; ``chip_smoke.py``'s
``fence`` leg checks on every run that it is a real one on the machine at
hand (implied FLOP/s under the chip's peak before and after the process's
first device->host read).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
import zlib
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax._src import xla_bridge  # backends_are_initialized: no public twin

from ggrs_tpu.games import BoxGame, ChipVM, EcsWorld, boxgame_config
from ggrs_tpu.sessions import DeviceSyncTestSession
from ggrs_tpu.utils.device import (
    device_peaks,
    device_record,
    place_compile_cache,
    require_chip,
)

CHECK_DISTANCE = 8
PLAYERS = 2
# timed passes per config, best-of.  Inherited from rounds measured over a
# shared link whose throughput drifted; whether a directly attached chip
# needs it is unverified — ROADMAP A0 replaces it with medians.
REPEATS = 3

# config name -> (function name, per-child wall-clock budget in seconds[,
# extra environment for the child]).  PRINT order (the driver reads the
# final line as the headline, so the flagship prints last); EXECUTION order
# puts the flagship first so slow configs can't starve the headline of wall
# clock — see orchestrate().
#
# The DEFAULT invocation runs only the COMPACT subset below (the 15-config
# suite's worst-case budgets, ~5.5 h, do not fit a driver's capture window:
# BENCH_r05 recorded rc:124 with an empty tail).  GGRS_BENCH_FULL=1 restores
# the full suite.
_CPU = {"JAX_PLATFORMS": "cpu"}  # a host proxy: never a per-chip number
CONFIGS = {
    "host_cd2": ("run_host_cd2", 600, _CPU),
    "host_datapath": ("run_host_datapath", 600, _CPU),
    "spec_p2p": ("run_spec_p2p", 1500),
    # the same speculation measurement on the CPU backend
    "spec_p2p_cpu": (
        "run_spec_p2p", 900,
        {**_CPU, "GGRS_BENCH_METRIC_PREFIX": "cpubackend_"},
    ),
    "ecs": ("run_ecs", 1800),
    "chipvm256": ("run_chipvm256", 1800),
    "pallas_checksum": ("run_pallas_checksum", 1200),
    "spec_width": ("run_spec_width", 1200),
    "batch_sweep": ("run_batch_sweep", 1800),
    # the sweep's biggest B validated on the virtual 8-device CPU mesh
    "batch_sweep_mesh": (
        "run_batch_sweep_mesh", 900,
        {**_CPU, "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    ),
    "pool_hosting": ("run_pool_hosting", 1500),
    "pool_capacity": ("run_pool_capacity", 1800),
    "soak": ("run_soak", 1500),
    "pool_capacity_cpu": (
        "run_pool_capacity", 1200,
        {**_CPU, "GGRS_BENCH_METRIC_PREFIX": "cpubackend_"},
    ),
    # the native session bank (one C++ crossing per pool tick for ALL
    # sessions' protocol+sync mechanism): 4-peer tick vs the 0.25 ms target
    # and the pooled capacity ramp, on the CPU-backend host proxy
    "host_bank": ("run_host_bank", 900, _CPU),
    # the supervised bank running DEGRADED: 1/8 of slots quarantined and
    # evicted to per-session Python sessions (the fault-isolation layer's
    # steady state after real faults) vs the all-native pool
    "host_bank_degraded": ("run_host_bank_degraded", 900, _CPU),
    # broadcast fan-out (DESIGN.md §13): one bank-hosted match fanning its
    # confirmed-input stream to {8, 64} real spectator sessions — p99 pool
    # tick and wire bytes per viewer, on the CPU-backend host proxy
    "broadcast_fanout": ("run_broadcast_fanout", 900, _CPU),
    # the kernel-batched socket datapath (DESIGN.md §15): B=64 matches
    # over real loopback UDP with per-match viewer fan-out — socket
    # syscalls per pool tick and host-loop p99, native_io on vs off
    "host_bank_io": ("run_host_bank_io", 900, _CPU),
    # the vectorized policy plane (DESIGN.md §19): capacity sweep
    # B=64/128/256/512 matches with knee detection, fast-path coverage,
    # vectorized-vs-legacy decode p99, per-phase attribution, and the
    # serving GC posture (freeze after warmup) priced explicitly
    "host_bank_capacity": ("run_host_bank_capacity", 900, _CPU),
    # datapath gen 2 (DESIGN.md §23): the one-crossing inbound drain and
    # the shared dispatch socket — B=512/1024 inbound A/B (batched and
    # dispatch vs the per-slot reference drain), inbound syscalls per
    # pool tick and host-loop p99
    "inbound_gen2": ("run_inbound_gen2", 900, _CPU),
    # parallel slow-slot decode + GRO inbound (DESIGN.md §24): the
    # inbound_gen2 population with the decode backend and GRO toggled
    # independently — B=256/512/1024 host p99 per posture, syscalls
    # gro-on vs gro-off, decode-plane engagement counters
    "decode_parallel": ("run_decode_parallel", 900, _CPU),
    # the input plane (DESIGN.md §27): B=256 pooled matches with fixed
    # 4-byte uint inputs vs variable-size command records in the varrec
    # envelope — host tick p99 and wire bytes/tick, payload-vs-envelope
    # accounting, native engagement named per leg
    "input_plane": ("run_input_plane", 900, _CPU),
    "flagship": ("run_flagship", 900),
}

# The configs that measure the chip: main() refuses to run them without a
# TPU of a known kind (require_chip) — no CPU sizes, no skip.
DEVICE_CONFIGS = frozenset({
    "flagship", "ecs", "chipvm256", "batch_sweep", "pool_hosting",
    "pool_capacity", "spec_p2p", "spec_width", "soak", "pallas_checksum",
})

# The default subset: sized so the driver's capture window always sees the
# flagship line.  BENCH_r05 recorded rc=124 with an EMPTY tail against the
# round-5 suite, and the round-6 six-config compact subset still summed to
# a 7200 s worst case — far past any driver window — so the default is
# three configs (worst-case budgets 1500 s) under a hard total deadline
# (GGRS_BENCH_TOTAL_BUDGET, default 420 s) that clamps every child's
# budget to the time actually remaining.  A config that does not fit is
# reported on stderr and makes the run exit nonzero, and every child's
# metric lines stream to stdout the moment the child prints them, so even
# a driver that kills the orchestrator mid-run has captured everything
# measured so far.  GGRS_BENCH_FULL=1 restores the full suite (no default
# deadline).
COMPACT_CONFIGS = (
    "host_cd2",
    "host_bank",
    "flagship",
)

# Compact-run deadline: leave generous headroom inside the shortest
# plausible driver capture window (the tier-1 harness uses ~870 s).
DEFAULT_TOTAL_BUDGET_S = 420


def _inputs(n: int, players: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 16, size=(n, players)).astype(np.uint8)


# children run with a metric prefix when one measurement is repeated under a
# different backend (e.g. "cpubackend_" for the CPU-dispatch speculation run)
_METRIC_PREFIX = os.environ.get("GGRS_BENCH_METRIC_PREFIX", "")


def emit(metric: str, value: float, unit: str, vs_baseline: float,
         obs: Optional[dict] = None) -> None:
    dev = device_record()  # the device this child measured on
    record = {
        "metric": _METRIC_PREFIX + metric,
        # small values (roofline fractions, ratios) need the digits
        "value": round(value, 1) if abs(value) >= 10 else round(value, 5),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 2),
        "platform": dev["platform"],
        "device_kind": dev["kind"],
        "device_count": dev["count"],
    }
    if obs is not None:
        # obs metrics snapshot (ggrs_tpu.obs.json_snapshot shape) — rides
        # into bench_out/latest.json with the metric it annotates
        record["obs"] = obs
    print(json.dumps(record), flush=True)


def fail(reason: str) -> None:
    """A config that cannot measure what it names fails its child (nonzero
    exit, the reason on stderr): there is no skip, and a fallback tier is
    never presented under the native or per-chip metric's name."""
    raise SystemExit(f"bench: FAIL: {reason}")


def _require_native_bank(config: str) -> None:
    """Fail ``config`` unless the native session bank loads, saying why:
    the kill switch, or the build's error with the compiler's output."""
    from ggrs_tpu.net import _native

    # env check FIRST: bank_lib() would g++-build the library the user
    # explicitly disabled
    if os.environ.get("GGRS_TPU_NO_NATIVE"):
        fail(f"{config}: GGRS_TPU_NO_NATIVE is set")
    if _native.bank_lib() is None:
        fail(f"{config}: native bank unavailable: {_native.load_error()}")


def _obs_counters_snapshot(registry) -> dict:
    """The registry's counter/histogram families as a compact snapshot —
    per-slot/per-endpoint scrape gauges are dropped (at B=64 matches they
    are ~1k samples of point-in-time noise; the counters are the record)."""
    from ggrs_tpu.obs import json_snapshot

    return {
        name: fam
        for name, fam in json_snapshot(registry).items()
        if not name.startswith(("ggrs_slot_", "ggrs_endpoint_"))
    }


# ---------------------------------------------------------------------------
# device synctest harness (configs 2 and 4)
# ---------------------------------------------------------------------------


def bench_device_synctest(
    advance, init_state, input_template, input_fn, d: int, total_ticks: int, chunk: int
) -> float:
    """Resim frames/sec through the fused device session.

    Inputs are pre-staged to device and the desync check deferred to the end:
    the timed loop contains zero host↔device data transfers, exactly how a
    throughput consumer would drive the session.  Completion IS awaited each
    pass (``block_until_ready``)."""
    sess = DeviceSyncTestSession(
        advance, init_state, input_template, check_distance=d, max_prediction=d
    )
    warm = input_fn(chunk, seed=100)
    sess.run_ticks(warm, check=False)  # warmup ticks + compiles both programs
    sess.run_ticks(warm, check=False)  # steady-state program now cached
    sess.block_until_ready()

    chunks = [
        jnp.asarray(input_fn(chunk, seed=i)) for i in range(total_ticks // chunk)
    ]
    jax.block_until_ready(chunks)

    # best of REPEATS passes (see REPEATS)
    best = 0.0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for staged in chunks:
            sess.run_ticks(staged, check=False)
        sess.block_until_ready()
        dt = time.perf_counter() - t0
        best = max(best, len(chunks) * chunk * d / dt)
    # zero desyncs required for the number to count; the caller runs verify()
    # (a D2H read) only after ALL device-timed configs have finished
    return best, sess.verify


# ---------------------------------------------------------------------------
# host request-loop harness (configs 1 and the vs_baseline denominators)
# ---------------------------------------------------------------------------


def bench_host_synctest(game, players: int, d: int, ticks: int, seed: int = 7) -> float:
    """Synctest semantics executed the reference's way: a Python request
    loop, one save/load/advance at a time, NumPy state."""
    state = game.init_state_np()
    saved = {}  # frame -> (state copy, checksum)
    history = {}
    inputs_by_frame = {}
    ins = _inputs(ticks, players, seed)

    def checksum(s):
        return zlib.crc32(b"".join(np.ascontiguousarray(v).tobytes() for v in s.values()))

    def copy(s):
        return {k: np.copy(v) for k, v in s.items()}

    t0 = time.perf_counter()
    resim_frames = 0
    for frame in range(ticks):
        inputs_by_frame[frame] = ins[frame]
        if frame > d:
            # verify window, then forced rollback: load + d×(save, advance)
            for f in range(frame - d, frame):
                if f in history and f in saved and saved[f][1] != history[f]:
                    raise AssertionError("desync in baseline")
            state = copy(saved[frame - d][0])
            for f in range(frame - d, frame):
                if f > frame - d:
                    saved[f] = (copy(state), checksum(state))
                state = game.advance_np(state, inputs_by_frame[f])
                resim_frames += 1
        cs = checksum(state)
        saved[frame] = (copy(state), cs)
        history.setdefault(frame, cs)
        state = game.advance_np(state, ins[frame])
        # drop data outside the ring, like the real session
        saved.pop(frame - d - 1, None)
        inputs_by_frame.pop(frame - d - 1, None)
    dt = time.perf_counter() - t0
    return max(resim_frames, 1) / dt


# ---------------------------------------------------------------------------
# config 3: speculative P2P (4 players, 8 branches)
# ---------------------------------------------------------------------------


def _speculative_p2p_setup(speculate: bool, game=None, programs=None) -> tuple:
    """Four P2P peers over the in-memory net, each fulfilling requests with a
    device executor; peer 0 optionally speculates with 8 branches.  Returns
    (tick_fn, executors).  Pass the same ``game`` + shared ``ExecutorPrograms``
    to both variants so all eight executors compile the burst/advance programs
    once."""
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.ops import DeviceRequestExecutor, ExecutorPrograms
    from ggrs_tpu.parallel import SpeculativeRollback
    from ggrs_tpu.sessions import SessionBuilder

    if game is None:
        game = BoxGame(4)
    peers = ["P0", "P1", "P2", "P3"]
    max_prediction = 8  # BASELINE config 3: 8-frame prediction window
    if programs is None:
        programs = ExecutorPrograms(game.advance, with_checksums=False)

    def sched(player, i):
        return ((i + player) // 3) % 16  # transitions force regular rollbacks

    # NumPy end to end on the host side: inputs_to_array and branch_inputs
    # never touch the device, so hypothesis construction costs no dispatches
    # (H2D happens once per fused call inside the executor/speculation)
    def to_arr(pairs):
        return np.asarray([p[0] for p in pairs], np.uint8)

    def branch_inputs(k, frame, arr):
        out = np.array(arr, np.uint8, copy=True)
        if k < 7:
            out[1:] = np.uint8(k)
        else:
            out[1:] = [sched(p, frame) for p in (1, 2, 3)]
        return out

    hyp_base = np.zeros((8, 4), np.uint8)
    hyp_base[:7, 1:] = np.arange(7, dtype=np.uint8)[:, None]

    def branch_inputs_all(frame, arr):
        # vectorized: all 8 hypotheses in one [K, players] array build
        out = hyp_base.copy()
        out[:, 0] = arr[0]
        out[7, 1:] = [sched(p, frame) for p in (1, 2, 3)]
        return out

    net = InMemoryNetwork()
    sessions, executors = [], []
    for me in range(4):
        b = (
            SessionBuilder(boxgame_config())
            .with_num_players(4)
            .with_max_prediction_window(max_prediction)
            .with_clock(lambda: 0)
            .with_rng(random.Random(91 + me))
        )
        for p in range(4):
            b = b.add_player(Local() if p == me else Remote(peers[p]), p)
        sessions.append(b.start_p2p_session(net.socket(peers[me])))
        spec = (
            SpeculativeRollback(
                game.advance, 8, branch_inputs, max_window=8,
                branch_inputs_all=branch_inputs_all,
            )
            if (speculate and me == 0)
            else None
        )
        ex = DeviceRequestExecutor(
            game.advance, game.init_state(), to_arr,
            with_checksums=False, speculation=spec, programs=programs,
        )
        # pre-compile everything (advance, bursts, speculation programs):
        # no jit compile may land inside the timed loop; the deepest burst
        # is max_prediction resim pairs + the trailing live advance
        ex.warmup(
            np.zeros((4,), np.uint8),
            burst_depths=range(2, max_prediction + 2),
        )
        executors.append(ex)

    from ggrs_tpu.core.types import LoadGameState

    def tick(i):
        """One tick of all four peers; True when peer 0's request list
        carried a rollback (a Load) — the ticks whose latency the
        speculation design claims to improve."""
        rolled = False
        for s in sessions:
            s.poll_remote_clients()
        for p, (s, ex) in enumerate(zip(sessions, executors)):
            s.add_local_input(p, sched(p, i))
            reqs = s.advance_frame()
            if p == 0 and any(isinstance(r, LoadGameState) for r in reqs):
                rolled = True
            ex.run(reqs)
        return rolled

    return tick, executors


def bench_speculative_p2p(seg_ticks: int = 100, segments: int = 4) -> tuple:
    """Time the speculative and plain variants in ALTERNATING segments so any
    drift of the machine hits both equally, and take each variant's best
    segment.  Returns (spec_rate, plain_rate,
    fetch_stats, latencies); ``fetch_stats()`` reads the device hit counter
    (a D2H transfer), deferred until after the timed segments purely to keep
    data transfers out of the loops."""
    from ggrs_tpu.ops import ExecutorPrograms

    game = BoxGame(4)
    shared = ExecutorPrograms(game.advance, with_checksums=False)
    variants = {
        name: _speculative_p2p_setup(
            speculate=(name == "spec"), game=game, programs=shared
        )
        for name in ("spec", "plain")
    }
    counters = {name: 0 for name in variants}
    rates = {name: [] for name in variants}

    def run(name, n):
        tick, executors = variants[name]
        start = counters[name]
        for i in range(start, start + n):
            tick(i)
        jax.block_until_ready([ex.state for ex in executors])
        counters[name] = start + n

    for name in variants:
        run(name, 24)  # warm caches (compiles were handled by warmup())

    for _ in range(segments):
        for name in variants:
            t0 = time.perf_counter()
            run(name, seg_ticks)
            rates[name].append(seg_ticks / (time.perf_counter() - t0))

    # ---- latency phase: per-tick wall time with the
    # state actually materialized each tick (block_until_ready), so a
    # rollback's stall is measured to COMPLETION, not to enqueue.  Alternate
    # segments again so drift hits both variants equally.
    latencies = {n: {"tick": [], "roll": []} for n in variants}

    def run_latency(name, n):
        tick, executors = variants[name]
        ex0 = executors[0]
        start = counters[name]
        for i in range(start, start + n):
            t0 = time.perf_counter()
            rolled = tick(i)
            jax.block_until_ready(ex0.state)
            dt = time.perf_counter() - t0
            latencies[name]["tick"].append(dt)
            if rolled:
                latencies[name]["roll"].append(dt)
        counters[name] = start + n

    # a p99 needs samples: the top percentile of N ticks is ~N/100 events,
    # so 300 ticks gave a 3-sample p99 that flipped run to run
    seg, rounds = 600, 4
    for name in variants:
        run_latency(name, 16)  # settle into the per-tick-blocking regime
        latencies[name] = {"tick": [], "roll": []}
    for _ in range(rounds):
        for name in variants:
            run_latency(name, seg)

    ex0 = variants["spec"][1][0]

    def fetch_stats():
        return ex0.spec_hits + ex0.spec_misses, ex0.spec_hits

    return max(rates["spec"]), max(rates["plain"]), fetch_stats, latencies


# ---------------------------------------------------------------------------
# config 5: massed batched sessions
# ---------------------------------------------------------------------------


def bench_batched_chipvm(
    batch: int,
    total_ticks: int,
    chunk: int,
    d: int,
    mesh_devices: int = 1,
    repeats: int = REPEATS,
) -> Tuple[float, Any, float, float]:
    """(agg resim f/s, verify fn, compile+warmup sec, carry MiB) across
    ``batch`` independent ChipVM synctest sessions (shard_map over a
    ``mesh_devices``-device mesh — the same program the 8-chip dry-run
    validates).  ``repeats=0`` skips the timed passes entirely
    (correctness-only dryruns) and reports rate 0."""
    from ggrs_tpu.parallel import BatchedSessions, make_mesh

    vm = ChipVM(2)
    t_compile0 = time.perf_counter()
    batched = BatchedSessions(
        vm.advance,
        vm.init_state(),
        jnp.zeros((2,), jnp.uint8),
        batch_size=batch,
        mesh=make_mesh(mesh_devices),
        check_distance=d,
        max_prediction=d,
    )
    def chunk_inputs(seed):
        return jnp.asarray(
            np.random.default_rng(seed).integers(
                0, 256, size=(batch, chunk, 2)
            ).astype(np.uint8)
        )

    batched.run_ticks(chunk_inputs(100), check=False)  # warmup ticks + compiles
    batched.run_ticks(chunk_inputs(101), check=False)  # full-chunk steady program
    batched.block_until_ready()
    compile_sec = time.perf_counter() - t_compile0
    carry_mb = _tree_nbytes(batched._carry) / 2**20

    staged = [chunk_inputs(i) for i in range(total_ticks // chunk)]
    jax.block_until_ready(staged)

    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        for c in staged:
            batched.run_ticks(c, check=False)  # fully async: no D2H inside
        batched.block_until_ready()
        dt = time.perf_counter() - t0
        best = max(best, batch * len(staged) * chunk * d / dt)

    def verify():
        assert batched.verify()["mismatches"] == 0

    return best, verify, compile_sec, carry_mb


# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# per-config entry points (each runs in its own process; see module docstring)
# ---------------------------------------------------------------------------


def _tree_nbytes(tree) -> int:
    return sum(
        np.asarray(l).nbytes for l in jax.tree_util.tree_leaves(tree)
    )


def emit_hbm_grounding(prefix: str, traffic_bytes_per_sec: float) -> None:
    """Ground a throughput number against the chip: modeled REQUIRED HBM
    traffic (ring writes + input reads; an upper bound — working sets
    smaller than VMEM may never touch HBM at all) as a fraction of the
    device's spec-sheet peak.  A fraction far below 1 states honestly that
    the config is dispatch/compute-bound on this silicon, not
    bandwidth-bound."""
    kind = device_record()["kind"]
    peaks = device_peaks(kind)  # an unknown device is an error
    pct = 100.0 * traffic_bytes_per_sec / 1e9 / peaks["hbm_gbs"]
    emit(
        f"{prefix}_modeled_hbm_traffic_pct_of_peak", pct,
        f"% of {peaks['hbm_gbs']:.0f}GB/s HBM peak ({kind}); modeled "
        f"required traffic, upper bound", 0.0,
    )


def run_host_cd2() -> None:
    """Config 1: the reference-shaped CPU request loop — the 1× denominator."""
    host_cd2 = bench_host_synctest(BoxGame(PLAYERS), PLAYERS, d=2, ticks=600)
    emit("boxgame_synctest_host_resim_frames_per_sec_cd2", host_cd2,
         "resim_frames/sec", 1.0)


def _four_peer_population():
    """THE single definition of the 4-peer host-tick scenario (names, rng
    seeds; inputs come from ``_four_peer_input``): yields
    ``(builder, socket)`` per peer.  ``host_datapath`` and ``host_bank``
    both consume it, so their numbers stay comparable."""
    import random as _random

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.sessions import SessionBuilder

    P = 4
    net = InMemoryNetwork()
    names = [f"N{h}" for h in range(P)]
    for h in range(P):
        b = (
            SessionBuilder(boxgame_config())
            .with_num_players(P)
            .with_clock(lambda: 0)
            .with_rng(_random.Random(40 + h))
        )
        for o in range(P):
            b = b.add_player(Local() if o == h else Remote(names[o]), o)
        yield b, net.socket(names[h])


def _four_peer_input(i: int, h: int) -> int:
    return (i * 7 + h) % 16


def run_host_datapath() -> None:
    """Host-tick microbench: four live P2P peers over
    the in-memory net with trivial (host, no-device) request fulfillment —
    pure session + endpoint-datapath cost, the number that bounds massed
    hosting.  ``vs_baseline`` is round 3's recorded 1.17 ms/tick over the
    measured value (>1 = faster than round 3's host path)."""
    R3_US_PER_TICK = 1170.0  # docs/DESIGN.md §10, BENCH_r03 era measurement

    sessions = [
        b.start_p2p_session(sock) for b, sock in _four_peer_population()
    ]
    state = [0] * len(sessions)

    def drive(ticks, base):
        for i in range(base, base + ticks):
            for s in sessions:
                s.poll_remote_clients()
            for h, s in enumerate(sessions):
                s.add_local_input(h, _four_peer_input(i, h))
                for r in s.advance_frame():
                    k = type(r).__name__
                    if k == "SaveGameState":
                        r.cell.save(r.frame, state[h], None)
                    elif k == "LoadGameState":
                        state[h] = r.cell.data()

    drive(200, 0)  # warm
    n, base = 2000, 200
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        drive(n, base)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
        base += n
    emit("p2p4_host_datapath_us_per_tick", best, "us/tick (4 sessions)",
         R3_US_PER_TICK / best if best else 0.0)


def run_spec_p2p() -> None:
    """Config 3: speculative P2P vs the same loop with speculation off —
    throughput AND per-tick latency distributions (the axis the speculation
    design actually targets: branch-select vs an 8-deep serial resim chain
    on rollback ticks).  The whole live path performs zero D2H, so both
    variants run at full dispatch rate; the stats fetch (a D2H read)
    happens after all timing."""
    spec_rate, plain_rate, fetch_spec_stats, lat = bench_speculative_p2p()

    # latency lines first (the throughput line stays the config headline).
    # For spec lines vs_baseline is plain/spec (>1 = speculation is FASTER
    # on that percentile); plain lines carry 1.0.
    pcts = {"p50": 50, "p99": 99}
    kinds = [("rollback_stall", "roll")]
    if any(len(lat[n]["roll"]) < len(lat[n]["tick"]) for n in lat):
        # only when some ticks did NOT roll back is the all-ticks
        # distribution a distinct measurement
        kinds.append(("tick_latency", "tick"))
    for kind, key in kinds:
        vals = {n: np.asarray(lat[n][key]) * 1e6 for n in lat}  # µs
        if any(v.size == 0 for v in vals.values()):
            continue
        stats = {
            n: {
                **{p: float(np.percentile(v, q)) for p, q in pcts.items()},
                "max": float(v.max()),
            }
            for n, v in vals.items()
        }
        for p in list(pcts) + ["max"]:
            emit(f"p2p4_plain_{kind}_us_{p}", stats["plain"][p],
                 "us/tick" if key == "tick" else "us/rollback-tick", 1.0)
            emit(f"p2p4_spec_{kind}_us_{p}", stats["spec"][p],
                 "us/tick" if key == "tick" else "us/rollback-tick",
                 stats["plain"][p] / stats["spec"][p]
                 if stats["spec"][p] else 0.0)

    rollbacks, hits = fetch_spec_stats()
    emit("p2p4_speculative_8branch_ticks_per_sec", spec_rate,
         f"ticks/sec (hit {hits}/{rollbacks} rollbacks)"
         if rollbacks else "ticks/sec",
         spec_rate / plain_rate if plain_rate else 0.0)


def run_ecs() -> None:
    """Config 4: EcsWorld, 4 players, 16-frame rollback window."""
    ecs = EcsWorld(4, entities_per_player=32)
    ticks4, chunk4 = 4096, 512
    ecs_fps, verify4 = bench_device_synctest(
        ecs.advance, ecs.init_state(), jnp.zeros((4,), jnp.uint8),
        lambda n, seed: _inputs(n, 4, seed), 16, ticks4, chunk4,
    )
    verify4()  # D2H desync gate — after timing
    ecs_host = bench_host_synctest(ecs, 4, d=16, ticks=300)
    emit("ecs_synctest_resim_frames_per_sec_cd16", ecs_fps,
         "resim_frames/sec", ecs_fps / ecs_host)
    state_b = _tree_nbytes(ecs.init_state())
    emit_hbm_grounding("ecs_synctest", (ecs_fps / 16) * (2 * state_b + 16 + 4))


def run_chipvm256() -> None:
    """Config 5: 256 concurrent ChipVM sessions batched on one chip."""
    ticks5, chunk5 = 1024, 256
    vm_rate, verify5, _, _ = bench_batched_chipvm(256, ticks5, chunk5, d=8)
    verify5()  # D2H desync gate — after timing
    vm_host = bench_host_synctest(ChipVM(2), 2, d=8, ticks=300)
    emit("chipvm_256sessions_resim_frames_per_sec", vm_rate,
         "resim_frames/sec", vm_rate / vm_host)
    state_b = _tree_nbytes(ChipVM(2).init_state())
    emit_hbm_grounding("chipvm_256sessions", (vm_rate / 8) * (2 * state_b + 16 + 2))


def run_batch_sweep_mesh() -> None:
    """The batch sweep's biggest B (16384 ChipVM sessions) over the virtual
    8-device CPU mesh: correctness only — CPU timing of 16k sessions means
    nothing, and a virtual mesh says nothing about real chips."""
    if len(jax.devices()) < 8:
        fail("batch_sweep_mesh needs the 8-device virtual mesh "
             "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    B = 16384
    _, verify, _, carry_mb = bench_batched_chipvm(
        B, total_ticks=8, chunk=4, d=8, mesh_devices=8, repeats=0,
    )
    verify()
    emit(
        f"chipvm_sweep_b{B}_virtual_mesh8_ok", 1.0,
        f"16384 sessions over 8 virtual devices, zero "
        f"mismatches ({carry_mb:.0f} MiB carry)",
        1.0,
    )


def run_batch_sweep() -> None:
    """Sweep the batch axis to its knee.

    B = 256 / 1024 / 4096 / 16384 ChipVM sessions on one chip, per-B
    aggregate resim f/s + compile time + carry HBM footprint.  Tick counts
    halve as B quadruples (bounding per-B wall time to ~2× the previous
    step even at perfect scaling); the knee is read off the REPORTED
    per-session rates, which divide by measured time and are plan-shape
    independent."""
    plan = [(256, 1024, 256), (1024, 512, 128), (4096, 256, 64), (16384, 128, 32)]
    per_session_256 = None
    best_agg = 0.0
    for B, ticks, chunk in plan:
        rate, verify, compile_sec, carry_mb = bench_batched_chipvm(
            B, ticks, chunk, d=8
        )
        verify()
        best_agg = max(best_agg, rate)
        per_session = rate / B
        if per_session_256 is None:
            per_session_256 = per_session
        emit(
            f"chipvm_sweep_b{B}_resim_frames_per_sec", rate,
            f"agg resim f/s ({per_session:.0f}/session, compile "
            f"{compile_sec:.1f}s, carry {carry_mb:.1f} MiB)",
            per_session / per_session_256,
        )
    # a 60 Hz session at d=8 consumes 480 resim f/s; the saturated aggregate
    # bounds how many device-resident synctest-style sessions one chip's
    # COMPUTE sustains (the pool_hosting config bounds the host side)
    emit(
        "chipvm_sweep_60hz_device_session_ceiling", best_agg / (60 * 8),
        "sessions/chip (saturated agg / 480 resim f/s)", 1.0,
    )


def run_pallas_checksum() -> None:
    """Supplemental: the pallas single-pass digest vs the XLA lane formulas
    on a 256 MiB state leaf — the per-save hot op at large-state scale.
    ``vs_baseline`` is pallas GB/s over XLA GB/s (>1 = the kernel wins).

    The leaf is sized ABOVE the chip's ~128 MiB VMEM so the measurement
    actually streams from HBM: round 3 used a 64 MiB leaf and recorded
    2627 GB/s — over 3x the v5e's 819 GB/s HBM peak — because the whole
    working set stayed VMEM-resident across the timed passes.  A
    pct-of-HBM-peak line grounds the reading against the spec sheet."""
    from ggrs_tpu.ops import pallas_checksum as pc
    from ggrs_tpu.ops.checksum import _leaf_digest

    words = jnp.asarray(
        np.random.default_rng(3).integers(
            0, 2**32, size=(64 * 1024 * 1024,), dtype=np.uint32
        )
    )
    nbytes = words.size * 4

    pallas_fn = jax.jit(pc.leaf_digest_pallas)
    # pin the baseline to the pure-XLA lanes even if the caller exported
    # GGRS_TPU_PALLAS_CHECKSUM=on (else this benchmark compares pallas to
    # itself and the lane-equality assert below is vacuous)
    pc.use_pallas_checksums(False)
    xla_fn = jax.jit(_leaf_digest)

    a, b = pallas_fn(words), xla_fn(words)
    jax.block_until_ready((a, b))

    def rate(fn) -> float:
        # 60 passes per fenced segment
        best = 0.0
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            out = [fn(words) for _ in range(60)]
            jax.block_until_ready(out)
            best = max(best, 60 * nbytes / (time.perf_counter() - t0))
        return best

    pallas_gbs = rate(pallas_fn) / 1e9
    xla_gbs = rate(xla_fn) / 1e9
    assert np.array_equal(np.asarray(a), np.asarray(b)), "lane mismatch"
    emit("pallas_checksum_digest_gb_per_sec", pallas_gbs, "GB/s (256MiB leaf)",
         pallas_gbs / xla_gbs if xla_gbs else 0.0)
    kind = device_record()["kind"]
    peaks = device_peaks(kind)
    best_gbs = max(pallas_gbs, xla_gbs)
    emit("checksum_digest_pct_of_hbm_peak",
         100.0 * best_gbs / peaks["hbm_gbs"],
         f"% of {peaks['hbm_gbs']:.0f}GB/s HBM peak ({kind}); 256MiB leaf "
         f"streams from HBM",
         0.0)


def _match_population(n_matches: int):
    """THE single definition of the hosting benches' match population:
    yields ``(builder, socket, schedule)`` per session — names, rng seeds,
    and input schedules that every hosting variant (per-session, pooled,
    host-bank) must share so their numbers stay comparable."""
    import random

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.sessions import SessionBuilder

    net = InMemoryNetwork()
    for m in range(n_matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            b = (
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(3 + 5 * m + me))
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            yield (
                b,
                net.socket(names[me]),
                lambda i, m=m, me=me: ((i + 2 * m + me) // (2 + m % 3)) % 16,
            )


def _build_matches(n_matches: int):
    """The per-session form of ``_match_population``: started P2PSessions."""
    sessions, schedules = [], []
    for b, sock, sched in _match_population(n_matches):
        sessions.append(b.start_p2p_session(sock))
        schedules.append(sched)
    return sessions, schedules


def _pooled_matches_setup(n_matches: int):
    """n_matches 2-peer BoxGame matches over one in-memory net with ONE
    BatchedRequestExecutor fulfilling all 2·n sessions.  Returns
    (sessions, schedules, pool)."""
    from ggrs_tpu.parallel import BatchedRequestExecutor

    game = BoxGame(2)

    def to_arr(pairs):
        return np.asarray([p[0] for p in pairs], np.uint8)

    sessions, schedules = _build_matches(n_matches)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), to_arr,
        batch_size=len(sessions), ring_length=10, max_burst=9,
        with_checksums=False,
    )
    pool.warmup(np.zeros((2,), np.uint8))
    return sessions, schedules, pool


def _hosting_setup(n_matches: int, pooled: bool):
    """n_matches 2-peer BoxGame matches over one in-memory net; fulfillment
    is either ONE BatchedRequestExecutor for all 2·n sessions (pooled) or a
    per-session DeviceRequestExecutor pool sharing compiled programs.
    Returns (tick_fn, finalize_fn)."""
    from ggrs_tpu.ops import DeviceRequestExecutor, ExecutorPrograms

    game = BoxGame(2)

    def to_arr(pairs):
        return np.asarray([p[0] for p in pairs], np.uint8)

    if pooled:
        sessions, schedules, pool = _pooled_matches_setup(n_matches)

        def tick(i):
            for s in sessions:
                s.poll_remote_clients()
            reqs = []
            for h, (s, sched) in enumerate(zip(sessions, schedules)):
                s.add_local_input(h % 2, sched(i))
                reqs.append(s.advance_frame())
            pool.run(reqs)

        return tick, pool.block_until_ready

    sessions, schedules = _build_matches(n_matches)
    B = len(sessions)

    programs = ExecutorPrograms(game.advance, with_checksums=False)
    executors = [
        DeviceRequestExecutor(
            game.advance, game.init_state(), to_arr,
            with_checksums=False, programs=programs,
        )
        for _ in range(B)
    ]
    executors[0].warmup(np.zeros((2,), np.uint8), burst_depths=range(2, 10))

    def tick(i):
        for s in sessions:
            s.poll_remote_clients()
        for h, (s, sched, ex) in enumerate(zip(sessions, schedules, executors)):
            s.add_local_input(h % 2, sched(i))
            ex.run(s.advance_frame())

    def finalize():
        jax.block_until_ready([ex.state for ex in executors])

    return tick, finalize


def p2p_soak(frames: int, periodic=None) -> dict:
    """THE long-horizon two-peer harness, shared verbatim by the bench soak
    line and tests/test_soak.py so both certify the same behavior: 2 peers
    over the seeded fault net, desync detection on, rolling bit-exact
    comparison of every settled frame (a frame's first save may be
    speculative — the LAST save wins, compared once both peers are
    max_prediction+1 past it, then forgotten so memory stays bounded).

    ``periodic(sessions, digests)`` runs every 10k frames for extra
    invariants (the test asserts queue bounds there).  Returns
    ``{"fps", "compared", "desyncs", "rss_drift_mb"}`` after asserting
    convergence itself."""
    import resource

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.types import DesyncDetection
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.sessions import SessionBuilder

    game = BoxGame(2)
    net = InMemoryNetwork(seed=1234, loss=0.08, duplicate=0.04, reorder=0.04)
    clock_now = [0]
    sessions = []
    for me in (0, 1):
        b = (
            SessionBuilder(boxgame_config())
            .with_desync_detection_mode(DesyncDetection.on(interval=100))
            .with_clock(lambda: clock_now[0])
            .with_rng(random.Random(77 + me))
            .add_player(Local(), me)
            .add_player(Remote(("peer", 1 - me)), 1 - me)
        )
        sessions.append(b.start_p2p_session(net.socket(("peer", me))))

    # settled = both peers advanced past the frame by the whole prediction
    # window, so no speculative save can still be pending for it
    horizon_slack = sessions[0]._max_prediction + 1
    states = [game.init_state_np(), game.init_state_np()]
    digests: list = [{}, {}]
    compared = [0]

    def digest(st) -> int:
        return zlib.crc32(
            b"".join(np.ascontiguousarray(v).tobytes() for v in st.values())
        )

    def compare_settled() -> None:
        horizon = min(s.current_frame for s in sessions) - horizon_slack
        for f in [f for f in digests[0] if f <= horizon]:
            if f in digests[1]:
                assert digests[0][f] == digests[1][f], (
                    f"state divergence at frame {f}"
                )
                del digests[1][f]
                compared[0] += 1
            del digests[0][f]

    def rss_mb() -> float:
        # CURRENT resident set, not ru_maxrss: the rusage value is a
        # process-lifetime high-water mark, so a pytest run whose earlier
        # device tests peaked higher would make the drift identically 0.0
        # and the leak certification vacuous
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1]) / 1024.0
        except OSError:
            pass
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    desyncs = 0
    rss_half = 0.0
    t0 = time.perf_counter()
    for i in range(frames):
        clock_now[0] += 16
        for me, s in enumerate(sessions):
            s.add_local_input(me, (i * 7 + me * 3) % 16)
            for r in s.advance_frame():
                k = type(r).__name__
                if k == "SaveGameState":
                    snap = {k2: v.copy() for k2, v in states[me].items()}
                    d = digest(snap)
                    r.cell.save(r.frame, snap, d)
                    digests[me][r.frame] = d  # last save wins
                elif k == "LoadGameState":
                    states[me] = {
                        k2: v.copy() for k2, v in r.cell.data().items()
                    }
                elif k == "AdvanceFrame":
                    inp = np.asarray([v for v, _ in r.inputs], np.uint8)
                    states[me] = game.advance_np(states[me], inp)
            desyncs += sum(
                1 for e in s.events()
                if type(e).__name__ == "DesyncDetected"
            )
        if i % 500 == 0:
            compare_settled()
        if i == frames // 2:
            rss_half = rss_mb()
        if periodic is not None and i % 10_000 == 0:
            periodic(sessions, digests)
    compare_settled()
    dt = time.perf_counter() - t0
    assert desyncs == 0, f"{desyncs} desync events over the soak"
    assert compared[0] > frames // 2, f"only {compared[0]} frames compared"
    assert all(s.current_frame >= frames - 64 for s in sessions), (
        "a peer stalled short of the horizon"
    )
    return {
        "fps": frames / dt,
        "compared": compared[0],
        "desyncs": desyncs,
        "rss_drift_mb": rss_mb() - rss_half,
    }


def pool_soak(ticks: int, n_matches: int = 4) -> dict:
    """Long-horizon pooled-hosting harness shared by bench and test: one
    BatchedRequestExecutor fulfilling 2·n_matches sessions for ``ticks``
    ticks (periodic fences), asserting every session reaches the horizon.
    Returns ``{"session_ticks_per_sec", "sessions", "ring_wraps"}``."""
    sessions, schedules, pool = _pooled_matches_setup(n_matches)
    n_sessions = len(sessions)
    t0 = time.perf_counter()
    for i in range(ticks):
        reqs = []
        for h, (s, sched) in enumerate(zip(sessions, schedules)):
            s.add_local_input(h % 2, sched(i))
            reqs.append(s.advance_frame())
        pool.run(reqs)
        if i % 2_000 == 0:
            pool.block_until_ready()
    pool.block_until_ready()
    dt = time.perf_counter() - t0
    assert all(s.current_frame >= ticks - 64 for s in sessions), (
        "a pooled session stalled short of the horizon"
    )
    for m in range(n_matches):
        fa = sessions[2 * m].current_frame
        fb = sessions[2 * m + 1].current_frame
        assert abs(fa - fb) <= sessions[0]._max_prediction
    return {
        "session_ticks_per_sec": n_sessions * ticks / dt,
        "sessions": n_sessions,
        "ring_wraps": ticks // 128,
    }


def run_soak() -> None:
    """Soak line: the long-horizon run as a recorded
    metric, certifying the bookkeeping doesn't leak or drift at horizons
    the reference never tests.  The harnesses are shared with
    tests/test_soak.py (p2p_soak / pool_soak above)."""
    FRAMES = 100_000
    stats = p2p_soak(FRAMES)
    emit(
        "soak_p2p_100k_frames_per_sec", stats["fps"],
        f"frames/sec sustained over 1e5 faulted frames ({stats['compared']} "
        f"settled frames bit-identical, 0 desyncs, RSS drift "
        f"{stats['rss_drift_mb']:.1f} MiB)",
        1.0,
    )
    # 2e4 pooled ticks: the input-ring wraparound horizons are crossed
    # ~156x, inside the config's budget at ~10 ms a tick
    ticks = 20_000
    pstats = pool_soak(ticks)
    emit(
        "soak_pool_session_ticks_per_sec", pstats["session_ticks_per_sec"],
        f"session_ticks/sec sustained over {ticks} pooled ticks "
        f"({pstats['sessions']} sessions, ~{pstats['ring_wraps']} "
        f"input-ring wraps/queue, all sessions at full horizon)",
        1.0,
    )


def run_pool_capacity() -> None:
    """THE capacity headline: how many live 60 Hz
    matches does one chip host?

    Ramps the pooled-hosting match count B; at each B, T ticks run with a
    per-tick completion fence (a real 60 Hz server must finish each tick's
    work inside its frame) and the per-tick wall-time distribution is
    recorded.  The capacity is the largest ramp step whose p99 tick time
    fits the 16.7 ms frame budget; at every step the tick is decomposed
    into host bookkeeping (sessions, input queues, request assembly) vs
    device fulfillment+fence, naming the limiting regime.  Runs on the chip
    and, as the pool_capacity_cpu child, on the CPU backend (a host proxy,
    prefixed ``cpubackend_``)."""
    frame_budget_ms = 1000.0 / 60.0
    T = 400
    depth = 8  # pipelined mode: fence the tick from `depth` ago — results
    #            become observable <= depth frames late (the rollback window;
    #            simulation itself stays device-resident and real-time)
    ramp = [16, 32, 64, 128, 256, 512]
    max_ok = {"strict": 0, "pipelined": 0}
    knee_stats = {}
    tick_counter = [0]
    for B in ramp:
        sessions, schedules, pool = _pooled_matches_setup(B)
        tick_counter[0] = 0
        fence_queue: list = []

        def tick(mode):
            i = tick_counter[0]
            tick_counter[0] = i + 1
            t0 = time.perf_counter()
            for s in sessions:
                s.poll_remote_clients()
            reqs = []
            for h, (s, sched) in enumerate(zip(sessions, schedules)):
                s.add_local_input(h % 2, sched(i))
                reqs.append(s.advance_frame())
            t1 = time.perf_counter()
            pool.run(reqs)
            if mode == "strict":
                pool.block_until_ready()
            else:
                # fence marker: a fresh scalar DERIVED from this tick's
                # carry.  Blocking on the carry leaf itself would block on
                # a buffer the NEXT tick donates back to the runtime
                # (session_pool jits with donate_argnums on TPU) — a
                # deleted-array error waiting to happen.  The derived sum
                # is donated nowhere, and fencing it fences the tick that
                # produced its operand.
                marker = jnp.sum(
                    jax.tree_util.tree_leaves(pool.live_states)[0]
                )
                fence_queue.append(marker)
                if len(fence_queue) > depth:
                    jax.block_until_ready(fence_queue.pop(0))
            t2 = time.perf_counter()
            return (t1 - t0) * 1e3, (t2 - t1) * 1e3

        for _ in range(16):
            tick("strict")
        for mode in ("strict", "pipelined"):
            if mode in knee_stats:
                continue  # past its knee at a smaller B: a noisy pass at a
                #           larger B must not overwrite max_ok upward
            # best-of-REPEATS distributions: a single 400-tick pass on the
            # shared box swings p99 by ±40% with ambient load; the pass
            # least polluted by contention is the honest capacity estimate
            # (same policy as every other timed config here)
            best = None
            for _ in range(REPEATS):
                host_ms = np.empty(T)
                dev_ms = np.empty(T)
                for i in range(T):
                    host_ms[i], dev_ms[i] = tick(mode)
                pool.block_until_ready()  # drain between passes
                fence_queue.clear()
                total = host_ms + dev_ms
                p50 = float(np.percentile(total, 50))
                p99 = float(np.percentile(total, 99))
                host_frac = float(np.median(host_ms / total))
                if best is None or p99 < best[1]:
                    best = (p50, p99, host_frac)
            p50, p99, host_frac = best
            tag = "" if mode == "strict" else f"_pipelined{depth}"
            emit(
                f"pool_capacity_b{B}{tag}_tick_ms_p99", p99,
                f"ms/tick p99, best of {REPEATS}x{T}-tick passes, {mode} "
                f"fence (p50 {p50:.2f} ms, host fraction {host_frac:.2f})",
                frame_budget_ms / p99,
            )
            if p99 <= frame_budget_ms:
                max_ok[mode] = B
            else:
                knee_stats[mode] = (B, host_frac)
        del sessions, schedules, pool
        if all(m in knee_stats for m in ("strict", "pipelined")):
            break

    for mode in ("strict", "pipelined"):
        regime = ""
        if mode in knee_stats:
            b_knee, host_frac = knee_stats[mode]
            regime = (
                f"; knee at B={b_knee}, limiting regime "
                f"{'host bookkeeping' if host_frac > 0.5 else 'device fulfillment+fence'}"
                f" ({host_frac:.0%} host)"
            )
        tag = "" if mode == "strict" else f"_pipelined{depth}"
        emit(
            f"pool_max_60hz_matches_per_chip{tag}", float(max_ok[mode]),
            f"matches (2 sessions each) with p99 tick <= 16.7 ms, {mode} "
            f"fence{regime}",
            1.0,
        )


def run_spec_width() -> None:
    """The K-branch width ratio DESIGN §5 called unverifiable — measured.

    The question: does advancing K vmapped branch hypotheses alongside the
    live state cost ~the wall time of one advance (spare parallel width, the
    TPU's proposition) or ~K× (serialized)?  Per-tick host dispatches mix
    dispatch overhead into the answer, so this scans T ticks of the
    branch-upkeep program — live advance + vmapped
    K-branch advance + the window-ring write, the device body of
    ``SpeculativeRollback.advance_and_extend`` — in ONE program per dispatch,
    fenced once, against the identical scan of the plain advance.
    ``spec_width_ratio_kK`` = t(K)/t(plain) per tick: 1.0 = branches ride
    free, K = fully serialized."""
    game = BoxGame(PLAYERS)
    T = 4096  # ticks per dispatch
    dispatches, window = 4, 64
    inps = jnp.asarray(_inputs(T, PLAYERS, seed=17))
    st0 = jax.tree_util.tree_map(
        lambda l: jnp.array(l, copy=True), game.init_state()
    )

    def plain_scan(st, xs):
        return jax.lax.scan(lambda s, x: (game.advance(s, x), None), st, xs)[0]

    def make_width_scan(K: int):
        # K hypotheses: local player's real input, remote held at candidate k
        cands = jnp.arange(K, dtype=jnp.uint8)

        def body(carry, xs):
            live, branches, ring = carry
            inp, i = xs
            live = game.advance(live, inp)
            inp_k = jnp.stack(
                [jnp.broadcast_to(inp[0], (K,)), cands], axis=1
            ).astype(jnp.uint8)
            branches = jax.vmap(game.advance)(branches, inp_k)
            slot = jax.lax.rem(i, jnp.int32(window))
            ring = jax.tree_util.tree_map(
                lambda buf, leaf: jax.lax.dynamic_update_index_in_dim(
                    buf, leaf, slot, axis=0
                ),
                ring,
                branches,
            )
            return (live, branches, ring), None

        def run(st, xs):
            branches0 = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l[None], (K,) + l.shape).copy(), st
            )
            ring0 = jax.tree_util.tree_map(
                lambda l: jnp.zeros((window,) + l.shape, l.dtype), branches0
            )
            out, _ = jax.lax.scan(body, (st, branches0, ring0), xs)
            # return the FULL carry: returning only the live state lets
            # XLA's while-loop simplifier dead-code-eliminate the branch
            # advances and ring writes entirely (verified via HLO cost
            # analysis: 0 dynamic-update-slices and ~2.5x fewer flops with
            # a live-only return), which would time plain against plain
            return out

        return run

    ticks_i = jnp.arange(T, dtype=jnp.int32)
    plain_j = jax.jit(plain_scan)
    jax.block_until_ready(plain_j(st0, inps))

    def timed(fn, xs) -> float:
        best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            out = None
            for _ in range(dispatches):
                out = fn(st0, xs)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best / (dispatches * T)  # seconds per tick

    t_plain = timed(plain_j, inps)
    emit("spec_width_plain_us_per_tick", t_plain * 1e6, "us/tick", 1.0)
    for K in (1, 2, 4, 8):
        wj = jax.jit(make_width_scan(K))
        jax.block_until_ready(wj(st0, (inps, ticks_i)))
        t_k = timed(wj, (inps, ticks_i))
        emit(
            f"spec_width_ratio_k{K}", t_k / t_plain,
            f"x plain advance per tick ({t_k*1e6:.2f} us/tick; 1.0 = "
            f"branches ride free, {K}.0 = serialized)",
            t_plain / t_k,
        )


def run_pool_hosting() -> None:
    """Supplemental: massed hosting — 32 live P2P matches (64 sessions) on
    one chip, every tick's 64 heterogeneous request lists fulfilled as ONE
    batched dispatch (parallel.BatchedRequestExecutor) vs one device
    executor per session.  Metric is aggregate session-ticks/sec;
    ``vs_baseline`` is pooled over per-session (>1 = batching wins)."""
    n_matches, seg, segments = 32, 60, 3
    variants = {
        name: _hosting_setup(n_matches, pooled=(name == "pooled"))
        for name in ("pooled", "individual")
    }
    counters = {name: 0 for name in variants}
    rates = {name: [] for name in variants}

    def run(name, n):
        tick, finalize = variants[name]
        start = counters[name]
        for i in range(start, start + n):
            tick(i)
        finalize()
        counters[name] = start + n

    for name in variants:
        run(name, 16)  # warm
    # alternate segments so drift hits both variants equally
    for _ in range(segments):
        for name in variants:
            t0 = time.perf_counter()
            run(name, seg)
            rates[name].append(
                2 * n_matches * seg / (time.perf_counter() - t0)
            )

    pooled, individual = max(rates["pooled"]), max(rates["individual"])
    emit("p2p_pool_hosting_64sessions_session_ticks_per_sec", pooled,
         "session_ticks/sec (one dispatch per tick)",
         pooled / individual if individual else 0.0)


def bench_bare_scan_floor(game, total_ticks: int, chunk: int) -> float:
    """The control: a bare ``jit(lax.scan(advance))`` —
    no ring, no digest, no history — run over the same advance-step count as
    the flagship's replay and credited at the same d-resim-frames-per-tick
    rate.  This measures the serial-scan physics floor; the flagship/floor
    ratio is the replay program's true overhead (scripts/floor_probe.py
    splits the remainder into digest and ring)."""
    d = CHECK_DISTANCE
    steps = (d + 1) * chunk  # same advance count per dispatch as the replay

    def body(st, inp):
        return game.advance(st, inp), None

    bare = jax.jit(lambda st, i: jax.lax.scan(body, st, i)[0])
    st0 = jax.tree_util.tree_map(
        lambda l: jnp.array(l, copy=True), game.init_state()
    )
    inps = jnp.asarray(_inputs(steps, PLAYERS, seed=41))
    jax.block_until_ready(bare(st0, inps))
    dispatches = max(1, total_ticks // chunk)
    best = 0.0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = None
        for _ in range(dispatches):
            out = bare(st0, inps)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        best = max(best, dispatches * chunk * d / dt)
    return best


def run_flagship() -> None:
    """Config 2 (flagship): BoxGame device synctest at cd=8, plus the
    bare-scan floor control that grounds the overhead accounting."""
    game = BoxGame(PLAYERS)
    total_ticks, chunk = 16384, 1024
    device_fps, verify2 = bench_device_synctest(
        game.advance, game.init_state(), jnp.zeros((PLAYERS,), jnp.uint8),
        lambda n, seed: _inputs(n, PLAYERS, seed),
        CHECK_DISTANCE, total_ticks, chunk,
    )
    verify2()  # D2H desync gate — after timing
    floor_fps = bench_bare_scan_floor(game, total_ticks // 2, chunk)
    host_fps = bench_host_synctest(game, PLAYERS, d=CHECK_DISTANCE, ticks=600)
    state_b = _tree_nbytes(game.init_state())
    emit_hbm_grounding(
        "boxgame_synctest",
        (device_fps / CHECK_DISTANCE) * (2 * state_b + 16 + PLAYERS),
    )
    emit(
        "bare_scan_floor_frames_per_sec", floor_fps,
        "resim-credit frames/sec (bare lax.scan(advance), no replay extras)",
        floor_fps / host_fps,
    )
    emit(
        f"boxgame_synctest_resim_frames_per_sec_cd{CHECK_DISTANCE}",
        device_fps, "resim_frames/sec", device_fps / host_fps,
    )


def _bank_matches_setup(n_matches: int, metrics=None, tracer=None):
    """The host-bank form of ``_match_population``: the SAME builders /
    sockets / schedules driven through ``parallel.HostSessionPool`` instead
    of per-session P2PSessions, fulfilled by the same
    ``BatchedRequestExecutor``.  ``metrics``: optional isolated
    ``ggrs_tpu.obs.Registry`` for the obs-budget measurements; ``tracer``:
    optional ``ggrs_tpu.obs.Tracer`` for the trace-overhead pricing."""
    from ggrs_tpu.parallel import BatchedRequestExecutor, HostSessionPool

    game = BoxGame(2)

    def to_arr(pairs):
        return np.asarray([p[0] for p in pairs], np.uint8)

    kwargs = {}
    if metrics is not None:
        kwargs["metrics"] = metrics
    if tracer is not None:
        kwargs["tracer"] = tracer
    host = HostSessionPool(**kwargs)
    schedules = []
    for b, sock, sched in _match_population(n_matches):
        host.add_session(b, sock)
        schedules.append(sched)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), to_arr,
        batch_size=len(host), ring_length=10, max_burst=9,
        with_checksums=False,
        # descriptor plane (DESIGN.md §21): bulk twin of to_arr — the
        # encoded blobs' first byte IS the value for small uint inputs,
        # so quiet slots convert in one NumPy slice
        raw_inputs_to_array=lambda blobs, statuses: blobs[:, :, 0],
    )
    pool.warmup(np.zeros((2,), np.uint8))
    return host, schedules, pool


def _bank_tick_fn(host, schedules, pool, scrape_each_tick=False,
                  staged=False, split=None):
    """One strict-fence pool tick (host crossing + device fulfillment),
    returning (host_ms, device_ms) — the shared harness of the host_bank
    capacity ramp and the degraded config.  ``scrape_each_tick`` adds the
    obs stat harvest (one extra ctypes crossing) inside the host window —
    the scrape-budget measurement of DESIGN.md §12.  ``staged`` routes
    the local inputs through the batched ``stage_inputs`` crossing
    (descriptor plane, §21) instead of B ``add_local_input`` calls;
    ``split``, when a list, collects per-tick ``(staging_ms,
    advance_ms)`` host sub-phases (the §21 staging/decode attribution)."""
    n = len(host)
    counter = [0]
    stage = getattr(host, "stage_inputs", None) if staged else None

    def tick():
        i = counter[0]
        counter[0] = i + 1
        t0 = time.perf_counter()
        if stage is not None:
            stage([(h, h % 2, schedules[h](i)) for h in range(n)])
        else:
            for h in range(n):
                host.add_local_input(h, h % 2, schedules[h](i))
        ts = time.perf_counter() if split is not None else 0.0
        reqs = host.advance_all()
        if scrape_each_tick:
            host.scrape()
        t1 = time.perf_counter()
        if split is not None:
            split.append(((ts - t0) * 1e3, (t1 - ts) * 1e3))
        pool.run(reqs)
        pool.block_until_ready()
        t2 = time.perf_counter()
        return (t1 - t0) * 1e3, (t2 - t1) * 1e3

    return tick


def _best_tick_percentiles(tick, T):
    """(p50_ms, p99_ms, host_fraction) over T ticks, best-of-REPEATS by
    p99."""
    best = None
    for _ in range(REPEATS):
        host_ms = np.empty(T)
        dev_ms = np.empty(T)
        for i in range(T):
            host_ms[i], dev_ms[i] = tick()
        total = host_ms + dev_ms
        p50 = float(np.percentile(total, 50))
        p99 = float(np.percentile(total, 99))
        host_frac = float(np.median(host_ms / total))
        if best is None or p99 < best[1]:
            best = (p50, p99, host_frac)
    return best


def run_host_bank() -> None:
    """The native session bank —
    every pooled session's protocol+sync mechanism in ONE C++ crossing per
    pool tick.

    Two measurements, both on the CPU-backend proxy (µs dispatch — the
    host-bound regime the capacity headline lives in):

    1. The 4-peer host tick vs the twice-missed ≤0.25 ms round-4 target
       (``vs_baseline`` = 250 µs / measured; >1 = target met), with the
       per-session Python path's tick in the unit string for attribution.
    2. The pooled-capacity ramp: largest match count whose p99 strict-fence
       tick fits the 16.7 ms frame budget, host fraction named per step.
    """
    from ggrs_tpu.parallel import HostSessionPool

    # ---- 1. the 4-peer tick (host_datapath's EXACT scenario, via
    # _four_peer_population, bank-driven vs per-session) ----
    def four_peer_tick_us(use_bank: bool) -> float:
        builders = list(_four_peer_population())
        P = len(builders)
        state = [0] * P
        if use_bank:
            host = HostSessionPool()
            for b, s in builders:
                host.add_session(b, s)
            if not host.native_active:
                # never present the Python fallback as the native-bank
                # headline (e.g. GGRS_TPU_NO_NATIVE set): the caller skips
                return None

            def drive(ticks, base):
                for i in range(base, base + ticks):
                    for h in range(P):
                        host.add_local_input(h, h, _four_peer_input(i, h))
                    for h, reqs in enumerate(host.advance_all()):
                        for r in reqs:
                            k = type(r).__name__
                            if k == "SaveGameState":
                                r.cell.save(r.frame, state[h], None)
                            elif k == "LoadGameState":
                                state[h] = r.cell.data()
        else:
            sessions = [b.start_p2p_session(s) for b, s in builders]

            def drive(ticks, base):
                for i in range(base, base + ticks):
                    for s in sessions:
                        s.poll_remote_clients()
                    for h, s in enumerate(sessions):
                        s.add_local_input(h, _four_peer_input(i, h))
                        for r in s.advance_frame():
                            k = type(r).__name__
                            if k == "SaveGameState":
                                r.cell.save(r.frame, state[h], None)
                            elif k == "LoadGameState":
                                state[h] = r.cell.data()

        drive(200, 0)
        n, base = 2000, 200
        best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            drive(n, base)
            best = min(best, (time.perf_counter() - t0) / n * 1e6)
            base += n
        return best

    _require_native_bank("host_bank")

    bank_us = four_peer_tick_us(use_bank=True)
    if bank_us is None:  # the pool silently fell back: not a native number
        fail("host_bank pool did not engage the native bank")
    py_us = four_peer_tick_us(use_bank=False)
    emit(
        "host_bank_p2p4_tick_us", bank_us,
        f"us/tick (target 250; per-session python path {py_us:.0f} us, "
        f"{py_us / bank_us:.1f}x)",
        250.0 / bank_us if bank_us else 0.0,
    )

    # ---- 1b. the obs scrape budget (DESIGN.md §12): p99 with a metrics
    # scrape every tick vs without, at the B=64 capacity point; the scrape
    # run's counter snapshot is embedded in the bench record ----
    from ggrs_tpu.obs import Registry

    def scrape_leg(scrape: bool):
        reg = Registry()
        host, schedules, pool = _bank_matches_setup(64, metrics=reg)
        if not host.native_active:
            return None
        tick = _bank_tick_fn(host, schedules, pool,
                             scrape_each_tick=scrape)
        for _ in range(16):
            tick()
        p = _best_tick_percentiles(tick, 200)
        snap = _obs_counters_snapshot(reg)
        crossings = (host.crossings, host.stat_crossings)
        del host, schedules, pool
        return p, snap, crossings

    plain = scrape_leg(False)
    scraped = scrape_leg(True)
    if plain is not None and scraped is not None:
        p99_plain, p99_scraped = plain[0][1], scraped[0][1]
        overhead_pct = (
            (p99_scraped - p99_plain) / p99_plain * 100.0 if p99_plain else 0.0
        )
        ticks, stat_crossings = scraped[2]
        emit(
            "host_bank_obs_scrape_overhead_pct", overhead_pct,
            f"p99 delta with a per-tick metrics scrape, B=64 matches, strict "
            f"fence (scraped {p99_scraped:.2f} ms vs plain {p99_plain:.2f} "
            f"ms; {stat_crossings} stat crossings over {ticks} ticks = one "
            f"per scrape; target <5%)",
            5.0 / overhead_pct if overhead_pct > 0 else 99.0,
            obs=scraped[1],
        )

    # ---- 1c. the trace budget (DESIGN.md §14): p99 with a live Tracer
    # (python tick/crossing/slot spans + the native in-crossing phase
    # timers, armed) vs the shared NULL_TRACER, at the B=64 capacity
    # point — priced exactly like the scrape overhead above ----
    from ggrs_tpu.obs import Tracer

    def trace_leg(trace: bool):
        reg = Registry()
        tracer = Tracer(capacity=1 << 14) if trace else None
        host, schedules, pool = _bank_matches_setup(
            64, metrics=reg, tracer=tracer
        )
        if not host.native_active:
            return None
        armed = host._trace_native
        tick = _bank_tick_fn(host, schedules, pool)
        for _ in range(16):
            tick()
        p = _best_tick_percentiles(tick, 200)
        del host, schedules, pool
        return p, armed

    t_plain = trace_leg(False)
    t_traced = trace_leg(True)
    if t_plain is not None and t_traced is not None:
        p99_plain, p99_traced = t_plain[0][1], t_traced[0][1]
        overhead_pct = (
            (p99_traced - p99_plain) / p99_plain * 100.0 if p99_plain else 0.0
        )
        emit(
            "host_bank_trace_overhead_pct", overhead_pct,
            f"p99 delta with tracing on (python spans + native phase timers "
            f"{'armed' if t_traced[1] else 'UNAVAILABLE'}), B=64 matches, "
            f"strict fence (traced {p99_traced:.2f} ms vs plain "
            f"{p99_plain:.2f} ms; zero extra crossings; target <5%)",
            5.0 / overhead_pct if overhead_pct > 0 else 99.0,
        )

    # ---- 2. capacity ramp with one-crossing host + one-dispatch device ----
    frame_budget_ms = 1000.0 / 60.0
    T = 300
    max_ok = 0
    knee = None
    for B in (64, 128, 256, 512):
        host, schedules, pool = _bank_matches_setup(B)
        tick = _bank_tick_fn(host, schedules, pool)
        for _ in range(16):
            tick()
        p50, p99, host_frac = _best_tick_percentiles(tick, T)
        emit(
            f"host_bank_capacity_b{B}_tick_ms_p99", p99,
            f"ms/tick p99, strict fence, one host crossing + one dispatch "
            f"(p50 {p50:.2f} ms, host fraction {host_frac:.2f}, native "
            f"{'on' if host.native_active else 'OFF'})",
            frame_budget_ms / p99,
        )
        if p99 <= frame_budget_ms:
            max_ok = B
        else:
            knee = (B, host_frac)
        del host, schedules, pool
        if knee is not None:
            break
    regime = ""
    if knee is not None:
        b_knee, host_frac = knee
        regime = (
            f"; knee at B={b_knee}, limiting regime "
            f"{'host bookkeeping' if host_frac > 0.5 else 'device fulfillment+fence'}"
            f" ({host_frac:.0%} host)"
        )
    emit(
        "host_bank_max_60hz_matches_per_chip", float(max_ok),
        f"matches (2 sessions each) with p99 tick <= 16.7 ms, strict fence, "
        f"native session bank{regime}",
        1.0,
    )


def run_host_bank_degraded() -> None:
    """Pool throughput with 1/8 of slots quarantined+evicted (the
    supervision layer's steady state after real faults): the evicted slots
    tick per-session Python P2PSessions inside the same advance_all while
    the survivors keep the one-crossing native path.  Reported against the
    same pool fully native (``vs_baseline`` = healthy p99 / degraded p99;
    1.0 = eviction is free, lower = the Python slots' cost)."""
    _require_native_bank("host_bank_degraded")

    B = 64  # matches (2 sessions each)
    T = 300

    def measure(degrade: bool):
        from ggrs_tpu.obs import Registry

        reg = Registry()
        host, schedules, pool = _bank_matches_setup(B, metrics=reg)
        n = len(host)
        if not host.native_active:
            return None
        tick = _bank_tick_fn(host, schedules, pool)
        for _ in range(16):
            tick()
        if degrade:
            for idx in range(0, n, 8):  # every 8th slot: 1/8 of the pool
                host.inject_slot_error(idx)
            for _ in range(16):  # let quarantine + eviction settle
                tick()
            evicted = sum(
                1 for i in range(n) if host.slot_state(i) == "evicted"
            )
            if evicted == 0:
                return None
        best = _best_tick_percentiles(tick, T)
        snap = _obs_counters_snapshot(reg)
        del host, schedules, pool
        return best, snap

    healthy = measure(degrade=False)
    degraded = measure(degrade=True)
    if healthy is None or degraded is None:
        fail("host_bank_degraded pool did not engage/degrade")
    (d50, d99, dfrac), dsnap = degraded
    emit(
        f"host_bank_degraded_b{B}_tick_ms_p99", d99,
        f"ms/tick p99, strict fence, 1/8 slots evicted to Python "
        f"(p50 {d50:.2f} ms, host fraction {dfrac:.2f}; "
        f"all-native p99 {healthy[0][1]:.2f} ms)",
        healthy[0][1] / d99 if d99 else 0.0,
        obs=dsnap,  # the degraded run's fault/eviction/crossing counters
    )


def run_host_bank_capacity() -> None:
    """ISSUE 12 acceptance sweep (DESIGN.md §21): the capacity ramp on
    the descriptor plane — B in 64/128/256/512/1024 MATCHES (2 sessions
    each) with batched input staging + lazy request plans, strict-fence
    host+device tick, knee detection, fast-path coverage, a
    staging+decode A/B at the BENCH_r07 knee (B=512, legacy per-call
    staging + reference parse vs the descriptor plane; target >= 2x),
    and per-phase attribution — including the §21 `staging` phase — from
    the PR 5 in-crossing timers.

    GC posture: the headline p99 is measured with the collector FROZEN
    after warmup (``gc.collect()`` + ``gc.freeze()`` — the standard
    long-lived-serving configuration; at B>=256 the default collector's
    full-heap passes dominate p99).  The default-GC p99 is emitted
    alongside so the delta stays visible rather than hidden."""
    import gc

    _require_native_bank("host_bank_capacity")

    frame_budget_ms = 1000.0 / 60.0
    T = 150

    def percentiles(tick, ticks):
        """Like _best_tick_percentiles but also reports the HOST-side p99
        (input staging + crossing + decode, device excluded) — the
        acceptance metric of ROADMAP item 3 is a host number."""
        best = None
        for _ in range(REPEATS):
            host_ms = np.empty(ticks)
            dev_ms = np.empty(ticks)
            for i in range(ticks):
                host_ms[i], dev_ms[i] = tick()
            total = host_ms + dev_ms
            p50 = float(np.percentile(total, 50))
            p99 = float(np.percentile(total, 99))
            host_frac = float(np.median(host_ms / total))
            host_p99 = float(np.percentile(host_ms, 99))
            if best is None or p99 < best[1]:
                best = (p50, p99, host_frac, host_p99)
        return best

    # ---- descriptor-plane A/B at the BENCH_r07 knee (B=512): staging +
    # decode host time, reference posture (per-call add_local_input +
    # the GGRS_TPU_NO_FASTPATH per-slot reference parse — NOT r07's §19
    # vectorized decode, which the plan path replaced and which cannot
    # be A/B'd in-tree; the r07 comparison is the recorded 23.7 ms
    # B=512 host p99 vs this sweep's number) vs the descriptor plane
    # (stage_inputs + RequestPlan) — the §21 acceptance ratio ----
    def staging_decode(B, descriptor):
        prev = os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
        if not descriptor:
            os.environ["GGRS_TPU_NO_FASTPATH"] = "1"
        try:
            host, schedules, pool = _bank_matches_setup(B)
            if not host.native_active:
                return None
            split = []
            tick = _bank_tick_fn(host, schedules, pool,
                                 staged=descriptor, split=split)
            for _ in range(16):
                tick()
            best = None
            gc.collect()
            gc.freeze()  # the serving posture, like the sweep below: the
            # A/B prices the CODE paths, not default-GC full-heap spikes
            try:
                dev = []
                for _ in range(REPEATS):
                    del split[:]
                    del dev[:]
                    for _ in range(min(T, 100)):
                        dev.append(tick()[1])
                    arr = np.asarray(split)
                    sd50 = float(np.percentile(arr.sum(axis=1), 50))
                    if best is None or sd50 < best[0]:
                        best = (
                            sd50,
                            float(np.percentile(arr.sum(axis=1), 99)),
                            float(np.percentile(arr[:, 0], 50)),
                            float(np.percentile(arr[:, 1], 50)),
                            float(np.percentile(dev, 50)),
                        )
            finally:
                gc.unfreeze()
                gc.collect()
            cov = host.fast_slot_ticks
            del host, schedules, pool
            return best + (cov,)
        finally:
            os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
            if prev is not None:
                os.environ["GGRS_TPU_NO_FASTPATH"] = prev

    legacy = staging_decode(512, descriptor=False)
    desc = staging_decode(512, descriptor=True)
    if legacy is None or desc is None:
        fail("host_bank_capacity pool did not engage the native "
             "bank")
    emit(
        "host_bank_capacity_b512_staging_decode_ms_p50", desc[0],
        f"ms/tick staging+advance_all HOST p50 at B=512 on the "
        f"descriptor plane, GC frozen, best of {REPEATS} "
        f"(staging {desc[2]:.2f} + advance_all {desc[3]:.2f}, p99 "
        f"{desc[1]:.2f}, device window p50 {desc[4]:.2f}; SAME-DAY "
        f"reference leg = per-call staging + NO_FASTPATH per-slot "
        f"parse, NOT r07's since-replaced vectorized decode: "
        f"{legacy[0]:.2f} = {legacy[2]:.2f} + {legacy[3]:.2f}, p99 "
        f"{legacy[1]:.2f}, device p50 {legacy[4]:.2f}; "
        f"{desc[5]} fast-path slot ticks vs {legacy[5]}; the r07 "
        f"cross-reference is its recorded 23.7 ms B=512 host p99 vs "
        f"this sweep's b512_host_ms_p99)",
        legacy[0] / desc[0] if desc[0] else 0.0,
    )

    # ---- the sweep: default-GC and frozen-GC p99 per B, knee detect,
    # batched staging (the production driver posture, §21) ----
    max_ok = 0
    knee = None
    for B in (64, 128, 256, 512, 1024):
        host, schedules, pool = _bank_matches_setup(B)
        if not host.native_active:
            fail("pool fell back at B=%d" % B)
        tick = _bank_tick_fn(host, schedules, pool, staged=True)
        for _ in range(16):
            tick()
        p50_d, p99_d, _, hp99_d = percentiles(tick, min(T, 100))
        gc.collect()
        gc.freeze()
        try:
            # (h_p99, not host_p99: that name is the A/B helper above)
            p50, p99, host_frac, h_p99 = percentiles(tick, T)
        finally:
            gc.unfreeze()
            gc.collect()
        fast_cov = host.fast_slot_ticks / max(
            1, host.crossings * len(host)
        )
        emit(
            f"host_bank_capacity_b{B}_host_ms_p99", h_p99,
            f"ms/tick HOST p99 (staging + one crossing + decode; the "
            f"ROADMAP item 3 acceptance metric; default-GC host p99 "
            f"{hp99_d:.2f} ms; fast-path coverage {fast_cov:.0%})",
            frame_budget_ms / h_p99 if h_p99 else 0.0,
        )
        emit(
            f"host_bank_capacity_b{B}_tick_ms_p99", p99,
            f"ms/tick p99, strict fence host+device, GC frozen after "
            f"warmup (default-GC p99 {p99_d:.2f} ms, p50 {p50_d:.2f}; "
            f"frozen p50 {p50:.2f}; host fraction {host_frac:.2f})",
            frame_budget_ms / p99,
        )
        if h_p99 <= frame_budget_ms and knee is None:
            # largest PASSING PREFIX: a noisy post-knee rung that squeaks
            # under budget must not overwrite the capacity headline
            max_ok = B
        elif h_p99 > frame_budget_ms and knee is None:
            knee = (B, host_frac)
        del host, schedules, pool
        # no early break: the B=1024 rung is part of the ISSUE 12
        # acceptance record even when the knee lands below it

    # ---- per-phase attribution at B=512 (PR 5 in-crossing timers plus
    # the §21 `staging` phase: stage_inputs time accrued outside the tick
    # window rides the same trace tail; a traced pool decodes like any
    # other, so the split prices the served path) ----
    from ggrs_tpu.obs import Tracer

    host, schedules, pool = _bank_matches_setup(
        512, tracer=Tracer(capacity=1 << 14)
    )
    if host.native_active and host._trace_native:
        tick = _bank_tick_fn(host, schedules, pool, staged=True)
        for _ in range(60):
            tick()
        host.scrape()
        totals = host.native_phase_totals()
        if totals:
            ticks, phases = totals
            per_tick = {
                k: v / max(1, ticks) / 1000.0 for k, v in phases.items()
            }
            top = sorted(per_tick.items(), key=lambda kv: -kv[1])
            emit(
                "host_bank_capacity_b512_crossing_phase_us", sum(
                    per_tick.values()
                ),
                "us/tick in-crossing + staging total at B=512 matches ("
                + " ".join(f"{k}={v:.0f}" for k, v in top)
                + ")",
                1.0,
            )
    del host, schedules, pool

    regime = ""
    if knee is not None:
        b_knee, host_frac = knee
        regime = (
            f"; knee at B={b_knee}, "
            f"{'host' if host_frac > 0.5 else 'device+fence'} bound "
            f"({host_frac:.0%} host)"
        )
    emit(
        "host_bank_capacity_max_60hz_matches_per_chip", float(max_ok),
        f"matches (2 sessions each) with HOST p99 tick <= 16.7 ms, "
        f"descriptor plane (batched staging + lazy request plans), GC "
        f"frozen after warmup{regime}",
        max_ok / 512.0 if max_ok else 0.0,  # vs the BENCH_r07 knee
    )


class _AckingViewer:
    """Minimal spectator endpoint for the io bench: drains its UDP
    socket, tracks the newest InputMessage start frame, and acks once per
    tick — enough inbound/outbound viewer traffic to make the host's
    per-datagram syscall bill honest without ticking 512 full
    ``SpectatorSession`` objects."""

    def __init__(self, host_addr):
        from ggrs_tpu.net.sockets import UdpNonBlockingSocket

        self.sock = UdpNonBlockingSocket(0)
        self.addr = ("127.0.0.1", self.sock.local_port())
        self.host = host_addr
        self.last = -1

    def tick(self) -> None:
        from ggrs_tpu.net.messages import InputAck, InputMessage, Message

        saw = False
        for _, msg in self.sock.receive_all_messages():
            if isinstance(msg.body, InputMessage):
                if msg.body.start_frame > self.last:
                    self.last = msg.body.start_frame
                saw = True
        if saw:
            self.sock.send_to(
                Message(0x5150, InputAck(self.last)), self.host
            )


def run_host_bank_io() -> None:
    """The kernel-batched socket datapath (DESIGN.md §15): B=64 matches
    over REAL loopback UDP, each host slot with one external peer and
    ``IO_VIEWERS`` fan-out viewers — the topology whose packet path is
    hundreds of sendto/recvfrom syscalls per pool tick on the Python
    shuttle.  Two legs, identical traffic: ``native_io=True`` (one
    recvmmsg + one sendmmsg per slot per tick via ggrs_bank_pump) vs the
    per-datagram shuttle.  Reported: host socket syscalls per pool tick
    (target ≥10× fewer; ``vs_baseline`` = ratio/10, ≥1 = met) and the
    host-loop p99 (``vs_baseline`` = shuttle p99 / batched p99, ≥1 = no
    worse)."""
    import random as _random

    from ggrs_tpu.broadcast import SpectatorHub
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.config import Config
    from ggrs_tpu.net import _native
    from ggrs_tpu.net.sockets import UdpNonBlockingSocket
    from ggrs_tpu.obs import Registry
    from ggrs_tpu.parallel import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    _require_native_bank("host_bank_io")
    io_available = _native.net_lib() is not None

    B = 64
    IO_VIEWERS = 8
    WARMUP, T = 16, 120
    cfg = Config.for_uint(16)

    def leg(native_io: bool, trace: bool = False, warmup: int = WARMUP,
            t: int = T):
        from ggrs_tpu.obs import Tracer

        clock = [0]
        pool = HostSessionPool(
            native_io=native_io, metrics=Registry(),
            tracer=Tracer(capacity=1 << 12) if trace else None,
        )
        hub = SpectatorHub(pool, rng=_random.Random(99))
        peers = []
        host_socks = []
        viewer_groups = []
        for m in range(B):
            host_sock = UdpNonBlockingSocket(0)
            peer_sock = UdpNonBlockingSocket(0)
            host_addr = ("127.0.0.1", host_sock.local_port())
            pool.add_session(
                SessionBuilder(cfg)
                .with_clock(lambda: clock[0])
                .with_rng(_random.Random(3 + 5 * m))
                .add_player(Local(), 0)
                .add_player(
                    Remote(("127.0.0.1", peer_sock.local_port())), 1
                ),
                host_sock,
            )
            peers.append(
                SessionBuilder(cfg)
                .with_clock(lambda: clock[0])
                .with_rng(_random.Random(4 + 5 * m))
                .add_player(Local(), 1)
                .add_player(Remote(host_addr), 0)
                .start_p2p_session(peer_sock)
            )
            host_socks.append(host_sock)
            viewer_groups.append(
                [_AckingViewer(host_addr) for _ in range(IO_VIEWERS)]
            )
        for m, group in enumerate(viewer_groups):
            for v in group:
                hub.attach(m, v.addr)
        if not pool.native_active:
            return None
        if native_io and not pool.native_io_active:
            return None

        def fulfill(reqs):
            for r in reqs:
                if type(r).__name__ == "SaveGameState":
                    r.cell.save(r.frame, None, None)

        host_ms = np.empty(t)

        def tick(i, record=None):
            clock[0] += 16
            for m, peer in enumerate(peers):
                peer.add_local_input(1, (i + m) % 16)
                fulfill(peer.advance_frame())
            for group in viewer_groups:
                for v in group:
                    v.tick()
            t0 = time.perf_counter()
            for m in range(B):
                pool.add_local_input(m, 0, (i + m) % 16)
            for reqs in pool.advance_all():
                fulfill(reqs)
            if record is not None:
                host_ms[record] = (time.perf_counter() - t0) * 1e3

        for i in range(warmup):
            tick(i)
        io0 = pool.io_stats()
        py0 = sum(s.io_syscalls for s in host_socks)
        for i in range(t):
            tick(warmup + i, record=i)
        io1 = pool.io_stats()
        py1 = sum(s.io_syscalls for s in host_socks)
        native_calls = (
            io1["recv_calls"] + io1["send_calls"]
            - io0["recv_calls"] - io0["send_calls"]
        )
        datagrams = (
            io1["recv_datagrams"] + io1["send_datagrams"]
            - io0["recv_datagrams"] - io0["send_datagrams"]
        )
        syscalls_per_tick = (native_calls + (py1 - py0)) / t
        p99 = float(np.percentile(host_ms, 99))
        p50 = float(np.percentile(host_ms, 50))
        frames = [pool.current_frame(m) for m in range(B)]
        phases = None
        if trace:
            totals = pool.native_phase_totals()
            if totals is not None:
                timed, ph = totals
                phases = {
                    k: ph.get(k, 0) / max(timed, 1) / 1e3  # us/tick
                    for k in ("inbound", "outbound", "fanout")
                }
        result = dict(
            syscalls=syscalls_per_tick,
            dgrams_per_tick=datagrams / t,
            p99=p99, p50=p50,
            min_frame=min(frames),
            phases=phases,
        )
        # release the leg's ~640 fds NOW: the pool<->hub cycle keeps the
        # socket objects alive until a full GC pass, and four legs of
        # unclosed fds would trip a default 1024-fd ulimit mid-bench
        del pool, hub
        for sock in host_socks:
            sock.close()
        for peer in peers:
            peer._socket.close()
        for group in viewer_groups:
            for v in group:
                v.sock.close()
        return result

    shuttle = leg(False)
    if shuttle is None:
        fail("host_bank_io pool did not engage the native bank")
    batched = leg(True) if io_available else None
    if batched is None:
        fail("host_bank_io batched leg unavailable "
             "(no recvmmsg/sendmmsg)")
    assert batched["min_frame"] > T - 32, "a batched match stalled"
    ratio = (
        shuttle["syscalls"] / batched["syscalls"]
        if batched["syscalls"] else 0.0
    )
    emit(
        "host_bank_io_syscalls_per_tick", batched["syscalls"],
        f"host socket syscalls per pool tick, B={B} matches x "
        f"{IO_VIEWERS} viewers, native_io on (shuttle "
        f"{shuttle['syscalls']:.0f}/tick; {ratio:.1f}x fewer; "
        f"~{batched['dgrams_per_tick']:.0f} datagrams/tick batched; "
        f"target >=10x)",
        ratio / 10.0,
    )
    emit(
        f"host_bank_io_b{B}_tick_ms_p99", batched["p99"],
        f"ms/tick p99, host loop only, native_io on (p50 "
        f"{batched['p50']:.2f} ms; shuttle p99 {shuttle['p99']:.2f} ms "
        f"p50 {shuttle['p50']:.2f} ms; >=1.0 = no worse than shuttle)",
        shuttle["p99"] / batched["p99"] if batched["p99"] else 0.0,
    )
    # the PR 5 in-crossing phase timers price the move honestly: on the
    # batched leg, inbound/outbound now INCLUDE the kernel I/O that used
    # to live in Python outside the crossing (short traced legs; the p99
    # above stays untraced)
    ph_shuttle = leg(False, trace=True, warmup=8, t=60)
    ph_batched = leg(True, trace=True, warmup=8, t=60)
    if (ph_shuttle and ph_batched and ph_shuttle["phases"]
            and ph_batched["phases"]):
        ps, pb = ph_shuttle["phases"], ph_batched["phases"]
        total_b = sum(pb.values())
        emit(
            "host_bank_io_phase_us_per_tick", total_b,
            "us/tick in-crossing inbound+outbound+fanout with native_io on "
            f"(inbound {pb['inbound']:.0f} outbound {pb['outbound']:.0f} "
            f"fanout {pb['fanout']:.0f}; shuttle crossing-only "
            f"{ps['inbound']:.0f}/{ps['outbound']:.0f}/{ps['fanout']:.0f} "
            "us — the batched phases now CONTAIN the kernel I/O the "
            "shuttle paid per-datagram in Python outside the crossing)",
            1.0,
        )


def run_inbound_gen2() -> None:
    """Datapath gen 2 inbound A/B (DESIGN.md §23): B matches over real
    loopback UDP, one external peer each, NO viewer fan-out — the
    inbound path isolated.  Three legs with identical seeded traffic:

    * ``reference`` — per-slot sockets with the batched drain disabled
      (``GGRS_TPU_NO_RECV_TABLE``): the pre-gen-2 per-slot recvmmsg pump.
    * ``batched``   — per-slot sockets drained by ``ggrs_net_recv_table``
      (one crossing, still one fd per slot).
    * ``dispatch``  — every slot a view on ONE DispatchHub port
      (+1 SO_REUSEPORT sibling), native route-table demux: the fd floor
      and the syscall floor drop together.

    Reported at B=512 (headline; B=1024 reference-vs-dispatch rides
    along): inbound syscalls per pool tick in dispatch mode
    (``vs_baseline`` = reference/dispatch ratio over the 4x target) and
    the dispatch host-loop p99 vs the 16.7 ms frame budget."""
    import gc
    import random as _random

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.config import Config
    from ggrs_tpu.net import _native
    from ggrs_tpu.net.sockets import DispatchHub, UdpNonBlockingSocket
    from ggrs_tpu.parallel import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    _require_native_bank("inbound_gen2")
    lib = _native.net_lib()
    if lib is None or not hasattr(lib, "ggrs_net_recv_table"):
        fail("inbound_gen2 needs ggrs_net_recv_table")

    WARMUP = 12

    def leg(mode: str, b: int, t: int):
        env_key = "GGRS_TPU_NO_RECV_TABLE"
        saved = os.environ.get(env_key)
        if mode == "reference":
            os.environ[env_key] = "1"
        try:
            cfg = Config.for_uint(16)
            clock = [0]
            pool = HostSessionPool()
            hub = DispatchHub(siblings=1) if mode == "dispatch" else None
            peers, host_socks = [], []
            for m in range(b):
                host_sock = hub.view() if hub else UdpNonBlockingSocket(0)
                host_port = host_sock.local_port()
                peer_sock = UdpNonBlockingSocket(0)
                pool.add_session(
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(_random.Random(3 + 5 * m))
                    .add_player(Local(), 0)
                    .add_player(
                        Remote(("127.0.0.1", peer_sock.local_port())), 1
                    ),
                    host_sock,
                )
                peers.append(
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(_random.Random(4 + 5 * m))
                    .add_player(Local(), 1)
                    .add_player(Remote(("127.0.0.1", host_port)), 0)
                    .start_p2p_session(peer_sock)
                )
                host_socks.append(host_sock)
            if not pool.native_active:
                return None

            def fulfill(reqs):
                for r in reqs:
                    if type(r).__name__ == "SaveGameState":
                        r.cell.save(r.frame, None, None)

            host_ms = np.empty(t)

            def tick(i, record=None):
                clock[0] += 16
                for m, peer in enumerate(peers):
                    peer.add_local_input(1, (i + m) % 16)
                    fulfill(peer.advance_frame())
                # the host window matches _bank_tick_fn: staging (the §21
                # batched crossing) + the crossing (inbound drain +
                # mechanism + outbound flush) + plan decode; request
                # fulfillment is the device side and stays outside, as in
                # the capacity ramp
                t0 = time.perf_counter()
                pool.stage_inputs(
                    [(m, 0, (i + m) % 16) for m in range(b)]
                )
                plan = pool.advance_all()
                if record is not None:
                    host_ms[record] = (time.perf_counter() - t0) * 1e3
                for reqs in plan:
                    fulfill(reqs)

            def inbound_syscalls():
                io = pool.io_stats()
                py = (
                    hub.io_syscalls if hub
                    else sum(s.io_syscalls for s in host_socks)
                )
                return io["recv_calls"] + io["drain"]["recv_calls"] + py

            for i in range(WARMUP):
                tick(i)
            s0 = inbound_syscalls()
            # the serving posture (as in run_host_bank_capacity): the A/B
            # prices the datapaths, not default-GC full-heap spikes over
            # 2B live session graphs; best-of-REPEATS p99 counters
            # scheduler drift like _best_tick_percentiles
            gc.collect()
            gc.freeze()
            best = None
            try:
                for rep in range(REPEATS):
                    for i in range(t):
                        tick(WARMUP + rep * t + i, record=i)
                    p99 = float(np.percentile(host_ms, 99))
                    if best is None or p99 < best[0]:
                        best = (p99, float(np.percentile(host_ms, 50)))
            finally:
                gc.unfreeze()
                gc.collect()
            s1 = inbound_syscalls()
            frames = [pool.current_frame(m) for m in range(b)]
            drain = pool.io_stats()["drain"]
            result = dict(
                syscalls=(s1 - s0) / (t * REPEATS),
                p99=best[0],
                p50=best[1],
                min_frame=min(frames),
                fds=len(hub.filenos()) if hub else b,
                crossings=pool.crossings,
                drain_crossings=pool.drain_crossings,
                unroutable=drain["unroutable"],
            )
            del pool
            for sock in host_socks:
                sock.close()
            if hub is not None:
                hub.close()
            for peer in peers:
                peer._socket.close()
            return result
        finally:
            if saved is None:
                os.environ.pop(env_key, None)
            else:
                os.environ[env_key] = saved

    B, T = 512, 80
    legs = {}
    for mode in ("reference", "batched", "dispatch"):
        legs[mode] = leg(mode, B, T)
        if legs[mode] is None:
            fail(f"inbound_gen2 {mode} leg did not engage the "
                 "native datapath")
        assert legs[mode]["min_frame"] > T - 32, f"a {mode} match stalled"
    ref, bat, dis = legs["reference"], legs["batched"], legs["dispatch"]
    assert dis["unroutable"] == 0, "dispatch demux dropped routed traffic"
    # the reference leg never touches the recv table; the batched legs
    # drain once per tick plus a bounded regrow re-invocation per
    # backpressure stop while the record table warms up to B (the exact
    # one-drain-per-tick pin lives in tests/test_net_gen2.py)
    assert ref["drain_crossings"] == 0
    assert dis["drain_crossings"] >= WARMUP + T
    assert bat["drain_crossings"] >= WARMUP + T
    ratio = ref["syscalls"] / dis["syscalls"] if dis["syscalls"] else 0.0
    emit(
        f"inbound_gen2_b{B}_syscalls_per_tick", dis["syscalls"],
        f"inbound syscalls per pool tick, B={B}, dispatch mode "
        f"({dis['fds']} fds; reference {ref['syscalls']:.0f}/tick on "
        f"{ref['fds']} fds, batched {bat['syscalls']:.0f}/tick; "
        f"{ratio:.1f}x fewer vs reference; target >=4x)",
        ratio / 4.0,
    )
    emit(
        f"inbound_gen2_b{B}_tick_ms_p99", dis["p99"],
        f"ms/tick p99, host loop only, dispatch mode (p50 "
        f"{dis['p50']:.2f} ms; batched p99 {bat['p99']:.2f} ms; "
        f"reference p99 {ref['p99']:.2f} ms; >=1.0 = inside the "
        "16.7 ms frame budget)",
        16.7 / dis["p99"] if dis["p99"] else 0.0,
    )
    # B=1024: does the dispatch win survive a doubling past the capacity
    # knee?  Reference-vs-dispatch only (shorter; the headline stays 512)
    B2, T2 = 1024, 48
    ref2 = leg("reference", B2, T2)
    dis2 = leg("dispatch", B2, T2)
    if ref2 and dis2:
        r2 = ref2["syscalls"] / dis2["syscalls"] if dis2["syscalls"] else 0.0
        emit(
            f"inbound_gen2_b{B2}_syscalls_per_tick", dis2["syscalls"],
            f"inbound syscalls per pool tick, B={B2}, dispatch mode "
            f"(reference {ref2['syscalls']:.0f}/tick; {r2:.1f}x fewer; "
            f"dispatch p99 {dis2['p99']:.2f} ms vs reference "
            f"{ref2['p99']:.2f} ms)",
            r2 / 4.0,
        )


def run_decode_parallel() -> None:
    """Parallel slow-slot decode + GRO inbound A/B (DESIGN.md §24): the
    inbound_gen2 population — B matches over real loopback UDP, one
    external rollback-every-tick peer each, dispatch mode — with the two
    §24 axes toggled independently:

    * decode ``serial``  — the kill-switch posture (the reference
      ``_parse_slot`` path, bit-identical baseline), vs ``thread`` — the
      DecodePool fan-out (on a GIL build this prices the machinery
      honestly; the wall win needs free-threading or sub-interpreters).
    * GRO off (``GGRS_TPU_NO_GRO``) vs on — coalesced inbound trains
      split natively by ``ggrs_net_recv_table``; the syscall floor drops
      when the kernel actually coalesces.

    Reported: host-loop p99 per leg at B=512 (vs the 16.7 ms budget) and
    B=1024 (vs BENCH_r09's 32.0 ms dispatch baseline, target >=1.5x),
    inbound syscalls per tick GRO-on vs GRO-off, and the decode plane's
    engagement counters (fanned ticks, slow slots/tick, workers)."""
    import gc
    import random as _random

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.config import Config
    from ggrs_tpu.net import _native
    from ggrs_tpu.net.sockets import DispatchHub, UdpNonBlockingSocket
    from ggrs_tpu.parallel import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    _require_native_bank("decode_parallel")
    lib = _native.net_lib()
    if lib is None or not hasattr(lib, "ggrs_net_recv_table"):
        fail("decode_parallel needs ggrs_net_recv_table")

    WARMUP = 12
    _ENV = ("GGRS_TPU_NO_PARALLEL_DECODE", "GGRS_TPU_DECODE_BACKEND",
            "GGRS_TPU_NO_GRO")

    def leg(decode: str, gro: bool, b: int, t: int):
        env = {}
        if decode == "serial":
            env["GGRS_TPU_NO_PARALLEL_DECODE"] = "1"
        else:
            env["GGRS_TPU_DECODE_BACKEND"] = decode
        if not gro:
            env["GGRS_TPU_NO_GRO"] = "1"
        saved = {k: os.environ.pop(k, None) for k in _ENV}
        os.environ.update(env)
        try:
            cfg = Config.for_uint(16)
            clock = [0]
            pool = HostSessionPool()
            hub = DispatchHub(siblings=1)
            peers = []
            for m in range(b):
                host_sock = hub.view()
                host_port = host_sock.local_port()
                peer_sock = UdpNonBlockingSocket(0)
                pool.add_session(
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(_random.Random(3 + 5 * m))
                    .add_player(Local(), 0)
                    .add_player(
                        Remote(("127.0.0.1", peer_sock.local_port())), 1
                    ),
                    host_sock,
                )
                peers.append(
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(_random.Random(4 + 5 * m))
                    .add_player(Local(), 1)
                    .add_player(Remote(("127.0.0.1", host_port)), 0)
                    .start_p2p_session(peer_sock)
                )
            if not pool.native_active:
                return None

            def fulfill(reqs):
                for r in reqs:
                    if type(r).__name__ == "SaveGameState":
                        r.cell.save(r.frame, None, None)

            host_ms = np.empty(t)

            def tick(i, record=None):
                clock[0] += 16
                for m, peer in enumerate(peers):
                    peer.add_local_input(1, (i + m) % 16)
                    fulfill(peer.advance_frame())
                t0 = time.perf_counter()
                pool.stage_inputs(
                    [(m, 0, (i + m) % 16) for m in range(b)]
                )
                plan = pool.advance_all()
                if record is not None:
                    host_ms[record] = (time.perf_counter() - t0) * 1e3
                for reqs in plan:
                    fulfill(reqs)

            def inbound_syscalls():
                io = pool.io_stats()
                return (io["recv_calls"] + io["drain"]["recv_calls"]
                        + hub.io_syscalls)

            for i in range(WARMUP):
                tick(i)
            s0 = inbound_syscalls()
            gc.collect()
            gc.freeze()
            best = None
            try:
                for rep in range(REPEATS):
                    for i in range(t):
                        tick(WARMUP + rep * t + i, record=i)
                    p99 = float(np.percentile(host_ms, 99))
                    if best is None or p99 < best[0]:
                        best = (p99, float(np.percentile(host_ms, 50)))
            finally:
                gc.unfreeze()
                gc.collect()
            s1 = inbound_syscalls()
            frames = [pool.current_frame(m) for m in range(b)]
            io = pool.io_stats()
            result = dict(
                p99=best[0],
                p50=best[1],
                syscalls=(s1 - s0) / (t * REPEATS),
                min_frame=min(frames),
                decode=io["decode"],
                gro_active=io["capabilities"]["gro_active"],
                gro_datagrams=io["drain"]["gro_datagrams"],
                gro_segments=io["drain"]["gro_segments"],
            )
            del pool
            hub.close()
            for peer in peers:
                peer._socket.close()
            return result
        finally:
            for k, v in saved.items():
                os.environ.pop(k, None)
                if v is not None:
                    os.environ[k] = v

    for b, t, baseline in ((256, 96, None), (512, 80, 16.7),
                           (1024, 48, 32.04)):
        legs = {
            "serial_nogro": leg("serial", False, b, t),
            "serial_gro": leg("serial", True, b, t),
            "thread_gro": leg("thread", True, b, t),
        }
        if any(v is None for v in legs.values()):
            fail(f"decode_parallel B={b} leg did not engage")
        for name, r in legs.items():
            assert r["min_frame"] > t - 32, f"a {name} B={b} match stalled"
        par = legs["thread_gro"]
        ser = legs["serial_gro"]
        off = legs["serial_nogro"]
        dec = par["decode"]
        assert dec["parallel_ticks"] > 0, "decode plane never fanned out"
        assert ser["decode"]["parallel_ticks"] == 0, "kill switch leaked"
        slots_tick = dec["jobs"] / max(1, dec["parallel_ticks"])
        gro_note = (
            f"{off['syscalls']:.0f} syscalls/tick gro-off vs "
            f"{ser['syscalls']:.0f} gro-on"
            + (f", {ser['gro_segments']}/{ser['gro_datagrams']} "
               f"segs/trains coalesced" if ser["gro_datagrams"] else
               ", kernel coalesced nothing on this run")
        )
        # headline per B: the best serving posture measured, with every
        # leg in the note — vs the 16.7 ms frame budget at B<=512 and vs
        # the r09 dispatch baseline (target >=1.5x better) at B=1024
        best_p99 = min(r["p99"] for r in legs.values())
        vs = ((baseline / 1.5) / best_p99 if b == 1024
              else (baseline or 16.7) / best_p99)
        emit(
            f"decode_parallel_b{b}_tick_ms_p99", best_p99,
            f"ms/tick p99, host loop, B={b} dispatch, best posture "
            f"(serial+gro {ser['p99']:.2f}, serial+nogro "
            f"{off['p99']:.2f}, thread+gro {par['p99']:.2f} ms; thread "
            f"leg fanned {dec['parallel_ticks']} ticks, "
            f"{slots_tick:.0f} slow slots/tick over {dec['workers']} "
            f"workers; {gro_note})",
            vs,
        )


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def run_broadcast_fanout() -> None:
    """Broadcast fan-out capacity (DESIGN.md §13): one bank-hosted 2-peer
    match whose confirmed-input stream fans natively to N real
    ``SpectatorSession`` viewers, N in {8, 64}.  Reports the host's pool
    tick p99 (vs the 0-viewer pool as baseline — the fan-out must ride the
    existing crossing, so the ratio is the whole story) and wire bytes per
    viewer per tick."""
    from ggrs_tpu.net import _native

    if _native.broadcast_lib() is None:
        fail("broadcast_fanout needs the native toolchain")

    import random as _random

    from ggrs_tpu.broadcast import SpectatorHub
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.config import Config
    from ggrs_tpu.core.errors import NotSynchronized, PredictionThreshold
    from ggrs_tpu.core.types import Spectator
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.obs import Registry
    from ggrs_tpu.parallel.host_bank import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    TICKS = 400
    cfg = Config.for_uint(16)

    def measure(n_viewers: int):
        clock = [0]
        net = InMemoryNetwork()
        hb = (
            SessionBuilder(cfg)
            .with_clock(lambda: clock[0])
            .with_rng(_random.Random(1))
            .add_player(Local(), 0)
            .add_player(Remote("P"), 1)
        )
        for k in range(n_viewers):
            hb = hb.add_player(Spectator(f"V{k}"), 2 + k)
        peer = (
            SessionBuilder(cfg)
            .with_clock(lambda: clock[0])
            .with_rng(_random.Random(2))
            .add_player(Local(), 1)
            .add_player(Remote("H"), 0)
        ).start_p2p_session(net.socket("P"))
        viewers = [
            SessionBuilder(cfg)
            .with_clock(lambda: clock[0])
            .with_rng(_random.Random(10 + k))
            .start_spectator_session("H", net.socket(f"V{k}"))
            for k in range(n_viewers)
        ]
        registry = Registry()
        pool = HostSessionPool(metrics=registry)
        if n_viewers:
            SpectatorHub(pool, rng=_random.Random(3))
        pool.add_session(hb, net.socket("H"))
        assert pool.native_active

        def fulfill(reqs):
            for r in reqs:
                if type(r).__name__ == "SaveGameState":
                    r.cell.save(r.frame, None, None)

        samples = []
        for i in range(TICKS):
            clock[0] += 16
            peer.add_local_input(1, (i * 3) % 16)
            fulfill(peer.advance_frame())
            t0 = time.perf_counter()
            pool.add_local_input(0, 0, (i * 7) % 16)
            for reqs in pool.advance_all():
                fulfill(reqs)
            samples.append(time.perf_counter() - t0)
            for viewer in viewers:
                try:
                    viewer.advance_frame()
                except (NotSynchronized, PredictionThreshold):
                    pass
        p99 = float(np.percentile(np.asarray(samples) * 1e3, 99))
        fan_bytes = registry.value(
            "ggrs_fanout_bytes_total", slot="0"
        ) or 0.0
        per_viewer_tick = (
            fan_bytes / n_viewers / TICKS if n_viewers else 0.0
        )
        return p99, per_viewer_tick

    base_p99, _ = measure(0)
    for n in (8, 64):
        p99, bpv = measure(n)
        emit(f"broadcast_fanout{n}_tick_p99_ms", p99, "ms",
             p99 / base_p99 if base_p99 else 0.0)
        emit(f"broadcast_fanout{n}_bytes_per_viewer_tick", bpv,
             "bytes/viewer/tick", 1.0)


def _parse_child_lines(stdout: str) -> list:
    """The child's valid JSON metric lines, parsed."""
    parsed = []
    for line in (stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed.append(json.loads(line))
            except ValueError:
                continue
    return parsed


def run_input_plane() -> None:
    """The input plane (DESIGN.md §27): B=256 pooled matches with fixed
    4-byte uint inputs vs variable-size RTS command records in the varrec
    envelope — host-loop tick p99 and wire bytes per tick.

    Both peers of every match live in ONE HostSessionPool (2B sessions)
    over one in-memory network whose delivery hook counts every payload
    byte; fulfillment is frame-as-state, so the number prices the host
    input/wire plane, not device fulfillment.  The varrec leg checks the
    §27 claim that variable-size records stay native-bank eligible (the
    unit string names native on/off per leg), and the byte accounting
    splits live payload bytes from envelope capacity — the headroom a
    length-aware wire codec could reclaim."""
    import random

    from ggrs_tpu.core import Config, Local, Remote
    from ggrs_tpu.games import RtsCmd, encode_commands
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.parallel import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    B = 256
    T = 300
    CYCLE = 64  # precomputed schedule window; rng stays out of the timing
    frame_budget_ms = 1000.0 / 60.0
    rts = RtsCmd(num_players=2, num_units=4, max_cmds=4)

    def _cmds(rng) -> tuple:
        cmds = []
        for _ in range(rng.randrange(0, 4)):
            kind = rng.randrange(3)
            if kind == 0:
                cmds.append(("move", rng.randrange(4),
                             rng.randrange(-2, 3), rng.randrange(-2, 3)))
            elif kind == 1:
                cmds.append(("gather", rng.randrange(4)))
            else:
                cmds.append(("build", rng.randrange(16), rng.randrange(16)))
        return tuple(cmds)

    def leg(kind: str):
        wire = [0]
        net = InMemoryNetwork()
        orig_send = net._send

        def counted(src, dst, payload):
            wire[0] += len(payload)
            orig_send(src, dst, payload)

        net._send = counted
        host = HostSessionPool()
        for m in range(B):
            names = (f"A{m}", f"B{m}")
            for me in (0, 1):
                cfg = Config.for_uint(32) if kind == "fixed4" else rts.config()
                b = (
                    SessionBuilder(cfg)
                    .with_clock(lambda: 0)
                    .with_rng(random.Random(3 + 5 * m + me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                host.add_session(b, net.socket(names[me]))
        n = len(host)
        state = [0] * n

        # per-session CYCLE-long schedules, plus the live payload bytes each
        # tick of the cycle contributes (pre-envelope — what the game sent)
        if kind == "fixed4":
            sched = [
                [((i + h) * 2654435761) & 0xFFFFFFFF for i in range(CYCLE)]
                for h in range(n)
            ]
            pay_per_tick = 4.0 * n
        else:
            sched = [
                [_cmds(random.Random(17 + h * 613 + i)) for i in range(CYCLE)]
                for h in range(n)
            ]
            pay_per_tick = (
                sum(
                    len(encode_commands(c)) for row in sched for c in row
                ) / CYCLE
            )

        def tick(i: int) -> float:
            j = i % CYCLE
            t0 = time.perf_counter()
            for h in range(n):
                host.add_local_input(h, h & 1, sched[h][j])
            for h, reqs in enumerate(host.advance_all()):
                for r in reqs:
                    k = type(r).__name__
                    if k == "SaveGameState":
                        r.cell.save(r.frame, state[h], None)
                    elif k == "LoadGameState":
                        state[h] = r.cell.data()
            return (time.perf_counter() - t0) * 1e3

        for i in range(16):  # pipeline fill
            tick(i)
        best = None
        base = 16
        for _ in range(REPEATS):
            wire[0] = 0
            ms = np.empty(T)
            for i in range(T):
                ms[i] = tick(base + i)
            base += T
            p50 = float(np.percentile(ms, 50))
            p99 = float(np.percentile(ms, 99))
            if best is None or p99 < best[0]:
                best = (p99, p50, wire[0] / T)
        return best, pay_per_tick, host.native_active

    (fp99, fp50, fwire), fpay, f_native = leg("fixed4")
    (vp99, vp50, vwire), vpay, v_native = leg("varrec")
    env = rts.config().native_input_size  # [u16 len][payload][pad]

    emit(
        "input_plane_fixed4_b256_tick_ms_p99", fp99,
        f"ms/tick p99, host loop, B={B} matches ({2 * B} pooled sessions), "
        f"4-byte uint inputs, native {'on' if f_native else 'OFF'} "
        f"(p50 {fp50:.2f} ms)",
        frame_budget_ms / fp99 if fp99 else 0.0,
    )
    emit(
        "input_plane_varrec_b256_tick_ms_p99", vp99,
        f"ms/tick p99, host loop, B={B} matches, RTS command records in the "
        f"{env}-byte varrec envelope, native {'on' if v_native else 'OFF'} "
        f"(p50 {vp50:.2f} ms; fixed-4 leg {fp99:.2f} ms, "
        f"{vp99 / fp99 if fp99 else 0.0:.2f}x)",
        frame_budget_ms / vp99 if vp99 else 0.0,
    )
    emit(
        "input_plane_varrec_wire_bytes_per_tick", vwire,
        f"bytes/tick on the wire, B={B} ({vwire / B:.0f} B/match/tick; live "
        f"payload {vpay:.0f} B/tick = {vpay / vwire if vwire else 0.0:.1%} "
        f"of wire — the rest is the fixed {env}-byte envelope + protocol "
        f"framing; fixed-4 leg {fwire:.0f} B/tick)",
        fwire / vwire if vwire else 0.0,
    )


def orchestrate() -> None:
    """Run each selected config in its own subprocess.  The flagship child
    runs FIRST and its metric lines are printed THE MOMENT it completes (a
    driver capture window must never close on an empty stream), then
    re-printed at the end so the final line stays the headline.  The default
    selection is the COMPACT subset; GGRS_BENCH_FULL=1 restores the full
    suite.
    A child that dies or times out costs its own lines only, but the run
    exits nonzero when ANY selected config failed, timed out, printed no
    metric or did not fit the budget: a partial run must not read as a
    clean one to a driver that records the exit status."""
    here = os.path.abspath(__file__)
    if os.environ.get("GGRS_BENCH_FULL"):
        names = list(CONFIGS)
        total_budget = float(
            os.environ.get("GGRS_BENCH_TOTAL_BUDGET") or "inf"
        )
    else:
        names = [n for n in CONFIGS if n in COMPACT_CONFIGS]
        total_budget = float(
            os.environ.get("GGRS_BENCH_TOTAL_BUDGET")
            or DEFAULT_TOTAL_BUDGET_S
        )
    only = os.environ.get("GGRS_BENCH_ONLY")
    if only:  # comma-separated subset, e.g. GGRS_BENCH_ONLY=flagship,ecs
        sel = {s.strip() for s in only.split(",") if s.strip()}
        unknown = sel - set(CONFIGS)  # any config selectable, not just compact
        if unknown or not sel:
            sys.stderr.write(
                f"GGRS_BENCH_ONLY: unknown configs {unknown or only!r}; "
                f"one of {list(CONFIGS)}\n"
            )
            raise SystemExit(2)
        names = [n for n in CONFIGS if n in sel]
    run_order = (["flagship"] if "flagship" in names else []) + [
        n for n in names if n != "flagship"
    ]
    deadline = time.monotonic() + total_budget

    def run_child(name: str) -> Tuple[str, str, str]:
        """Returns (stdout, failure_note, stderr_tail); failure_note is ""
        on a clean exit, else a one-line diagnosis (timeout or nonzero rc).

        STREAMING (the BENCH_r05 rc=124/empty-tail fix): the child's
        stdout is polled twice a second and every complete metric line is
        forwarded to OUR stdout the moment the child prints it — a driver
        that kills the orchestrator mid-child still has every measurement
        taken so far on its capture.  The child's budget is additionally
        clamped to the orchestrator's remaining total deadline, so the
        suite can never outlive its window with nothing printed.

        Child output goes to temp FILES, not pipes: a file keeps whatever
        the child printed before it hung — so a measurement that completed
        before a stall at teardown is still salvaged.  Files are
        binary and decoded with errors='replace': a child SIGKILLed
        mid-write must not take the rest of the suite down with a
        UnicodeDecodeError."""
        import tempfile

        spec = CONFIGS[name]
        budget = min(spec[1], max(0.0, deadline - time.monotonic()))
        env = None
        if len(spec) > 2 and spec[2]:
            env = dict(os.environ)
            env.update(spec[2])
        # one process per chip: a parent holding a backend would leave the
        # child's device configs to fail or hang
        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "bench.py's orchestrator initialised a JAX backend before "
                "spawning its children; it must never touch the device"
            )
        with tempfile.TemporaryFile() as out_f, tempfile.TemporaryFile() as err_f:
            proc = subprocess.Popen(
                [sys.executable, here, name],
                stdout=out_f,
                stderr=err_f,
                cwd=os.path.dirname(here),
                env=env,
            )
            start = time.monotonic()
            streamed = 0  # bytes of the child's stdout already scanned
            pending = b""
            out_fd = out_f.fileno()

            def forward_new() -> None:
                """Scan from the last offset, print complete metric
                lines immediately (partial trailing line waits).
                os.pread, NOT seek+read: the child's stdout fd shares
                this open file description, so seeking here would move
                the offset the child writes at mid-run and corrupt its
                own stream."""
                nonlocal streamed, pending
                while True:
                    chunk = os.pread(out_fd, 1 << 16, streamed)
                    if not chunk:
                        break
                    streamed += len(chunk)
                    pending += chunk
                while b"\n" in pending:
                    line, pending = pending.split(b"\n", 1)
                    text = line.decode(errors="replace").strip()
                    if not text.startswith("{"):
                        continue
                    try:
                        json.loads(text)
                    except json.JSONDecodeError:
                        continue
                    print(text, flush=True)

            note = ""
            while True:
                forward_new()
                if proc.poll() is not None:
                    break
                if time.monotonic() - start > budget:
                    proc.kill()
                    proc.wait()
                    note = f"exceeded its {budget:.0f}s budget"
                    break
                time.sleep(0.5)
            forward_new()
            if not note and proc.returncode not in (0, None):
                note = f"exited rc={proc.returncode}"
            out_f.seek(0)
            err_f.seek(0)
            out = out_f.read().decode(errors="replace")
            err_tail = err_f.read()[-2000:].decode(errors="replace")
            return out, note, err_tail

    def report(name: str, out: str, note: str, err_tail: str) -> bool:
        """Surface every failure note (the metric lines already streamed
        to stdout while the child ran), with the child's stderr tail
        whenever something needs diagnosing."""
        parsed = parsed_by_name[name]
        if note:
            salvage = " (metric salvaged from partial output)" if parsed \
                else ""
            sys.stderr.write(
                f"bench config {name!r} {note}{salvage}; stderr tail:\n"
                f"{err_tail}\n"
            )
        elif not parsed:
            sys.stderr.write(
                f"bench config {name!r} produced no metric (rc=0); "
                f"stderr tail:\n{err_tail}\n"
            )
        return bool(parsed) and not note

    def write_artifact(results: dict, parsed_by_name: dict) -> list:
        """Write bench_out/latest.json from what has completed SO FAR and
        return the metric list.  Called after every config: the round-5
        config list runs for tens of minutes, and a driver that kills the
        orchestrator mid-run must still find every completed config's
        metrics in the artifact."""
        all_metrics = []
        for name in names:  # print order, flagship last
            if name in results:
                all_metrics.extend(parsed_by_name[name])
        if not all_metrics:
            return all_metrics
        artifact = {
            "schema": "ggrs_tpu bench full stream v1",
            "time_unix": int(time.time()),
            "configs_run": [n for n in names if n in results],
            "configs_pending": [n for n in names if n not in results],
            "metrics": all_metrics,
        }
        out_dir = os.path.join(os.path.dirname(here), "bench_out")
        try:
            os.makedirs(out_dir, exist_ok=True)
            tmp = os.path.join(out_dir, f".latest.{os.getpid()}.tmp")
            with open(tmp, "w") as f:
                json.dump(artifact, f, indent=1)
            os.replace(tmp, os.path.join(out_dir, "latest.json"))
        except OSError as e:  # the final print still carries the full list
            sys.stderr.write(f"bench_out/latest.json not written: {e}\n")
        return all_metrics

    not_ok: list = []  # configs that failed, timed out or did not fit
    all_metrics: list = []
    results: dict = {}
    parsed_by_name: dict = {}  # name -> parsed metric objs
    for name in run_order:
        remaining = deadline - time.monotonic()
        if remaining < 10:
            # no silent caps: a config that does not fit the window is
            # reported, fails the run, and the streamed metrics stand
            sys.stderr.write(
                f"bench config {name!r} NOT RUN: {max(0, remaining):.0f}s "
                f"left of the {total_budget:.0f}s total budget "
                "(GGRS_BENCH_TOTAL_BUDGET)\n"
            )
            not_ok.append(name)
            continue
        result = run_child(name)
        results[name] = result
        parsed_by_name[name] = _parse_child_lines(result[0])
        # EVERY config (the flagship included) reports the moment its child
        # completes: a driver that kills the orchestrator mid-run, or whose
        # capture window closes early, still has the headline on stdout.
        # The flagship's lines are re-printed at the very end so the final
        # line keeps its headline semantics.
        if not report(name, *result):
            not_ok.append(name)
        all_metrics = write_artifact(results, parsed_by_name)

    # Canonical self-contained artifact: the driver's
    # recorded BENCH file keeps only the tail of stdout, so earlier configs'
    # metrics used to survive only in prose.  The artifact was refreshed
    # after every config above (all_metrics holds the final refresh); print
    # the complete list as one schema-shaped line right before the
    # flagship, so a tail capture of the last two lines is still the whole
    # run.
    if all_metrics:  # a total-failure run must not leave a valid metric line
        print(
            json.dumps(
                {
                    "metric": "bench_full_stream",
                    "value": len(all_metrics),
                    "unit": "metrics (complete list under 'metrics'; also "
                            "bench_out/latest.json)",
                    "vs_baseline": 1.0,
                    "metrics": all_metrics,
                }
            ),
            flush=True,
        )

    # re-print (no duplicate stderr note): the last line is the headline
    for obj in parsed_by_name.get("flagship", ()):
        print(json.dumps(obj), flush=True)
    if not_ok:
        sys.stderr.write(f"bench: FAIL: configs not measured: {not_ok}\n")
        raise SystemExit(1)


def main(argv: list) -> None:
    if len(argv) > 1:
        name = argv[1]
        if name not in CONFIGS:
            sys.stderr.write(
                f"unknown bench config {name!r}; one of {list(CONFIGS)}\n"
            )
            raise SystemExit(2)
        # a child (its environment, incl. JAX_PLATFORMS for the host
        # proxies, comes from its CONFIGS entry via the orchestrator)
        if name in DEVICE_CONFIGS:
            require_chip()
        place_compile_cache()
        globals()[CONFIGS[name][0]]()
    else:
        orchestrate()


if __name__ == "__main__":
    main(sys.argv)
