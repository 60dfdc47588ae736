#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process owns the chip from start to end and drives the main path once
through the entry points a user calls, at a deployment's size, checking
every answer against a plain reference:

    python chip_smoke.py              # the TPU v5e machine; exit 0 + verdict
    python chip_smoke.py --chips 4    # pool leg sharded over a 4-chip host

Legs (each raises on failure; nothing is caught and survived):

  pool      B = 256 ex_game BoxGame matches = 512 sessions in one
            ``HostSessionPool`` -> ``RequestPlan`` ->
            ``BatchedRequestExecutor`` (``HostedPool.tick``), 600 ticks over
            an in-memory network with latency, so rollbacks really happen,
            desync detection on at interval 10 inside the native bank.
            Every match's two peers must hold bit-identical device state,
            equal to ``BoxGame.advance_np`` replayed on the host; every
            session's reports are compared by its peer and none differs.
  fence     is ``block_until_ready`` a real completion fence here, before
            and after the process's first device->host read?
  synctest  BASELINE config 2: ``DeviceSyncTestSession`` cd=8 (the donating
            ``ops/replay.py`` programs), zero mismatches, final state and
            digest equal to the NumPy mirror's.
  games     every bundled game's ``advance`` compiled for the chip and
            compared bit-for-bit with its ``advance_np``.
  pallas    ``leaf_digest_pallas`` compiled (not interpreted) on a 256 MiB
            leaf and a ragged one, lanes equal to the XLA digest.
  udp       32 matches whose hosted peers sit in a
            ``HostSessionPool(native_io=True)`` over real loopback UDP
            against 32 plain ``P2PSession`` peers, 300 ticks.

The fence leg's pre-read sample is the process's first device work (it has
to precede every device->host read); the pool leg is the first leg run.

The last line of standard output is the verdict and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
the device as JAX reports it.  The line before it (``summary: {...}``, also
written to ``chiprun_out/chip_smoke.json``) carries every leg's facts.

Without a TPU of a known kind the script exits nonzero and prints no
result.  ``--rehearse-cpu`` (tiny sizes, Pallas interpreted) and ``--legs``
(a subset) exist for debugging; both label their output and neither can
print the passing verdict (``"ok": false``, exit 10).
"""

from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import math
import random
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ggrs_tpu.core import DesyncDetected, DesyncDetection, Local, Remote
from ggrs_tpu.games import BoxGame, ChipVM, EcsWorld, RtsCmd, boxgame_config
from ggrs_tpu.net import InMemoryNetwork
from ggrs_tpu.net.sockets import UdpNonBlockingSocket
from ggrs_tpu.obs.registry import default_registry
from ggrs_tpu.ops import pallas_checksum as pc
from ggrs_tpu.ops.checksum import (
    _GOLDEN,
    _PRIME_A,
    _PRIME_B,
    _leaf_digest,
    _structure_salt,
    checksum_device,
)
from ggrs_tpu.parallel import (
    BatchedRequestExecutor,
    HostedPool,
    HostSessionPool,
    make_mesh,
)
from ggrs_tpu.sessions import DeviceSyncTestSession, SessionBuilder
from ggrs_tpu.utils.device import (
    NoChipError,
    cache_entry_count,
    device_peaks,
    device_record,
    place_compile_cache,
    require_chip,
)

LEGS = ("pool", "fence", "synctest", "games", "pallas", "udp")
# exit code of a rehearsal or a subset run whose legs all passed: nonzero,
# because nothing such a run prints is the chip result
NOT_A_CHIP_RESULT = 10
MAX_PREDICTION = 8  # the builder default the pool and udp legs run under
DETECTION_INTERVAL = 10  # the pool leg's desync detection, in frames

# the deployment's size, and the tiny one a CPU rehearsal walks through
REAL = dict(
    matches=256, ticks=600, latency_ticks=2,
    fence_n=8192, fence_chain=16,
    synctest_ticks=4096, synctest_chunk=512,
    game_frames=64, chipvm_batch=256,
    pallas_words=64 * 1024 * 1024,
    udp_matches=32, udp_ticks=300,
)
REHEARSAL = dict(
    matches=4, ticks=96, latency_ticks=2,
    fence_n=256, fence_chain=4,
    synctest_ticks=96, synctest_chunk=32,
    game_frames=8, chipvm_batch=4,
    pallas_words=3 * pc._BLOCK_ROWS * pc._LANES,
    udp_matches=2, udp_ticks=64,
)


class SmokeFailure(AssertionError):
    """A leg's check did not hold."""


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _compiles() -> int:
    """Backend compile requests of this process so far (one served from the
    persistent cache counts too), as the program counts them itself
    (``ggrs_tpu/parallel/session_pool.py``, DESIGN.md §14)."""
    return int(
        default_registry().value("ggrs_process_backend_compiles_total") or 0
    )


def _equal_trees(a: Any, b: Any) -> bool:
    """Same pytree structure (dict keys included) and bit-equal leaves."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# the match population shared by the pool and udp legs
# ---------------------------------------------------------------------------


def _schedule(seed: int, m: int, me: int, i: int) -> int:
    """Player ``me`` of match ``m``'s true input at tick ``i`` (input delay
    is 0, so also its input for frame ``i``): button masks that change every
    2-4 ticks, so repeat-last predictions miss regularly."""
    return ((i + seed + 2 * m + me) // (2 + m % 3)) % 16


def _builder(clock: List[int], seed: int, m: int, me: int, other: Any):
    """ex_game's session at builder defaults (prediction window 8, 60 FPS)."""
    return (
        SessionBuilder(boxgame_config())
        .with_clock(lambda: clock[0])
        .with_rng(random.Random(seed * 7919 + 5 * m + me))
        .add_player(Local(), me)
        .add_player(Remote(other), 1 - me)
    )


def _executor(game: BoxGame, sessions: int, mesh: Any) -> BatchedRequestExecutor:
    ex = BatchedRequestExecutor(
        game.advance, game.init_state(),
        lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
        batch_size=sessions,
        ring_length=MAX_PREDICTION + 2, max_burst=MAX_PREDICTION + 1,
        mesh=mesh,
        # boxgame_config encodes a button mask as one little-endian uint:
        # byte 0 of each blob is the value
        raw_inputs_to_array=lambda blobs, statuses: blobs[:, :, 0],
    )
    ex.warmup(np.zeros((2,), np.uint8))
    return ex


def _reference_state(game: BoxGame, seed: int, m: int, frames: int,
                     hold_from: int) -> Dict[str, np.ndarray]:
    """The plain reference: ``advance_np`` replayed on the host over match
    ``m``'s true input schedule, independent of sessions, pool and device."""
    state = game.init_state_np()
    for f in range(frames):
        i = min(f, hold_from)
        state = game.advance_np(state, np.asarray(
            [_schedule(seed, m, 0, i), _schedule(seed, m, 1, i)], np.uint8
        ))
    return state


def _exchange_counts() -> Tuple[float, float, float]:
    """(ChecksumReports sent, reports compared, compares that differed) by
    the sessions of native banks, from the pools' process-wide counters."""
    reg = default_registry()
    return tuple(
        reg.value(f"ggrs_pool_{name}_total") or 0.0
        for name in ("checksum_reports_sent", "checksum_compares", "desyncs")
    )


def _burst_counts() -> Tuple[float, int, int]:
    """(rollback loads, dispatched ticks, ticks whose deepest burst was 1)
    from the executor's own process-wide counters."""
    reg = default_registry()
    loads = reg.value("ggrs_executor_rollback_loads_total") or 0.0
    hist = next(
        f for f in reg.families()
        if f.name == "ggrs_executor_burst_depth_frames"
    )
    cumulative = hist.cumulative()
    depth1 = next((n for le, n in cumulative if le == 1), 0)
    total = cumulative[-1][1] if cumulative else 0
    return loads, total, depth1


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def leg_pool(size: Dict[str, int], seed: int, chips: int,
             platform: str) -> Dict[str, Any]:
    matches, ticks = size["matches"], size["ticks"]
    sessions = 2 * matches
    game = BoxGame(2)
    clock = [0]
    net = InMemoryNetwork(seed=seed, latency_ticks=size["latency_ticks"])
    host = HostSessionPool()
    for m in range(matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            host.add_session(
                # as upstream's examples run it: desync detection on
                _builder(clock, seed, m, me, names[1 - me])
                .with_desync_detection_mode(
                    DesyncDetection.on(DETECTION_INTERVAL)),
                net.socket(names[me]),
            )
    mesh = make_mesh(chips) if chips > 1 else None
    ex = _executor(game, sessions, mesh)
    hosted = HostedPool(host, ex)
    check(host.native_active, f"pool: host tier is Python: {host.native_reason}")

    # inputs hold constant over the last 3 windows so repeat-last
    # predictions come true and both peers' live states converge on the
    # true simulation (examples/ex_game_server.py does the same)
    hold_from = ticks - 3 * MAX_PREDICTION
    loads0, total0, depth1_0 = _burst_counts()
    exchange0 = _exchange_counts()
    compiles0 = _compiles()
    t0 = time.perf_counter()
    for i in range(ticks):
        clock[0] = (i * 1000) // 60
        j = min(i, hold_from)
        hosted.tick([
            (2 * m + me, me, _schedule(seed, m, me, j))
            for m in range(matches) for me in (0, 1)
        ])
        net.tick()
    hosted.block_until_ready()
    run_s = time.perf_counter() - t0
    compiled_in_ticks = _compiles() - compiles0
    loads1, total1, depth1_1 = _burst_counts()

    check(compiled_in_ticks == 0,
          f"pool: {compiled_in_ticks} compilations inside the {ticks} ticks")
    check(host.crossings == ticks,
          f"pool: {host.crossings} bank crossings over {ticks} ticks")
    check(host.plan_ticks == ticks,
          f"pool: {host.plan_ticks} plan ticks over {ticks} ticks")
    check(host.fast_slot_ticks > 0, "pool: the quiet fast path served no slot")
    off_bank = [
        (i, host.slot_state(i)) for i in range(sessions)
        if host.slot_state(i) != "native"
    ]
    check(not off_bank, f"pool: slots left the bank: {off_bank[:8]}")
    # the exchange closed: every session's digests reached its peer (the
    # device's, fetched in one batched read a tick that wants any) and
    # were compared there, inside the crossing; none differed
    sent, compared, desyncs = (
        after - before
        for before, after in zip(exchange0, _exchange_counts())
    )
    silent = [
        i for i in range(sessions)
        if not len(host.flight_recorder(i).checksums)
    ]
    check(not silent, f"pool: sessions that reported no digest: {silent[:8]}")
    check(compared >= sessions,
          f"pool: {compared} reports compared over {sessions} sessions")
    check(sent >= compared, f"pool: {compared} compared of {sent} sent")
    raised = [
        (i, e) for i in range(sessions) for e in host.events(i)
        if isinstance(e, DesyncDetected)
    ]
    check(desyncs == 0 and not raised,
          f"pool: {desyncs} desyncs detected: {raised[:4]}")
    rollback_loads = int(loads1 - loads0)
    deep_ticks = (total1 - total0) - (depth1_1 - depth1_0)
    check(rollback_loads > 0, "pool: no rollback load reached the executor")
    check(deep_ticks > 0, "pool: no tick carried a burst deeper than 1")
    stalled = [
        (i, host.current_frame(i)) for i in range(sessions)
        if host.current_frame(i) != ticks
    ]
    check(not stalled,
          f"pool: sessions not at frame {ticks}: {stalled[:8]}")

    # where the carry lives: on the chip, and with a mesh on every chip
    leaves = jax.tree_util.tree_leaves(ex._carry)
    platforms = {d.platform for leaf in leaves for d in leaf.devices()}
    check(platforms == {platform},
          f"pool: carry buffers sit on {platforms}, not {platform}")
    shard_devices = sorted({
        s.device.id for leaf in leaves for s in leaf.addressable_shards
    })
    check(len(shard_devices) == chips,
          f"pool: carry shards on devices {shard_devices}, wanted {chips}")
    rows = {
        s.data.shape[0] for leaf in leaves for s in leaf.addressable_shards
    }
    check(rows == {sessions // chips},
          f"pool: shards hold {rows} sessions, wanted {sessions // chips}")

    # the first device->host read of the leg: correctness, outside the ticks
    live = jax.device_get(ex.live_states)
    for m in range(matches):
        a = {k: v[2 * m] for k, v in live.items()}
        b = {k: v[2 * m + 1] for k, v in live.items()}
        check(_equal_trees(a, b), f"pool: match {m}: the two peers differ")
        ref = _reference_state(game, seed, m, ticks, hold_from)
        check(_equal_trees(a, ref),
              f"pool: match {m} differs from the advance_np replay")
    return {
        "run_s": run_s, "matches": matches, "sessions": sessions,
        "ticks": ticks, "chips": chips,
        "native": host.native_reason,
        "crossings": host.crossings, "plan_ticks": host.plan_ticks,
        "fast_slot_ticks": host.fast_slot_ticks,
        "rollback_loads": rollback_loads, "ticks_with_burst_gt1": deep_ticks,
        "compiles_in_ticks": compiled_in_ticks,
        "checksum_reports_sent": int(sent),
        "checksum_reports_compared": int(compared),
        "desyncs": int(desyncs),
        "checksum_lag_ticks_max": ex.checksum_lag_ticks_max,
        "carry_devices": shard_devices,
        # what a device holds of the carry, and how much of it the executor
        # keeps row-major between ticks (DESIGN.md section 3): 0 here, the
        # BoxGame ring is far under the rule
        "ring_resident_bytes": int(
            default_registry().value("ggrs_executor_ring_resident_bytes")),
        "ring_relaid_bytes": int(
            default_registry().value("ggrs_executor_ring_relaid_bytes")),
    }


def fence_sample(size: Dict[str, int], seed: int) -> Dict[str, Any]:
    """Time a chain of large bf16 matmuls ended by ``block_until_ready``:
    implied FLOP/s from the fastest of five runs (the reading most able to
    exceed the peak if the fence returned early) and the median time."""
    n, chain = size["fence_n"], size["fence_chain"]
    k1, k2 = jax.random.split(jax.random.key(seed))
    scale = 1.0 / math.sqrt(n)
    x = (jax.random.normal(k1, (n, n), jnp.float32) * scale).astype(jnp.bfloat16)
    w = (jax.random.normal(k2, (n, n), jnp.float32) * scale).astype(jnp.bfloat16)

    @jax.jit
    def chain_fn(x, w):
        return jax.lax.fori_loop(
            0, chain,
            lambda _, y: jnp.dot(y, w, preferred_element_type=jnp.bfloat16),
            x,
        )

    jax.block_until_ready(chain_fn(x, w))  # compile + first run
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = chain_fn(x, w)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    flops = chain * 2.0 * n ** 3
    return {
        "out": out,
        "median_s": float(np.median(times)),
        "tflops_fastest": flops / min(times) / 1e12,
    }


def leg_fence(size: Dict[str, int], seed: int, pre: Dict[str, Any],
              peak_tflops: float) -> Dict[str, Any]:
    t0 = time.perf_counter()
    # an explicit read, though the pool leg's checks have read already
    probe = float(jax.device_get(pre.pop("out")[0, 0]))
    check(math.isfinite(probe), "fence: the matmul chain produced a non-finite")
    post = fence_sample(size, seed)
    post.pop("out")
    ratio = pre["median_s"] / post["median_s"]
    if peak_tflops:  # a rehearsal has no peak to hold the readings against
        for when, s in (("before", pre), ("after", post)):
            check(s["tflops_fastest"] <= peak_tflops,
                  f"fence: {s['tflops_fastest']:.1f} TFLOP/s {when} the first "
                  f"read exceeds the {peak_tflops} TFLOP/s peak: "
                  f"block_until_ready returned early")
    check(0.5 <= ratio <= 2.0,
          f"fence: pre-read/post-read time ratio {ratio:.3f} is beyond 2x")
    return {
        "run_s": time.perf_counter() - t0,
        "pre_read_tflops": pre["tflops_fastest"],
        "post_read_tflops": post["tflops_fastest"],
        "pre_read_median_s": pre["median_s"],
        "post_read_median_s": post["median_s"],
        "pre_over_post": ratio,
        "fence_is_real": True,
    }


def digest_np(state: Dict[str, np.ndarray]) -> np.ndarray:
    """NumPy mirror of ``ops.checksum.checksum_device`` for a small pytree
    of 4-byte leaves: the four lane sums over the concatenated words, mixed
    with the structure salt."""
    leaves = [np.ascontiguousarray(l) for l in jax.tree_util.tree_leaves(state)]
    w = np.concatenate([l.view(np.uint32).ravel() for l in leaves])
    idx = np.arange(1, w.size + 1, dtype=np.uint32)
    rot = (w << np.uint32(13)) | (w >> np.uint32(19))
    lanes = np.stack([
        w, w * idx, w * (idx * _PRIME_A + np.uint32(1)), rot ^ (idx * _PRIME_B),
    ]).sum(axis=1, dtype=np.uint32)
    acc = _structure_salt(leaves) * _GOLDEN + lanes
    return acc ^ (acc >> np.uint32(15))


def leg_synctest(size: Dict[str, int], seed: int) -> Dict[str, Any]:
    ticks, chunk = size["synctest_ticks"], size["synctest_chunk"]
    game = BoxGame(2)
    sess = DeviceSyncTestSession(
        game.advance, game.init_state(), jnp.zeros((2,), jnp.uint8),
        check_distance=8, max_prediction=8,
    )
    inputs = np.random.default_rng(seed).integers(
        0, 16, size=(ticks, 2)
    ).astype(np.uint8)
    staged = [
        jnp.asarray(inputs[i:i + chunk]) for i in range(0, ticks, chunk)
    ]
    # the session compiles inside its first calls (the warm-up program,
    # then the steady program at the split and the full chunk shape): the
    # first two chunks are set-up, the rest are timed
    for c in staged[:2]:
        sess.run_ticks(c, check=False)
    sess.block_until_ready()
    t0 = time.perf_counter()
    for c in staged[2:]:
        sess.run_ticks(c, check=False)
    sess.block_until_ready()
    run_s = time.perf_counter() - t0
    sess.verify()  # raises MismatchedChecksum on any resim divergence
    lanes = jax.device_get(checksum_device(sess._carry["live"]))
    mirror = game.init_state_np()
    for f in range(ticks):
        mirror = game.advance_np(mirror, inputs[f])
    check(_equal_trees(sess.live_state(), mirror),
          "synctest: final state differs from the NumPy mirror")
    check(np.array_equal(lanes, digest_np(mirror)),
          "synctest: device digest differs from the NumPy mirror's")
    return {"run_s": run_s, "ticks": ticks,
            "timed_ticks": ticks - min(ticks, 2 * chunk),
            "check_distance": 8, "mismatches": 0}


def _rts_commands(rng: random.Random, max_cmds: int) -> Tuple:
    cmds = []
    for _ in range(rng.randrange(0, max_cmds + 1)):
        kind = rng.randrange(3)
        if kind == 0:
            cmds.append(("move", rng.randrange(4),
                         rng.randrange(-2, 3), rng.randrange(-2, 3)))
        elif kind == 1:
            cmds.append(("gather", rng.randrange(4)))
        else:
            cmds.append(("build", rng.randrange(16), rng.randrange(16)))
    return tuple(cmds)


def leg_games(size: Dict[str, int], seed: int) -> Dict[str, Any]:
    """jit(advance) of each bundled game at its BASELINE shape against its
    NumPy mirror — where a lowering the TPU compiler refuses shows first."""
    frames, vm_batch = size["game_frames"], size["chipvm_batch"]
    rng = np.random.default_rng(seed)
    run_s = 0.0

    def play(name: str, step: Callable, state: Any, device_inputs: List[Any],
             mirror: Callable[[], Any]) -> None:
        nonlocal run_s
        jax.block_until_ready(step(state, device_inputs[0]))  # compile
        t0 = time.perf_counter()
        for inp in device_inputs:
            state = step(state, inp)
        jax.block_until_ready(state)
        run_s += time.perf_counter() - t0
        check(_equal_trees(jax.device_get(state), mirror()),
              f"games: {name} differs from its advance_np after "
              f"{len(device_inputs)} frames")

    def replay_np(game: Any, inputs: Any) -> Any:
        state = game.init_state_np()
        for inp in inputs:
            state = game.advance_np(state, inp)
        return state

    box = BoxGame(2)
    box_in = rng.integers(0, 16, size=(frames, 2)).astype(np.uint8)
    play("BoxGame(2)", jax.jit(box.advance), box.init_state(),
         [jnp.asarray(i) for i in box_in], lambda: replay_np(box, box_in))

    ecs = EcsWorld(4, 32)
    ecs_in = rng.integers(0, 16, size=(frames, 4)).astype(np.uint8)
    play("EcsWorld(4, 32)", jax.jit(ecs.advance), ecs.init_state(),
         [jnp.asarray(i) for i in ecs_in], lambda: replay_np(ecs, ecs_in))

    vm = ChipVM(2)
    vm_in = rng.integers(0, 256, size=(frames, vm_batch, 2)).astype(np.uint8)
    vm_state = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (vm_batch,) + l.shape),
        vm.init_state(),
    )

    def vm_mirror() -> Any:
        per = [replay_np(vm, vm_in[:, b]) for b in range(vm_batch)]
        return {k: np.stack([s[k] for s in per]) for k in per[0]}

    play(f"ChipVM(2) x{vm_batch} under vmap", jax.jit(jax.vmap(vm.advance)),
         vm_state, [jnp.asarray(i) for i in vm_in], vm_mirror)

    rts = RtsCmd(2)
    prng = random.Random(seed)
    streams = [
        [_rts_commands(prng, rts.max_cmds) for _ in range(2)]
        for _ in range(frames)
    ]
    play("RtsCmd(2)", jax.jit(rts.advance), rts.init_state(),
         [jnp.asarray(rts.envelopes_np(s)) for s in streams],
         lambda: replay_np(rts, streams))
    return {"run_s": run_s, "frames": frames,
            "games": ["BoxGame(2)", "EcsWorld(4,32)",
                      f"ChipVM(2)x{vm_batch}", "RtsCmd(2)"]}


def leg_pallas(size: Dict[str, int], seed: int,
               interpret: bool) -> Dict[str, Any]:
    per_block = pc._BLOCK_ROWS * pc._LANES
    # the XLA side must be the XLA lanes even if the environment switched
    # the pallas digest on, or the kernel is compared with itself
    pc.use_pallas_checksums(False)
    kernel = jax.jit(
        functools.partial(pc.leaf_digest_pallas, interpret=True)
        if interpret else pc.leaf_digest_pallas
    )
    xla = jax.jit(_leaf_digest)
    facts: Dict[str, Any] = {"interpreted": interpret}
    run_s = 0.0
    lengths = (size["pallas_words"], 5 * per_block + 12345)
    for label, n in zip(("leaf", "ragged"), lengths):
        words = jax.random.bits(jax.random.key(seed + n), (n,), jnp.uint32)
        got, want = kernel(words), xla(words)  # compile + first run
        jax.block_until_ready((got, want))
        rates = {}
        for name, fn in (("pallas", kernel), ("xla", xla)):
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(words))
                times.append(time.perf_counter() - t0)
            run_s += sum(times)
            rates[name] = 4.0 * n / float(np.median(times)) / 1e9
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"pallas: kernel lanes differ from the XLA digest at n={n}")
        facts[f"{label}_words"] = n
        facts[f"{label}_pallas_gbs"] = rates["pallas"]
        facts[f"{label}_xla_gbs"] = rates["xla"]
        del words
    facts["run_s"] = run_s
    return facts


def leg_udp(size: Dict[str, int], seed: int) -> Dict[str, Any]:
    """The served transport: hosted peers on the kernel-batched datapath
    over real loopback UDP, their opponents plain per-session ``P2PSession``s
    fulfilled with ``advance_np`` on the host (the reference tier)."""
    matches, ticks = size["udp_matches"], size["udp_ticks"]
    game = BoxGame(2)
    clock = [0]
    host = HostSessionPool(native_io=True)
    peers, peer_states, sockets = [], [], []
    for m in range(matches):
        host_sock, peer_sock = UdpNonBlockingSocket(0), UdpNonBlockingSocket(0)
        sockets += [host_sock, peer_sock]
        host.add_session(
            _builder(clock, seed, m, 0, ("127.0.0.1", peer_sock.local_port())),
            host_sock,
        )
        peers.append(
            _builder(clock, seed, m, 1, ("127.0.0.1", host_sock.local_port()))
            .start_p2p_session(peer_sock)
        )
        peer_states.append(game.init_state_np())
    ex = _executor(game, matches, None)
    hosted = HostedPool(host, ex)
    check(host.native_active, f"udp: host tier is Python: {host.native_reason}")
    check(host.native_io_active, "udp: no slot attached to the native datapath")

    def fulfill(m: int, requests: List[Any]) -> None:
        for r in requests:
            kind = type(r).__name__
            if kind == "SaveGameState":
                r.cell.save(
                    r.frame, {k: v.copy() for k, v in peer_states[m].items()},
                    None,
                )
            elif kind == "LoadGameState":
                peer_states[m] = {k: v.copy() for k, v in r.cell.data().items()}
            else:
                peer_states[m] = game.advance_np(
                    peer_states[m],
                    np.asarray([v for v, _ in r.inputs], np.uint8),
                )

    hold_from = ticks - 3 * MAX_PREDICTION
    compiles0 = _compiles()
    t0 = time.perf_counter()
    for i in range(ticks):
        clock[0] = (i * 1000) // 60
        j = min(i, hold_from)
        for m, peer in enumerate(peers):
            peer.add_local_input(1, _schedule(seed, m, 1, j))
            fulfill(m, peer.advance_frame())
        hosted.tick([(m, 0, _schedule(seed, m, 0, j)) for m in range(matches)])
    hosted.block_until_ready()
    run_s = time.perf_counter() - t0

    check(_compiles() == compiles0, "udp: a compilation inside the ticks")
    check(host.crossings == ticks,
          f"udp: {host.crossings} crossings over {ticks} ticks")
    io = host.io_stats()
    check(io["recv_datagrams"] > io["recv_calls"] > 0,
          f"udp: receive batching not real: {io['recv_datagrams']} datagrams "
          f"in {io['recv_calls']} calls")
    live = jax.device_get(ex.live_states)
    for m in range(matches):
        check(host.slot_state(m) == "native" and host.io_state(m) == "native",
              f"udp: slot {m} is {host.slot_state(m)}/{host.io_state(m)}")
        check(host.current_frame(m) == ticks == peers[m].current_frame,
              f"udp: match {m} at frames {host.current_frame(m)}/"
              f"{peers[m].current_frame}, wanted {ticks}")
        hosted_state = {k: v[m] for k, v in live.items()}
        ref = _reference_state(game, seed, m, ticks, hold_from)
        check(_equal_trees(hosted_state, peer_states[m]),
              f"udp: match {m}: hosted device state differs from its peer")
        check(_equal_trees(hosted_state, ref),
              f"udp: match {m} differs from the advance_np replay")
    for s in sockets:
        s.close()
    return {"run_s": run_s, "matches": matches, "ticks": ticks,
            "loopback": "usable",
            "recv_datagrams": int(io["recv_datagrams"]),
            "recv_calls": int(io["recv_calls"])}


# ---------------------------------------------------------------------------


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="chips the pool leg spans (refused if the machine "
                         "has fewer)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="debugging: a comma-separated subset; a subset run "
                         "cannot print the passing verdict")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debugging: tiny sizes on whatever backend JAX has, "
                         "Pallas interpreted; cannot print the passing verdict")
    args = ap.parse_args(argv)
    legs = [l.strip() for l in args.legs.split(",") if l.strip()]
    unknown = [l for l in legs if l not in LEGS]
    if unknown or not legs:
        ap.error(f"unknown legs {unknown}; one of {LEGS}")
    rehearsal = args.rehearse_cpu

    if rehearsal:
        device = device_record()
        if device["count"] < args.chips:
            raise NoChipError(
                f"asked for {args.chips} devices, have {device['count']}"
            )
        peak_tflops = 0.0
    else:
        try:
            device = require_chip(args.chips)
        except NoChipError as e:
            # no chip, no result: one line on stderr, nothing on stdout
            sys.exit(f"chip_smoke: FAIL: {e}")
        peak_tflops = float(device_peaks(device["kind"])["bf16_tflops"])
    try:
        complete = run_legs(args, legs, rehearsal, device, peak_tflops)
    except BaseException:
        # a failed leg: the verdict line, then the exception, not survived
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise
    # the contract's last line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": complete, "device": device}), flush=True)
    return 0 if complete else NOT_A_CHIP_RESULT


def run_legs(args: argparse.Namespace, legs: List[str], rehearsal: bool,
             device: Dict[str, Any], peak_tflops: float) -> bool:
    """Run ``legs`` in order, print each leg's facts and the summary, and
    return whether this was the complete chip run.  A failed check raises."""
    tag = "REHEARSAL (not a chip result) " if rehearsal else ""
    size = REHEARSAL if rehearsal else REAL

    def version(dist: str) -> str:
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    versions = {d: version(d) for d in ("jax", "jaxlib", "libtpu")}
    cache_dir = place_compile_cache()
    cache_before = cache_entry_count(cache_dir)
    print(f"{tag}platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"chips_used={args.chips} "
          + " ".join(f"{k}={v}" for k, v in versions.items()), flush=True)
    print(f"{tag}compile cache: "
          f"{cache_dir or 'none (CPU backend, none placed from outside)'} "
          f"({cache_before} entries before)", flush=True)

    compiles_at_start = _compiles()
    report: Dict[str, Any] = {}

    def run_leg(name: str, fn: Callable[[], Dict[str, Any]],
                carried: int = 0) -> None:
        c0 = _compiles()
        t0 = time.perf_counter()
        facts = fn()  # a failed check raises; nothing catches it
        wall = time.perf_counter() - t0
        entry = {
            "verdict": "pass",
            "run_s": round(facts.pop("run_s"), 3),
            "wall_s": round(wall, 3),
            "compiles": _compiles() - c0 + carried,
            **facts,
        }
        report[name] = entry
        print(f"{tag}leg {name}: PASS " + json.dumps(entry), flush=True)

    # the fence leg's pre-read sample has to be the first device work
    if "fence" in legs:
        c0 = _compiles()
        fence_pre = fence_sample(size, args.seed)
        fence_pre_compiles = _compiles() - c0
    if "pool" in legs:
        run_leg("pool", lambda: leg_pool(
            size, args.seed, args.chips, str(device["platform"])))
    if "fence" in legs:
        run_leg("fence", lambda: leg_fence(
            size, args.seed, fence_pre, peak_tflops), fence_pre_compiles)
    if "synctest" in legs:
        run_leg("synctest", lambda: leg_synctest(size, args.seed))
    if "games" in legs:
        run_leg("games", lambda: leg_games(size, args.seed))
    if "pallas" in legs:
        run_leg("pallas", lambda: leg_pallas(size, args.seed, rehearsal))
    if "udp" in legs:
        run_leg("udp", lambda: leg_udp(size, args.seed))

    cache_after = cache_entry_count(cache_dir)
    requests = _compiles() - compiles_at_start
    print(f"{tag}compile cache: {cache_after} entries after "
          f"(+{cache_after - cache_before}) of {requests} compile requests",
          flush=True)

    complete = not rehearsal and legs == list(LEGS)
    summary = {
        "ok": complete,
        "device": device,
        "chips_used": args.chips,
        "versions": versions,
        "seed": args.seed,
        "legs": report,
        "compile": {
            "cache_dir": cache_dir,
            "entries_before": cache_before,
            "entries_after": cache_after,
            "requests": requests,
        },
    }
    if rehearsal:
        summary["rehearsal"] = True
    if legs != list(LEGS):
        summary["partial"] = legs
    summary["claim"] = None
    if complete:  # the record of a chip run; debugging runs leave none
        out_dir = Path(__file__).resolve().parent / "chiprun_out"
        out_dir.mkdir(exist_ok=True)
        (out_dir / "chip_smoke.json").write_text(
            json.dumps(summary, indent=1) + "\n"
        )
    print(f"{tag}summary: " + json.dumps(summary), flush=True)
    return complete


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
