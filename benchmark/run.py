#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The entry the measured window drives is ``HostedPool.tick``, as a server's
loop calls it: staged inputs -> one native bank crossing -> ``RequestPlan``
-> one dispatch of ``BatchedRequestExecutor`` -> the device tick program and
its state ring.  A cell is an entry of ``workloads``: a configuration file
(``benchmark/configs/``) under a traffic file (``benchmark/traffic/``) at the
size its own file states (``benchmark/cells/``); every metric is a file under
``benchmark/metrics/`` naming a reducer under ``benchmark/reducers/``.
Nothing here knows any one cell.

A run: set-up (build the cell's sessions, warm its one tick program, play the
match's first ticks), the window (``--seconds``; a closed loop runs ticks
back to back and counts the final drain, an open loop times every tick from
when it was *due*), with ``--trace 1`` a short profiled slice after the
window, then untimed ticks with inputs held so that both peers converge, and
the comparison with the plain reference that decides ``correct``.  The last
line of standard output is the result; there is no CPU fallback.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional, Set, Tuple  # noqa: E402

import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax  # noqa: E402

from benchmark import generator, roofline, trace_reduce  # noqa: E402
from benchmark.reference import digest as reference_digest  # noqa: E402
from ggrs_tpu.core import Local, Remote  # noqa: E402
from ggrs_tpu.net import InMemoryNetwork  # noqa: E402
from ggrs_tpu.obs.registry import default_registry  # noqa: E402
from ggrs_tpu.parallel import (  # noqa: E402
    BatchedRequestExecutor,
    HostedPool,
    HostSessionPool,
    make_mesh,
)
from ggrs_tpu.sessions import SessionBuilder  # noqa: E402
from ggrs_tpu.utils.device import place_compile_cache, require_chip  # noqa: E402

SPAN_PREFIX = "bench."
TICK_PROGRAM = "jit_tick"
RING_SAMPLES = 16
SAVING = ("every_frame", "sparse")  # a configuration's "saving"; absent: the first


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileMeter:
    """Counts backend compile requests from JAX's own monitoring events (a
    copy of ``chip_smoke.py``'s): a program fetched from the persistent cache
    still counts, so any program first met inside the window shows."""

    def __init__(self) -> None:
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def peaks_for(kind: str) -> Dict[str, Any]:
    """The benchmark's own table of published peaks; an unknown device kind
    is an error, not a default."""
    table = json.loads((REPO / "benchmark" / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(
            f"device_kind {kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[kind]


# ---------------------------------------------------------------------------
# the cell: BENCHMARK.json entry -> configuration, traffic, metrics
# ---------------------------------------------------------------------------


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _metric_file(root: Path, name: str) -> Dict[str, Any]:
    """``metrics/<name>.json``, or the file of the quantity (the name without
    its last dotted part) where ``bank_ms_p50.sat`` and ``bank_ms_p50.paced``
    differ only in what ``BENCHMARK.json`` says they move."""
    folder = root / "benchmark" / "metrics"
    for stem in (name, name.rpartition(".")[0]):
        if stem and (folder / f"{stem}.json").is_file():
            return json.loads((folder / f"{stem}.json").read_text())
    raise SystemExit(f"no file under benchmark/metrics for metric {name!r}")


def load_cell(root: Path, workload: str) -> Dict[str, Any]:
    """The cell's ``workloads`` entry with its configuration, its traffic
    mix, its own file (population, traced ticks) and its metrics, each
    ``BENCHMARK.json`` entry joined with the reader's file."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} (known: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    if config.get("saving", SAVING[0]) not in SAVING:
        raise SystemExit(f"{entry['file']}: saving must be one of {SAVING}, "
                         f"not {config['saving']!r}")
    traffic = generator.load_traffic(
        root / "benchmark" / "traffic" / f"{cell['traffic']}.json"
    )
    size = json.loads(
        (root / "benchmark" / "cells" / f"{workload}.json").read_text()
    )
    metrics = {
        kind: [dict(_metric_file(root, m["name"]), **m)
               for m in bench[kind] if _applies(m, workload)]
        for kind in ("end_to_end", "per_layer")
    }
    return {"cell": cell, "config": config, "traffic": traffic, "size": size,
            "metrics": metrics}


def saves_sparsely(config: Dict[str, Any]) -> bool:
    """GGRS's sparse saving (``SessionBuilder::with_sparse_saving_mode``):
    a session saves only confirmed frames and rolls back to its last save."""
    return config.get("saving") == "sparse"


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


class Pool:
    """One cell's population: ``matches`` matches of ``players`` sessions
    each (one local player, the others remote) in one ``HostSessionPool``
    over one in-memory network and one shared virtual clock, fulfilled by one
    ``BatchedRequestExecutor``.  Session ``m * players + k`` is player ``k``
    of match ``m``.  Every session saves as the configuration's ``saving``
    says."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 matches: int, seed: int, chips: int = 1) -> None:
        adapter = importlib.import_module(f"benchmark.adapters.{config['adapter']}")
        players = int(config["players"])
        self.matches, self.players = matches, players
        self.sessions = matches * players
        self.clock = [0]
        self.net = InMemoryNetwork(
            seed=seed, latency_ticks=int(traffic["latency_ticks"]),
            loss=float(traffic["loss"]),
        )
        self.host = HostSessionPool()
        for m in range(matches):
            for k in range(players):
                builder = (
                    SessionBuilder(adapter.session_config())
                    .with_num_players(players)
                    .with_clock(lambda: self.clock[0])
                    .with_rng(random.Random(seed * 7919 + 5 * m + k))
                    .with_max_prediction_window(int(config["max_prediction"]))
                    .with_input_delay(int(config["input_delay"]))
                )
                if saves_sparsely(config):
                    builder = builder.with_sparse_saving_mode(True)
                for j in range(players):
                    who = Local() if j == k else Remote(f"m{m}p{j}")
                    builder = builder.add_player(who, j)
                self.host.add_session(builder, self.net.socket(f"m{m}p{k}"))
        game = adapter.make_game(config)
        # a cell on several chips shards the session axis over a mesh of them
        across = {"mesh": make_mesh(chips)} if chips > 1 else {}
        self.executor = BatchedRequestExecutor(
            game.advance, game.init_state(), adapter.inputs_to_array,
            batch_size=self.sessions,
            ring_length=int(config["ring_length"]),
            max_burst=int(config["max_burst"]),
            raw_inputs_to_array=adapter.raw_inputs_to_array,
            **across,
        )
        # the cell's one tick program and the slot probe, and nothing else
        self.executor.warmup(adapter.example_inputs(config))
        self.hosted = HostedPool(self.host, self.executor)
        self._slots = list(range(self.sessions))
        self._handles = [k for _ in range(matches) for k in range(players)]
        self.ticks = 0

    def tick(self, row: np.ndarray) -> None:
        """One pool tick with this tick's inputs, ``row[m, k]``."""
        self.clock[0] = (self.ticks * 1000) // 60
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "generate"):
            items = list(zip(self._slots, self._handles, row.reshape(-1).tolist()))
        self.hosted.tick(items)
        self.net.tick()
        self.ticks += 1

    def fence(self) -> None:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "fence"):
            self.hosted.block_until_ready()


class Inputs:
    """The cell's input schedule from ``--seed``, made in set-up for as many
    ticks as the mix says a run can reach (nothing is generated inside the
    window); from ``hold_from`` on the last row repeats."""

    def __init__(self, traffic: Dict[str, Any], seed: int, matches: int,
                 players: int, ticks: int, delay: int) -> None:
        self.rows = generator.schedule(traffic, seed, matches, players, ticks)
        self.delay = delay
        self.hold_from: Optional[int] = None

    def row(self, i: int) -> np.ndarray:
        """What every player presses at tick ``i``."""
        if self.hold_from is not None:
            i = min(i, self.hold_from)
        if i >= len(self.rows):
            raise RuntimeError(
                f"tick {i} is past the {len(self.rows)} the schedule was made "
                f"for: the mix's max_ticks_per_s is too low for this host")
        return self.rows[i]

    def frame_row(self, f: int) -> np.ndarray:
        """What frame ``f`` is simulated with: the row pressed ``delay``
        ticks earlier, and before any could arrive the blank input."""
        if f < self.delay:
            return np.zeros_like(self.rows[0])
        return self.row(f - self.delay)


class Spans:
    """Host-clock spans around the calls into each layer, taken by wrapping
    the bound methods on the instances so that ``HostedPool.tick`` stays the
    entry.  Each is a ``TraceAnnotation`` too, so a traced slice can name
    idle gaps.  Installed in a ``--trace 1`` run only."""

    def __init__(self, pool: Pool, state_bytes: int) -> None:
        self.stage_s: List[float] = []
        self.advance_s: List[float] = []
        self.dispatch_s: List[float] = []
        self.bytes_needed: List[int] = []
        self._wrap(pool.host, "stage_inputs", "bank", self.stage_s)
        self._wrap(pool.host, "advance_all", "bank", self.advance_s)
        run = self._wrap(pool.executor, "run", "dispatch", self.dispatch_s)

        def counted(plan: Any) -> None:
            if getattr(plan, "quiet_rows", None) is not None:
                self.bytes_needed.append(roofline.bytes_needed(
                    roofline.plan_counts(plan), state_bytes))
            run(plan)

        pool.executor.run = counted

    @staticmethod
    def _wrap(obj: Any, attr: str, span: str, into: List[float]) -> Callable:
        inner = getattr(obj, attr)

        def wrapped(*args: Any, **kwargs: Any) -> Any:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + span):
                out = inner(*args, **kwargs)
            into.append(time.perf_counter() - t)
            return out

        setattr(obj, attr, wrapped)
        return wrapped

    def series_ms(self, n: int) -> Dict[str, List[float]]:
        """The first ``n`` ticks' spans: the window's, before any slice."""
        bank = [1e3 * (a + b) for a, b in zip(self.stage_s[:n], self.advance_s[:n])]
        return {"bank_ms": bank,
                "dispatch_ms": [1e3 * d for d in self.dispatch_s[:n]]}


# ---------------------------------------------------------------------------
# the loops
# ---------------------------------------------------------------------------


def closed_loop(pool: Pool, inputs: Inputs, seconds: Optional[float],
                ticks: Optional[int], fence_every: int) -> Dict[str, Any]:
    """Ticks back to back for ``seconds`` (or exactly ``ticks``), a fence
    every ``fence_every``-th tick to bound how far the host runs ahead, and
    one at the end: the rate is all work over all time, drain included."""
    n = 0
    bubbles: List[float] = []
    fences: List[float] = []
    t0 = fenced = time.perf_counter()  # the loop starts on a drained device
    while (n < ticks) if ticks is not None else (time.perf_counter() - t0 < seconds):
        pool.tick(inputs.row(pool.ticks))
        if fenced is not None:
            # the device sat idle from the fence's end until this dispatch
            bubbles.append(time.perf_counter() - fenced)
            fenced = None
        n += 1
        if fence_every and n % fence_every == 0:
            pool.fence()
            fenced = time.perf_counter()
            fences.append(fenced)
    t_issued = time.perf_counter()
    pool.fence()
    t1 = time.perf_counter()
    periods = np.diff([t0] + fences)
    out = {"ticks": n, "window_s": t1 - t0, "drain_s": t1 - t_issued,
           "bubble_s": sum(bubbles), "fences": len(bubbles),
           "fence_period_s": periods.tolist(),
           # what follows the last fence is no period: a stall there shows here
           "tail": {"ticks": n % fence_every if fence_every else n,
                    "s": t1 - (fences[-1] if fences else t0)}}
    if len(periods):
        # was the run disturbed from inside: the host's speed wanders over
        # some ten seconds (the quarters), a stall makes a few long periods
        p0, p25, p50, p75, p100 = np.percentile(periods, [0, 25, 50, 75, 100])
        out["fence_period_quartiles_s"] = {
            "min": float(p0), "p25": float(p25), "p50": float(p50),
            "p75": float(p75), "max": float(p100)}
        out["tick_ms_by_quarter"] = [
            1e3 * float(q.sum()) / (len(q) * fence_every)
            for q in np.array_split(periods, 4) if len(q)]
        out["long_periods"] = [[int(i), float(periods[i])]
                               for i in np.flatnonzero(periods > 1.5 * p50)][:8]
    return out


def open_loop(pool: Pool, inputs: Inputs, ticks: int, rate_hz: float,
              spin_s: float) -> Dict[str, Any]:
    """Tick ``i`` is due at ``t0 + i / rate_hz``, starts as soon as it is due
    and the one before is fenced, is fenced itself, and is timed from when it
    was due; a late generator never skips a tick.  The wait sleeps to
    ``spin_s`` before the due time and then spins on the clock, so that the
    core that runs the tick has not just come out of a sleep."""
    period = 1.0 / rate_hz
    tick_ms, late_ms, host_ms = [], [], []
    t0 = time.perf_counter() + 0.003
    for i in range(ticks):
        due = t0 + i * period
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "spin"):
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                if due - now > spin_s:
                    time.sleep(due - now - spin_s)
        pool.tick(inputs.row(pool.ticks))
        issued = time.perf_counter()
        pool.fence()
        end = time.perf_counter()
        late_ms.append(1e3 * (now - due))
        host_ms.append(1e3 * (issued - now))
        tick_ms.append(1e3 * (end - due))
    worst = int(np.argmax(tick_ms))
    # where the window's slowest tick spent its time: a stall is named by it;
    # the quarters' medians tell noise inside a process from noise between
    return {"ticks": ticks, "window_s": time.perf_counter() - t0,
            "tick_ms": tick_ms, "late_ms": late_ms,
            "tick_ms_p50_by_quarter": [
                float(np.median(q)) for q in np.array_split(tick_ms, 4) if len(q)],
            "worst_tick": {"index": worst, "tick_ms": tick_ms[worst],
                           "late_ms": late_ms[worst], "host_ms": host_ms[worst],
                           "fence_ms": tick_ms[worst] - late_ms[worst]
                           - host_ms[worst]}}


def run_loop(pool: Pool, inputs: Inputs, traffic: Dict[str, Any],
             seconds: Optional[float], ticks: Optional[int]) -> Dict[str, Any]:
    if traffic["loop"] == "open":
        rate = float(traffic["rate_hz"])
        n = ticks if ticks is not None else max(1, round(seconds * rate))
        return open_loop(pool, inputs, n, rate, float(traffic["spin_ms"]) / 1e3)
    return closed_loop(pool, inputs, seconds, ticks, int(traffic["fence_every"]))


def traced_slice(pool: Pool, inputs: Inputs, traffic: Dict[str, Any],
                 ticks: int) -> Dict[str, Any]:
    """``ticks`` more ticks of the same loop under the profiler, reduced and
    deleted.  The window's own ticks never run under it."""
    trace_dir = Path(tempfile.mkdtemp(prefix="ggrs_bench_trace_"))
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            run_loop(pool, inputs, traffic, None, ticks)
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        reduced = trace_reduce.reduce_trace(trace_dir, TICK_PROGRAM, SPAN_PREFIX)
        size = sum(f.stat().st_size for f in trace_dir.rglob("*") if f.is_file())
        log(f"traced slice: {ticks} ticks, {size / 1e6:.1f} MB of trace reduced "
            f"in {time.perf_counter() - t:.1f} s")
        return reduced
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# correct: the pool against the plain reference
# ---------------------------------------------------------------------------


def reference_states(config: Dict[str, Any], inputs: Inputs, matches: int,
                     frames: int, keep: Set[int]):
    """The reference replayed over every match's true inputs for ``frames``
    frames: its state after ``f`` frames for each ``f`` in ``keep``, and how
    often it saw what the family's ``witness`` counts (None: it has none)."""
    ref = importlib.import_module(f"benchmark.reference.{config['adapter']}")
    witness = getattr(ref, "witness", None)
    state = ref.init_state(config, matches)
    kept = {0: state} if 0 in keep else {}
    seen = 0
    for f in range(frames):
        state = ref.advance(config, state, inputs.frame_row(f))
        if witness is not None:
            seen += witness(state)
        if f + 1 in keep:
            kept[f + 1] = state
    return kept, (seen if witness is not None else None)


def ring_frames(pool: Pool) -> np.ndarray:
    """``[sessions, ring_length]``: the frame each ring slot holds, as the
    device tags it (negative: never written).  The executor has no public
    reader of the tags; ``ring_state`` and ``ring_checksum`` read the same
    array to validate a slot."""
    return np.asarray(jax.device_get(pool.executor._carry["ring"]["frames"]))


def ring_draws(seed: int, sessions: int, frames: int, depth: int,
               held: Optional[np.ndarray] = None) -> List[Tuple[int, int]]:
    """The ring slots ``correct`` reads: ``RING_SAMPLES`` (session, frame)
    pairs drawn from the seed.  A ring that saves every frame holds the last
    ``depth`` frames, all confirmed by the hold, and a draw is a session and
    one of them (a frame before the first is no draw).  Under sparse saving a
    session saves only confirmed frames, so every frame its ring holds
    (``held[session]``) is due, whatever its age: a draw is a session whose
    ring holds a frame and one of those frames (a ring that holds none is
    ``ring_behind_sessions``' to count)."""
    rng = random.Random(seed)
    draws: List[Tuple[int, int]] = []
    if held is not None:
        holding = [s for s in range(sessions) if (held[s] >= 0).any()]
        for _ in range(RING_SAMPLES if holding else 0):
            s = holding[rng.randrange(len(holding))]
            mine = sorted(int(f) for f in held[s] if f >= 0)
            draws.append((s, mine[rng.randrange(len(mine))]))
        return draws
    for _ in range(RING_SAMPLES):
        s = rng.randrange(sessions)
        f = frames - 1 - rng.randrange(depth)
        if f >= 0:
            draws.append((s, f))
    return draws


def wrong_sessions(live: Dict[str, Any], want: Dict[str, np.ndarray],
                   players: int) -> np.ndarray:
    """Which sessions' states (``live[k][session]``) differ in any bit, dtype
    or shape from their match's reference state (``want[k][match]``)."""
    sessions = len(next(iter(want.values()))) * players
    if set(live) != set(want):
        return np.ones(sessions, bool)
    wrong = np.zeros(sessions, bool)
    for k, ref in want.items():
        got, ref = np.asarray(live[k]), np.repeat(ref, players, axis=0)
        if got.shape != ref.shape or got.dtype != ref.dtype:
            return np.ones(sessions, bool)
        wrong |= (got != ref).reshape(sessions, -1).any(axis=1)
    return wrong


def compare(pool: Pool, config: Dict[str, Any], inputs: Inputs, seed: int,
            hold: int, witness_by_frame: Optional[int]) -> Dict[str, int]:
    """Every session's live state after the run, and a sample of ring slots
    drawn from the seed (the saved state and the digest the device keeps of
    it), against the reference; exact, so each limit is 0.  A pool that saves
    sparsely is also held to keeping a save within ``max_prediction`` frames
    of where each session stands (``ring_behind_sessions``), and to saving
    sparsely at all (``ring_every_frame_sessions``)."""
    frames, players = pool.ticks, pool.players
    ring = int(config["ring_length"])
    depth = max(1, min(ring - 1, hold))  # saved frames all confirmed by the hold
    held = ring_frames(pool) if saves_sparsely(config) else None
    draws = ring_draws(seed, pool.sessions, frames, depth, held)
    keep = {frames} | {f for _, f in draws}
    ref, seen = reference_states(config, inputs, pool.matches, frames, keep)
    live = jax.device_get(pool.executor.live_states)
    wrong = wrong_sessions(live, ref[frames], players)
    ring_wrong = digest_wrong = 0
    for s, f in draws:
        want = {k: v[s // players] for k, v in ref[f].items()}
        try:
            got = pool.executor.ring_state(s, f)
            same = all(np.array_equal(np.asarray(got[k]), want[k]) for k in want)
            digest = pool.executor.ring_checksum(s, f)
        except (RuntimeError, KeyError, AssertionError):
            same, digest = False, None
        ring_wrong += not same
        digest_wrong += digest != reference_digest.u128(want)
    behind = sum(
        max(0, frames - pool.host.current_frame(s)) for s in range(pool.sessions)
    )
    checks = {"state_mismatch_sessions": int(wrong.sum()),
              "ring_mismatch_samples": int(ring_wrong),
              "digest_mismatch_samples": int(digest_wrong),
              "session_ticks_missing": int(behind)}
    if held is not None:
        # what check_last_saved_state exists to prevent: the newest save
        # slid out of the prediction window (or no save at all).  And a ring
        # that holds its last ring_length frames saved every frame: once the
        # hold has confirmed every input, a sparse session saves only where
        # its last save lies max_prediction frames back, so its newest save
        # never has the frame before it saved too
        window, late, dense = int(config["max_prediction"]), 0, 0
        for s in range(pool.sessions):
            mine = held[s][held[s] >= 0]
            late += (not mine.size
                     or pool.host.current_frame(s) - int(mine.max()) > window)
            dense += (mine.size == ring
                      and int(mine.max()) - int(mine.min()) == ring - 1)
        checks["ring_behind_sessions"] = int(late)
        checks["ring_every_frame_sessions"] = int(dense)
    if seen is not None and witness_by_frame is not None:
        if frames >= witness_by_frame:
            # what the cell's `why` says the traffic exercises really happened
            checks["reference_saw_no_witness"] = int(seen == 0)
        log(f"reference witness: {seen} over {frames} frames "
            f"(held to it from frame {witness_by_frame})")
    return checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _reduce(metric: Dict[str, Any], facts: Dict[str, Any]) -> Optional[float]:
    reducer = importlib.import_module(f"benchmark.reducers.{metric['reducer']}")
    return reducer.reduce(facts, metric.get("args", {}))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = REPO, t0: Optional[float] = None,
             matches: Optional[int] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object.  ``matches``
    (a rehearsal's size) is for the tests under ``tests/benchmark``; the
    command line cannot set it."""
    t0 = time.perf_counter() if t0 is None else t0
    spec = load_cell(root, workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    size = spec["size"]
    device = require_chip(int(cell["chips"]))
    peaks = peaks_for(str(device["kind"]))
    log(f"device: platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} workload={workload} seed={seed}")
    cache_dir = place_compile_cache()
    meter = CompileMeter()

    if matches is None:
        matches = int(size["matches"])
    players = int(config["players"])
    hold = int(traffic["hold_windows"]) * int(config["max_prediction"])
    warm_ticks = int(traffic["warm_ticks"])
    trace_ticks = int(size["trace_ticks"]) if trace else 0
    reach = int(seconds * float(traffic.get("rate_hz") or traffic["max_ticks_per_s"]))
    inputs = Inputs(traffic, seed, matches, players,
                    warm_ticks + reach + trace_ticks + hold + 1,
                    int(config["input_delay"]))
    t_built = time.perf_counter()
    pool = Pool(config, traffic, matches, seed, int(cell["chips"]))
    t_pool = time.perf_counter()
    ref = importlib.import_module(f"benchmark.reference.{config['adapter']}")
    spans = Spans(pool, ref.state_bytes(config)) if trace else None

    # the match's first ticks are set-up: sessions start, first saves land
    for _ in range(warm_ticks):
        pool.tick(inputs.row(pool.ticks))
        pool.fence()
    if spans is not None:
        for series in (spans.stage_s, spans.advance_s, spans.dispatch_s,
                       spans.bytes_needed):
            series.clear()
    gc.collect()
    gc.freeze()  # set-up's garbage is not the window's; no collector is off
    registry = default_registry()
    loads0 = registry.value("ggrs_executor_rollback_loads_total") or 0.0
    compiles0 = meter.compiles
    setup_s = time.perf_counter() - t0
    log(f"set-up: {setup_s:.3f} s (to imports and device {t_built - t0:.3f}, "
        f"sessions and executor {t_pool - t_built:.3f}, first {warm_ticks} ticks "
        f"{setup_s - (t_pool - t0):.3f}), {meter.compiles} programs, cache "
        f"{cache_dir}, {pool.sessions} sessions, native bank: "
        f"{pool.host.native_reason}")

    collections0 = [g["collections"] for g in gc.get_stats()]
    window = run_loop(pool, inputs, traffic, float(seconds), None)
    window["gc_collections"] = [
        g["collections"] - c for g, c in zip(gc.get_stats(), collections0)]
    compiles_in_window = meter.compiles - compiles0
    loads = (registry.value("ggrs_executor_rollback_loads_total") or 0.0) - loads0
    window_ticks = window["ticks"]
    sliced = traced_slice(pool, inputs, traffic, trace_ticks) if trace else {}
    slice_bytes = sum(spans.bytes_needed[window_ticks:]) if trace else 0
    peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()[: int(cell["chips"])]
    )

    # inputs hold so that repeat-last predictions come true and every peer
    # converges on the true simulation; untimed
    inputs.hold_from = pool.ticks - 1
    for _ in range(hold):
        pool.tick(inputs.row(pool.ticks))
    pool.fence()

    host = pool.host
    off_bank = sum(host.slot_state(s) != "native" for s in range(pool.sessions))
    checks = compare(pool, config, inputs, seed, hold,
                     size.get("witness_by_frame"))
    checks.update({
        "compiles_in_window": compiles_in_window,
        "bank_crossings_off_ticks": abs(host.crossings - pool.ticks),
        "plan_ticks_off_ticks": abs(host.plan_ticks - pool.ticks),
        "slots_off_bank": int(off_bank),
        "native_bank_inactive": int(not host.native_active),
        "window_without_rollback": int(loads <= 0),
    })
    correct = all(v == 0 for v in checks.values())

    session_ticks = window_ticks * pool.sessions
    tick_ms = window.get("tick_ms", [])
    facts: Dict[str, Any] = {
        "peaks": peaks,
        "series": {"tick_ms": tick_ms, "late_ms": window.get("late_ms", []),
                   "fence_period_s": window.get("fence_period_s", [])},
        "counts": {
            "setup_s": setup_s,
            "window_s": window["window_s"],
            "ticks": window_ticks,
            "session_ticks": session_ticks,
            "rollback_loads": loads,
            "peak_bytes": peak_bytes or None,  # the CPU backend reports none
        },
    }
    for k in ("drain_s", "bubble_s"):
        if k in window:
            facts["counts"][k] = window[k]
    if spans is not None:
        facts["series"].update(spans.series_ms(window_ticks))
    if sliced:
        programs = sliced["program_ms"]
        facts["series"]["program_ms"] = programs
        facts["counts"].update({
            "busy_s": sliced["busy_s"],
            "trace_window_s": sliced["window_s"],
            "slice_program_s": sum(programs) / 1e3 / sliced["chips"],
            "slice_bytes_needed": slice_bytes,
        })

    metrics: Dict[str, Dict[str, Any]] = {}
    for metric in spec["metrics"]["per_layer" if trace else "end_to_end"]:
        value = _reduce(metric, facts)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": int(peak_bytes)}
    if sliced:
        out_device.update(busy_s=sliced["busy_s"], window_s=sliced["window_s"])
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(session_ticks),
        "failed": int(checks["session_ticks_missing"]),
        "metrics": metrics,
        "device": out_device,
    }
    if sliced:
        result["breakdown"] = {"device_ops": sliced["device_ops"],
                               "idle_gaps": sliced["idle_gaps"]}
    result["window"] = {k: v for k, v in window.items()
                        if k not in ("tick_ms", "late_ms", "fence_period_s")}
    if tick_ms:  # which ticks overran their frame: a stall shows as a run of them
        frame_ms = 1e3 / float(traffic["rate_hz"])
        result["window"]["late_ticks"] = [
            [i, v] for i, v in enumerate(tick_ms) if v > frame_ms][:40]
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for name, v in checks.items():
        log(f"check {name}: {v} (limit 0)")
    log(f"correct: {correct}")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t0=_PROCESS_T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
