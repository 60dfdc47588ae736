"""The one tracer on the served path (DESIGN.md §14): ``HostedPool.tick``
under ``obs.trace.default_tracer()``.

- tracing does not choose the path: a traced pool's plan decode, crossings
  and device state are the untraced pool's;
- the span tree of a tick: every span names its parent and its tick,
  children lie inside parents, the leaves cover the tick;
- one clock: under ``jax.profiler`` every span is a ``ggrs.*`` event on the
  profile's host plane, the root's ``perf_ns`` anchors the two clocks, and
  the tracer sleeps again when the profile stops;
- the bank's phase timers are armed on the tracer's transitions only;
- the tick program's parts carry their names into the lowered program.
"""

from __future__ import annotations

import re
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import program_spans, run  # noqa: E402
from ggrs_tpu.net import _native  # noqa: E402
from ggrs_tpu.obs import NULL_TRACER, Tracer, default_tracer  # noqa: E402
from ggrs_tpu.obs.trace import (  # noqa: E402
    ANNOTATION_PREFIX,
    profile_clock_offset_ns,
    span_stats,
    spans_by_tick,
)

pytestmark = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)

CELL = "boxgame-2p.wan-60hz"
SEED = 2**31 + 25
TREE = {  # span -> parent, as DESIGN.md §14 and PERF.md §3 table them
    "hosted.tick": None,
    "pool.stage": "hosted.tick",
    "pool.tick": "hosted.tick",
    "pool.build_cmd": "pool.tick",
    "bank.crossing": "pool.tick",
    "pool.decode": "pool.tick",
    "pool.supervise": "pool.tick",
    "device.dispatch": "hosted.tick",
    "device.fill": "device.dispatch",
    "device.descriptors": "device.fill",
    "device.fulfill": "device.fill",
    "device.launch": "device.dispatch",
    "device.fence": None,
}


def build(matches: int = 8, ticks: int = 400):
    spec = run.load_cell(REPO, CELL)
    pool = run.Pool(spec["config"], spec["traffic"], matches, SEED)
    inputs = run.Inputs(spec["traffic"], SEED, matches, 2, ticks, 2)
    return pool, inputs


def drive(pool, inputs, ticks: int) -> None:
    for _ in range(ticks):
        pool.tick(inputs.row(pool.ticks))
        pool.fence()


@pytest.fixture
def tracer():
    t = default_tracer()
    t.switch(False)
    t.clear()
    yield t
    t.switch(False)
    t.clear()


def test_the_default_tracer_is_what_pools_use_and_is_off(tracer):
    pool, inputs = build(2)
    assert pool.host.tracer is tracer and pool.executor.tracer is tracer
    assert not tracer.enabled
    assert tracer.span("x") is tracer.span("y")  # the shared no-op
    assert tracer.root_span("x") is tracer.span("y")
    drive(pool, inputs, 8)
    assert len(tracer) == 0 and not pool.host._trace_native
    # the hard no-op never wakes, whatever the profiler does
    assert NULL_TRACER.refresh() is False
    with NULL_TRACER.root_span("x") as span:
        span.set(n=1)
    assert len(NULL_TRACER) == 0


def test_tracing_does_not_choose_the_path(tracer):
    """Same seed, tracer on against off: the descriptor plane decodes every
    tick, one crossing a tick, and the device state and every datagram on
    the wire are bit-identical."""
    legs = {}
    for on in (False, True):
        tracer.switch(on)
        pool, inputs = build(4)
        wire, send = [], pool.net._send
        pool.net._send = lambda src, dst, payload: (
            wire.append((src, dst, bytes(payload))), send(src, dst, payload))
        drive(pool, inputs, 90)
        host = pool.host
        assert host.plan_ticks == host.crossings == pool.ticks == 90
        assert host._trace_native is on
        legs[on] = (jax.device_get(pool.executor.live_states),
                    host.fast_slot_ticks, host.desc_slow_slots,
                    pool.executor._host_frames.copy(), wire)
    tracer.switch(False)
    for a, b in zip(jax.tree_util.tree_leaves(legs[False][0]),
                    jax.tree_util.tree_leaves(legs[True][0])):
        np.testing.assert_array_equal(a, b)
    assert legs[False][1:3] == legs[True][1:3]
    np.testing.assert_array_equal(legs[False][3], legs[True][3])
    assert legs[False][4] == legs[True][4] and len(legs[True][4]) > 90


def test_span_tree_of_a_tick(tracer):
    pool, inputs = build(24)
    drive(pool, inputs, 40)  # first saves, first rollbacks, warm caches
    tracer.switch(True)
    drive(pool, inputs, 60)
    tracer.switch(False)
    drive(pool, inputs, 4)
    events = tracer.events()
    assert tracer.dropped == 0
    stats = span_stats(events)
    for name, parent in TREE.items():
        assert stats[name]["parent"] == parent, name
        assert stats[name]["count"] == 60, name
    ticks = spans_by_tick(events)
    assert sorted(ticks) == list(range(41, 101))
    phases = {f"bank.{n}" for n in _native.BANK_PHASES}
    for tick, evs in ticks.items():
        spans = {e[1]: (e[3], e[3] + e[4]) for e in evs}
        for e in evs:
            name, args = e[1], e[6]
            assert args["tick"] == tick
            parent = args.get("parent")
            if parent is None:
                assert name in ("hosted.tick", "device.fence")
                continue
            lo, hi = spans[parent]
            assert lo <= e[3] and e[3] + e[4] <= hi, (tick, name, parent)
            if name in phases:
                assert parent == ("pool.stage" if name == "bank.staging"
                                  else "bank.crossing")
        decode = next(e for e in evs if e[1] == "pool.decode")[6]
        assert decode["slots"] == 48
        assert decode["fast"] + decode["eager"] == 48
        fill = next(e for e in evs if e[1] == "device.fill")[6]
        assert fill["loads"] == decode["resim"] and fill["max_burst"] >= 1
        assert next(e for e in evs if e[1] == "pool.stage")[6]["items"] == 48
        assert next(e for e in evs if e[1] == "pool.build_cmd")[6]["cmd_bytes"] > 0
        assert next(e for e in evs if e[1] == "bank.crossing")[6]["out_bytes"] > 0
    covered = program_spans.coverage(program_spans.ticks_of(events))
    assert 0.9 <= covered <= 1.0
    assert any(e[6]["resim"] for evs in ticks.values() for e in evs
               if e[1] == "pool.decode")


def test_bank_timers_are_armed_on_the_transition_only(tracer):
    pool, inputs = build(2)
    drive(pool, inputs, 4)
    host = pool.host
    calls = []
    real = host._lib.ggrs_bank_set_timing

    class Lib:
        def __getattr__(self, name):
            return getattr(host.__dict__["_real_lib"], name)

        def ggrs_bank_set_timing(self, bank, on):
            calls.append(on)
            return real(bank, on)

    host.__dict__["_real_lib"] = host._lib
    host._lib = Lib()
    try:
        drive(pool, inputs, 5)
        assert calls == [] and host.last_tick_phases() is None
        tracer.switch(True)
        drive(pool, inputs, 5)
        assert calls == [1] and host._trace_native
        assert set(host.last_tick_phases()) == set(_native.BANK_PHASES)
        tracer.switch(False)
        drive(pool, inputs, 5)
        assert calls == [1, 0] and not host._trace_native
    finally:
        host._lib = host.__dict__.pop("_real_lib")
    assert host.plan_ticks == host.crossings == pool.ticks


def profile_events(trace_dir):
    from jax.profiler import ProfileData

    xplane = max(Path(trace_dir).rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((plane.name, ev.name, ev.start_ns,
                                ev.duration_ns, dict(ev.stats)))
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_land_in_the_profile_and_the_tracer_sleeps_after(tracer):
    pool, inputs = build(4)
    drive(pool, inputs, 40)
    assert len(tracer) == 0
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            drive(pool, inputs, 6)
        finally:
            jax.profiler.stop_trace()
        recorded = len(tracer)
        drive(pool, inputs, 6)  # none after stop_trace
        assert len(tracer) == recorded and not tracer.enabled
        assert not pool.host._trace_native
        found = profile_events(trace_dir)
    ring = tracer.events()
    assert {e[6]["tick"] for e in ring} == set(range(41, 47))
    assert all(plane.startswith("/host:") for plane, *_ in found)
    by_name = {}
    for _plane, name, start, dur, stats in found:
        by_name.setdefault(name[len(ANNOTATION_PREFIX):], []).append(
            (start, dur, stats))
    # every Python-side span of the ring is an event of the profile too
    # (the native phases have no annotation: the anchor places them)
    for name, parent in TREE.items():
        assert len(by_name[name]) == 6, name
        for _start, _dur, stats in by_name[name]:
            assert stats["tick"] in range(41, 47)
            assert stats.get("parent") == parent
    assert all("fast" in s and "slots" in s for _, _, s in by_name["pool.decode"])
    # the root's perf_ns against its start on the profile's clock: one
    # offset for every tick
    anchors = [(s["perf_ns"], start) for start, _d, s in by_name["hosted.tick"]]
    offsets = [start - perf for perf, start in anchors]
    assert max(offsets) - min(offsets) < 50_000
    offset = profile_clock_offset_ns(anchors)
    assert offset == min(offsets)
    # and with it a ring span lands on its own annotation
    ring_decode = {e[6]["tick"]: e for e in ring if e[1] == "pool.decode"}
    for start, dur, stats in by_name["pool.decode"]:
        e = ring_decode[stats["tick"]]
        assert abs((e[3] + offset) - start) < 50_000
        assert dur <= e[4] + 1_000
    assert profile_clock_offset_ns([]) is None


def test_an_explicit_tracer_is_annotated_under_the_profiler_too():
    tracer = Tracer()
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with tracer.root_span("outer", tick=3):
                with tracer.span("inner") as span:
                    span.set(n=2)
        finally:
            jax.profiler.stop_trace()
        found = {name: stats for _p, name, _s, _d, stats
                 in profile_events(trace_dir)}
    assert found["ggrs.outer"]["tick"] == 3 and "perf_ns" in found["ggrs.outer"]
    assert found["ggrs.inner"] == {"parent": "outer", "tick": 3, "n": 2}
    with tracer.root_span("after"):
        pass
    assert tracer.enabled and not tracer._annotate


def test_clear_forgets_what_it_cleared():
    t = Tracer(capacity=4)
    for i in range(6):
        with t.span(f"s{i}"):
            pass
    assert t.dropped == 2
    t.clear()
    assert len(t) == 0 and t.dropped == 0 and t.recorded == 6
    with t.span("again"):
        pass
    assert t.dropped == 0 and len(t) == 1


def test_the_tick_programs_parts_are_named_in_the_lowered_program():
    pool, _ = build(2)
    ex = pool.executor
    text = ex._tick.lower(ex._carry, ex._blank_desc()).as_text(debug_info=True)
    for scope in ("ring.pre_save", "ring.load", "advance", "ring.save", "digest"):
        # "jit(tick)/vmap(ring.load)/rem", "ring.save/while/body/digest/mul"
        assert re.search(rf"[/(\"]{re.escape(scope)}[/)]", text), scope
