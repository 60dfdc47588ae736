"""The per-layer metrics that read the program's own spans (ISSUE 25): the
reader and its arithmetic on hand-made event lists with hand-computed
answers, what reads as nothing, and a traced rehearsal of the paced cell
that reports all six quantities.

Like ``test_benchmark.py`` this stubs the chip here, never in the
benchmark, and edits no file the benchmark had.
"""

from __future__ import annotations

import builtins
import importlib
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import program_spans, run  # noqa: E402
from ggrs_tpu.obs import default_tracer  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
QUANTITIES = ["bank_crossing_ms_p50", "bank_python_ms_p50",
              "descriptor_fill_ms_p50", "launch_ms_p50", "fast_slot_share",
              "span_coverage_share"]
SEED = 2**31 + 25
MS = 1_000_000


def ev(name, start_ms, dur_ms, tick, parent=None, cat="py", **args):
    """One ring event as the tracer keeps it."""
    a = dict(args, tick=tick)
    if parent is not None:
        a["parent"] = parent
    return ("X", name, cat, int(start_ms * MS), int(dur_ms * MS), 1, a)


def hand_made_tick(tick, t, crossing_ms, fast):
    """A 10 ms tick whose leaves cover 9 ms: stage 1, build 1.5, crossing
    ``crossing_ms``, decode 6 - ``crossing_ms``, supervise 0, fill 0.5,
    launch 1; the native phases inside the crossing count for nothing."""
    return [
        ev("hosted.tick", t, 10, tick),
        ev("pool.stage", t, 1, tick, "hosted.tick", items=8),
        ev("bank.staging", t + 0.5, 0.25, tick, "pool.stage", cat="native.phase"),
        ev("pool.tick", t + 1, 8, tick, "hosted.tick"),
        ev("pool.build_cmd", t + 1, 1.5, tick, "pool.tick", cmd_bytes=64),
        ev("bank.crossing", t + 2.5, crossing_ms, tick, "pool.tick",
           cat="native", out_bytes=512),
        ev("bank.inbound", t + 2.5, crossing_ms / 2, tick, "bank.crossing",
           cat="native.phase"),
        ev("pool.decode", t + 2.5 + crossing_ms, 6 - crossing_ms, tick,
           "pool.tick", fast=fast, eager=8 - fast, resim=1, save_only=0, slots=8),
        ev("pool.supervise", t + 8.5, 0, tick, "pool.tick"),
        ev("device.dispatch", t + 9, 1, tick, "hosted.tick"),
        ev("device.fill", t + 9, 0.25, tick, "device.dispatch", loads=1, max_burst=2),
        ev("device.launch", t + 9.25, 0.75, tick, "device.dispatch"),
        ev("device.fence", t + 10, 5, tick),  # a root of its own: no leaf
    ]


EVENTS = (hand_made_tick(7, 0, 1.0, 8) + hand_made_tick(8, 20, 2.0, 6)
          + hand_made_tick(9, 40, 4.0, 7)
          + [ev("pool.tick", 60, 3, 10), ev("bank.crossing", 60, 1, 10, "pool.tick"),
             ("i", "pool.desync", "py", 0, 0, 1, {"slot": 3})])


def test_ticks_are_those_with_a_root_span():
    ticks = program_spans.ticks_of(EVENTS)
    assert sorted(ticks) == [7, 8, 9]  # tick 10 has no hosted.tick
    assert all(len(evs) == 13 for evs in ticks.values())
    assert program_spans.ticks_of([]) == {}


def test_span_arithmetic_on_a_hand_made_list():
    ticks = program_spans.ticks_of(EVENTS)
    p50 = program_spans.percentile_ms
    assert p50(ticks, ["bank.crossing"], (), 50) == pytest.approx(2.0)
    assert p50(ticks, ["bank.crossing"], (), 100) == pytest.approx(4.0)
    assert p50(ticks, ["bank.crossing"], (), 25) == pytest.approx(1.5)
    # stage 1 + tick 8 - crossing: 8, 7, 5
    assert p50(ticks, ["pool.stage", "pool.tick"], ["bank.crossing"], 50) \
        == pytest.approx(7.0)
    assert p50(ticks, ["device.fill"], (), 50) == pytest.approx(0.25)
    assert p50(ticks, ["device.launch"], (), 50) == pytest.approx(0.75)
    assert p50(ticks, ["no.such.span"], (), 50) is None
    share = program_spans.arg_share
    assert share(ticks, "pool.decode", "fast", "slots", 100.0) \
        == pytest.approx(100.0 * 21 / 24)
    assert share(ticks, "pool.decode", "resim", "slots") == pytest.approx(3 / 24)
    assert share(ticks, "pool.decode", "fast", "no_such_count") is None
    # leaves: 1 + 1.5 + c + (6 - c) + 0 + 0.25 + 0.75 = 9.5 of 10 ms
    assert program_spans.coverage(ticks) == pytest.approx(0.95)
    # were the phases layers of the tree, the crossing would be an inner span
    # and only its phase's half would count
    assert program_spans.coverage(ticks, subdivisions=()) \
        == pytest.approx((28.5 - 7 - 3 + 3.5 + 0.75) / 30)
    assert program_spans.coverage({}) is None


def reduce(name, args):
    module = importlib.import_module(f"benchmark.reducers.{name}")
    return module.reduce({"series": {}, "counts": {}}, args)


@pytest.fixture
def ring():
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    yield tracer
    tracer.switch(False)
    tracer.clear()


def test_the_reducers_read_the_default_tracers_ring(ring):
    ring.import_spans(EVENTS)  # dropped: the tracer is off
    assert len(ring) == 0
    args = {"add": ["bank.crossing"], "q": 50}
    assert reduce("span_percentile", args) is None  # nothing recorded
    ring.switch(True)
    ring.import_spans(EVENTS)
    ring.switch(False)
    assert reduce("span_percentile", args) == pytest.approx(2.0)
    assert reduce("span_arg_share", {"span": "pool.decode", "num": "fast",
                                     "den": "slots", "scale": 100.0}) \
        == pytest.approx(87.5)
    assert reduce("span_coverage", {"root": "hosted.tick", "scale": 100.0}) \
        == pytest.approx(95.0)


def test_a_ring_that_dropped_or_has_no_root_reads_as_nothing(ring):
    ring.switch(True)
    ring.import_spans([e for e in EVENTS if e[1] != "hosted.tick"])
    assert program_spans.slice_ticks() is None
    ring.clear()
    ring.import_spans(EVENTS * 120)  # 4,800 events into a ring of 4,096
    ring.switch(False)
    assert ring.dropped > 0
    assert program_spans.slice_ticks() is None
    for name, args in (("span_percentile", {"add": ["bank.crossing"], "q": 50}),
                       ("span_arg_share", {"span": "pool.decode", "num": "fast",
                                           "den": "slots"}),
                       ("span_coverage", {})):
        assert reduce(name, args) is None


def test_a_program_without_the_tracer_reads_as_nothing(monkeypatch):
    """The parent commit has no ``default_tracer``: the reader returns
    nothing there, and does not raise."""
    real_import = builtins.__import__

    def no_tracer(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "ggrs_tpu.obs.trace" and "default_tracer" in (fromlist or ()):
            raise ImportError("cannot import name 'default_tracer'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_tracer)
    assert program_spans.slice_ticks() is None
    assert reduce("span_coverage", {}) is None


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_each_quantity_has_one_file_and_two_entries(quantity):
    spec = json.loads(
        (REPO / "benchmark" / "metrics" / f"{quantity}.json").read_text())
    assert spec["why"] and spec["source_line"]
    assert (REPO / "benchmark" / "reducers" / f"{spec['reducer']}.py").is_file()
    entries = {m["name"]: m for m in BENCH["per_layer"]
               if m["name"].rpartition(".")[0] == quantity}
    assert set(entries) == {f"{quantity}.sat", f"{quantity}.paced"}
    sat, paced = entries[f"{quantity}.sat"], entries[f"{quantity}.paced"]
    # each read in the cells of its loop, the first of them among them
    loops = {w["name"]: run.load_cell(REPO, w["name"])["traffic"]["loop"]
             for w in BENCH["workloads"]}
    assert sat["moves"] == "session_ticks_per_s"
    assert "boxgame-2p.wan-sat" in sat["workloads"]
    assert {loops[cell] for cell in sat["workloads"]} == {"closed"}
    assert paced["moves"] == "tick_ms_p50"
    assert "boxgame-2p.wan-60hz" in paced["workloads"]
    assert {loops[cell] for cell in paced["workloads"]} == {"open"}
    assert sat["source"] == paced["source"] == "program_counter"
    assert sat["layer"] == paced["layer"]
    assert run._metric_file(REPO, f"{quantity}.sat") == spec


def test_the_traced_rehearsal_reports_all_six_paced_quantities(ring, no_chip_needed):
    result = run.run_cell("boxgame-2p.wan-60hz", SEED, 0.25, True, matches=4)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["plan_ticks_off_ticks"]["value"] == 0
    got = {n: result["metrics"][f"{n}.paced"]["value"] for n in QUANTITIES}
    assert all(v > 0 for v in got.values()), got
    assert got["fast_slot_share"] <= 100.0
    assert 50.0 < got["span_coverage_share"] <= 100.0
    # inside and outside agree on what they both time (the outside twin
    # wraps the same calls, over the window's ticks instead of the slice's)
    outside = result["metrics"]["bank_ms_p50.paced"]["value"]
    inside = got["bank_crossing_ms_p50"] + got["bank_python_ms_p50"]
    assert 0.3 * outside < inside < 3.0 * outside
    # the slice's 60 ticks and nothing else are on the ring; the untraced
    # window and the hold left none
    ticks = program_spans.slice_ticks()
    assert len(ticks) == 60 and ring.dropped == 0 and not ring.enabled
    # an untraced run reports none of them
    names = {m["name"] for m in
             run.load_cell(REPO, "boxgame-2p.wan-60hz")["metrics"]["end_to_end"]}
    assert not names & {f"{n}.paced" for n in QUANTITIES}
