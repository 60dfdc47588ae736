"""The host tick's parts as per-layer metrics (ISSUE 37): seven files under
``benchmark/metrics``, eleven ``per_layer`` entries appended, read from the
program's own spans (``tests/test_host_tick_parts.py`` holds the spans).

A traced CPU rehearsal of one ``.sat`` cell, one paced cell and the detection
cell holds every new name its lists give it; the numbers it reads there are
a CPU's and say nothing of the chip.  Like ``test_benchmark.py`` this stubs
the chip here, never in the benchmark, and edits no file the benchmark had.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import run  # noqa: E402
from ggrs_tpu.obs import default_tracer  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
SEED = 2**31 + 37
SAT = [w["name"] for w in BENCH["workloads"] if w["traffic"].endswith("-sat")]
PACED = [w["name"] for w in BENCH["workloads"] if w["name"] not in SAT]
DETECT = ["particles-2p-detect.wan-sat"]
HOST_BOUND = ["boxgame-2p.wan-sat", "ecs-4p.wan-sat"]  # among the cells of every host part
BANK = "native bank crossing and staging"
FILL = "descriptor fill and dispatch"
# quantity -> (layer, the kinds it is read in, cells the .sat entry names)
QUANTITIES = {
    "plan_decode_ms_p50": (BANK, ("sat", "paced"), HOST_BOUND),
    "command_build_ms_p50": (BANK, ("sat", "paced"), HOST_BOUND),
    "quiet_fulfill_ms_p50": (FILL, ("sat", "paced"), HOST_BOUND),
    "checksum_deliver_ms_p50": (BANK, ("sat",), DETECT),
    "checksum_ask_ms_p50": (BANK, ("sat",), DETECT),
    "fence_wait_ms_p50": ("whole tick", ("paced",), None),
    "device_ready_at_launch_share": (FILL, ("sat", "paced"), HOST_BOUND),
}
NEW = [f"{q}.{kind}" for q, spec in QUANTITIES.items() for kind in spec[1]]


def test_eleven_entries_were_appended_and_nothing_else():
    """Each of the eleven names is one entry, found by name wherever later
    entries are appended."""
    assert len(NEW) == 11
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)


@pytest.mark.parametrize("name", NEW)
def test_each_entry_names_its_file_its_layer_and_its_cells(name):
    quantity, _, kind = name.rpartition(".")
    layer, kinds, sat_cells = QUANTITIES[quantity]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    # a quantity of both kinds has one file, one of one kind its own name's
    stem = quantity if len(kinds) == 2 or kind == "sat" else name
    assert (REPO / "benchmark" / "metrics" / f"{stem}.json").is_file()
    spec = run._metric_file(REPO, name)
    assert spec["reducer"] and spec["why"] and spec["source_line"]
    assert (REPO / "benchmark" / "reducers" / f"{spec['reducer']}.py").is_file()
    assert entry["layer"] == layer and entry["source"] == "program_counter"
    # ready-at-launch too: a device that waits is a device not used
    assert entry["better"] == "lower"
    assert entry["unit"] == ("%" if quantity.endswith("_share") else "ms")
    # read in cells of its kind: among them the ones it was made for
    if kind == "sat":
        assert entry["moves"] == "session_ticks_per_s"
        assert set(sat_cells) <= set(entry["workloads"]) <= set(SAT)
    else:
        assert entry["moves"] == "tick_ms_p50"
        assert set(entry["workloads"]) <= set(PACED)
        assert {"boxgame-2p.wan-60hz", "ecs-4p.wan-60hz"} <= set(entry["workloads"])
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}


@pytest.fixture
def ring():
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    yield tracer
    tracer.switch(False)
    tracer.clear()


def launch(tick, **args):
    """A tick of two events as the tracer keeps them: root and launch."""
    return [("X", "hosted.tick", "py", 0, 10, 1, {"tick": tick}),
            ("X", "device.launch", "py", 2, 3, 1,
             dict(args, tick=tick, parent="device.dispatch"))]


def test_the_mean_of_an_argument_leaves_out_the_spans_that_lack_it(ring):
    """``span_arg_mean``, new with ``device_ready_at_launch_share``: a
    program whose ``device.launch`` carries no ``device_ready`` (the parent)
    reads as nothing, where ``span_arg_share`` over ``dispatches`` read 0."""
    from benchmark.reducers import span_arg_mean, span_arg_share

    spec = run._metric_file(REPO, "device_ready_at_launch_share.sat")
    assert spec["reducer"] == "span_arg_mean"
    facts = {"series": {}, "counts": {}}
    assert span_arg_mean.reduce(facts, spec["args"]) is None  # no slice
    ring.switch(True)
    ring.import_spans(launch(1, dispatches=1) + launch(2, dispatches=1))
    assert span_arg_mean.reduce(facts, spec["args"]) is None
    assert span_arg_share.reduce(facts, {
        "span": "device.launch", "num": "device_ready", "den": "dispatches",
        "scale": 100.0}) == 0.0
    ring.import_spans(launch(3, dispatches=1, device_ready=1)
                      + launch(4, dispatches=1, device_ready=0)
                      + launch(5, dispatches=1, device_ready=1))
    ring.switch(False)
    assert span_arg_mean.reduce(facts, spec["args"]) == pytest.approx(200 / 3)
    assert span_arg_mean.reduce(
        facts, {"span": "device.launch", "arg": "device_ready"}) \
        == pytest.approx(2 / 3)


@pytest.mark.parametrize(
    "cell", ["boxgame-2p.wan-sat", "ecs-4p.wan-60hz", DETECT[0]])
def test_a_traced_rehearsal_holds_every_new_name_of_its_lists(
        cell, ring, no_chip_needed, monkeypatch):
    tick = run.Pool.tick

    def paced(pool, row):
        # this CPU is slow: a fence a tick, of the carry and of the digests
        # on their way, so that one asked for in the slice lands in it
        tick(pool, row)
        pool.hosted.block_until_ready()
        for fetch in pool.executor._digest_fetches:
            fetch[4].block_until_ready()

    if cell in DETECT:
        monkeypatch.setattr(run.Pool, "tick", paced)
    result = run.run_cell(cell, SEED, 0.25, True, matches=4)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["compiles_in_window"]["value"] == 0
    wanted = [m["name"] for m in BENCH["per_layer"]
              if m["name"] in NEW and cell in m["workloads"]]
    assert len(wanted) == {"boxgame-2p.wan-sat": 4, "ecs-4p.wan-60hz": 5,
                           DETECT[0]: 6}[cell]
    got = {n: result["metrics"][n] for n in wanted}  # KeyError: a name missing
    for name, metric in got.items():
        if name.startswith("device_ready_at_launch_share"):
            assert metric["unit"] == "%" and 0.0 <= metric["value"] <= 100.0
        else:
            assert metric["unit"] == "ms" and 0.0 < metric["value"] < 1000.0
    kind = "paced" if cell in PACED else "sat"
    metrics = {n: m["value"] for n, m in result["metrics"].items()}
    # the parts lie inside what holds them, on the same ticks
    assert (metrics[f"plan_decode_ms_p50.{kind}"]
            + metrics[f"command_build_ms_p50.{kind}"]
            <= 1.25 * metrics[f"bank_python_ms_p50.{kind}"])
    assert (metrics[f"quiet_fulfill_ms_p50.{kind}"]
            < metrics[f"descriptor_fill_ms_p50.{kind}"])
    if cell in PACED:
        # every paced tick is fenced before the next: the device had finished
        assert metrics["device_ready_at_launch_share.paced"] == 100.0
    if cell not in DETECT:
        # the fill's two halves are both leaves: the tick is still covered
        # (the detection cell's short fenced ticks here hold a gap no span
        # names: on this CPU a landed fetch's array takes milliseconds to
        # free, after device.checksum_fetch has ended)
        assert metrics[f"span_coverage_share.{kind}"] > 60.0
    # a cell's other names are none of this PR's
    others = set(NEW) - set(wanted)
    assert not others & set(result["metrics"])
