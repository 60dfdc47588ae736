"""``particles-2p-x4`` and its cell ``particles-2p-x4.wan-sat`` (PR 33): the
particle host on the one layout no other cell has, one pool whose session
axis is sharded over four chips.

``test_benchmark.py`` runs its cases over every cell of ``BENCHMARK.json``,
this one too (rehearsal, altered input, altered digest, stuck step, control);
here is what only this cell needs: that the rehearsal really runs over a mesh
of four with every leaf of the carry on four devices, that a fault on ONE
shard is seen, what the new per-layer metric reads on one chip and on four,
and that the configuration is ``particles-2p``'s but for what its file says.
The virtual CPU mesh of ``tests/conftest.py`` stands in for the four chips,
in the test and never in the benchmark.
"""

from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import control, generator, program_spans, run  # noqa: E402
from ggrs_tpu.obs import default_tracer  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "particles-2p-x4.wan-sat"
METRIC = "launch_transfers_per_dispatch.sat"
# the cells that read the metric, as its own list in BENCHMARK.json says
SAT_CELLS = next(m for m in BENCH["per_layer"] if m["name"] == METRIC)["workloads"]
SEED = 2**31 + 33


@pytest.fixture
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh of tests/conftest.py")


@pytest.fixture
def pools(monkeypatch):
    """Every ``run.Pool`` a rehearsal builds, kept for the test to look at."""
    kept = []
    init = run.Pool.__init__

    def keeping(pool, *args, **kwargs):
        init(pool, *args, **kwargs)
        kept.append(pool)

    monkeypatch.setattr(run.Pool, "__init__", keeping)
    return kept


@pytest.fixture
def ring():
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    yield tracer
    tracer.switch(False)
    tracer.clear()


# --- the cell over a mesh ----------------------------------------------------


def test_the_cell_rehearses_correct_over_a_mesh_of_four(
        four_devices, no_chip_needed, pools):
    import jax

    result = run.run_cell(CELL, SEED, 0.25, False, matches=4)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["window_without_rollback"]["value"] == 0
    assert set(result["metrics"]) == {"session_ticks_per_s", "setup_s"}
    pool, = pools
    executor = pool.executor
    assert executor.mesh is not None and executor.mesh.devices.size == 4
    leaves = jax.tree_util.tree_leaves(executor._carry)
    assert len(leaves) == 16  # 7 live, 7 ring, digests, frame tags
    for leaf in leaves:
        assert leaf.shape[0] == pool.sessions == 8
        assert len(leaf.sharding.device_set) == 4
        # contiguous blocks: both peers of a match on one device
        blocks = sorted((s.index[0].start or 0, s.data.shape[0])
                        for s in leaf.addressable_shards)
        assert blocks == [(0, 2), (2, 2), (4, 2), (6, 2)]
    live = jax.device_get(executor.live_states)
    assert int(live["ttl"].sum()) > 0


def test_a_misprediction_never_corrected_on_one_shard_is_seen(
        four_devices, no_chip_needed, pools, monkeypatch):
    """Session 5 (shard 2 of four) once replays a rollback with the input it
    had predicted and not the one that arrived: the skipped correction the
    control stands for, on the timed path.  It alone differs from the
    reference, and from its peer on the same shard."""
    import jax

    from ggrs_tpu.parallel import BatchedRequestExecutor

    victim, after_tick = 5, 40
    launch = BatchedRequestExecutor._launch
    state = {"ticks": 0, "predicted": None, "done": False}

    def uncorrected(executor, desc):
        state["ticks"] += 1
        n = int(desc["n_adv"][victim])
        if (not state["done"] and state["ticks"] > after_tick
                and desc["do_load"][victim] and state["predicted"] is not None
                and (desc["inputs"][victim, 0] != state["predicted"]).any()):
            desc["inputs"][victim, 0] = state["predicted"]
            state["done"] = True
        if n:
            state["predicted"] = desc["inputs"][victim, n - 1].copy()
        launch(executor, desc)

    monkeypatch.setattr(BatchedRequestExecutor, "_launch", uncorrected)
    result = run.run_cell(CELL, SEED, 0.25, False, matches=4)
    assert state["done"], "no rollback of the victim after the warm ticks"
    assert result["correct"] is False
    assert result["checks"]["state_mismatch_sessions"]["value"] == 1
    assert result["checks"]["session_ticks_missing"]["value"] == 0
    live = jax.device_get(pools[0].executor.live_states)
    differs = [b for b in range(0, 8, 2)
               if any((v[b] != v[b + 1]).any() for v in live.values())]
    assert differs == [victim - 1]  # match 2, and no other shard's


def test_the_control_is_not_correct_at_the_cells_own_shape():
    spec = run.load_cell(REPO, CELL)
    rows = generator.schedule(spec["traffic"], SEED, 4, 2, 160)
    rows = np.concatenate([rows, np.repeat(rows[-1:], 24, axis=0)])
    small = dict(spec["config"], capacity=200, rate=2)
    checks = control.control_checks(small, rows, SEED)
    # the last peer of every match that kept a misprediction: 1 of each 2
    assert 0 < checks["state_mismatch_sessions"] <= 4


# --- the new per-layer metric -------------------------------------------------


@pytest.mark.parametrize("cell", SAT_CELLS)
def test_transfers_a_dispatch_are_one_descriptor_buffer_a_chip(
        cell, four_devices, no_chip_needed, ring):
    """One descriptor buffer a dispatch, sent to each device the call splits
    it over: as many transfers as the span's ``shards``, the cell's chips."""
    chips = int(run.load_cell(REPO, cell)["cell"]["chips"])
    result = run.run_cell(cell, SEED, 0.25, True, matches=4)
    assert result["correct"] is True, result["checks"]
    got = result["metrics"][METRIC]
    assert got == {"value": 1.0 * chips, "unit": "transfers"}
    assert result["metrics"]["launch_ms_p50.sat"]["value"] > 0
    launches = [e[6] for evs in program_spans.slice_ticks().values()
                for e in evs if e[1] == "device.launch"]
    assert len(launches) == 16 and not ring.enabled
    assert {a["shards"] for a in launches} == {chips}
    if cell == CELL:
        # 8 sessions over four devices: a quarter of the carry a device
        resident = result["metrics"]["ring_resident_gb.sat"]["value"]
        assert 2 * 11 * 520028e-9 < resident < 2 * 11 * 520300e-9
        # the CPU backend has no device plane: those readers return nothing
        assert "tick_program_ms_p50.sat" not in result["metrics"]


def test_a_program_whose_launch_carries_no_count_reads_as_nothing(ring):
    """The parent of PR 33: ``device.launch`` without arguments."""
    spec = run._metric_file(REPO, METRIC)
    assert spec["reducer"] == "span_arg_share" and spec["why"] and spec["source_line"]
    reducer = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
    events = [("X", "hosted.tick", "py", 0, 10, 1, {"tick": 1}),
              ("X", "device.launch", "py", 2, 5, 1,
               {"tick": 1, "parent": "device.dispatch"})]
    ring.switch(True)
    ring.import_spans(events)
    ring.switch(False)
    assert reducer.reduce({}, spec["args"]) is None
    ring.switch(True)
    ring.import_spans([("X", "hosted.tick", "py", 20, 10, 1, {"tick": 2}),
                       ("X", "device.launch", "py", 22, 5, 1,
                        {"tick": 2, "parent": "device.dispatch", "shards": 4,
                         "transfers": 40, "dispatches": 1})])
    ring.switch(False)
    assert reducer.reduce({}, spec["args"]) == 40.0


def test_the_metric_is_read_in_every_sat_cell_and_the_cell_in_every_sat_metric():
    """Looked up by name, wherever later entries are appended: the metric is
    read in closed-loop cells, this one and the first among them, and this
    cell is on the list of every metric all one-chip closed-loop cells read,
    but one."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    closed = {w["name"]: w["chips"] for w in BENCH["workloads"]
              if run.load_cell(REPO, w["name"])["traffic"]["loop"] == "closed"}
    assert set(entry["workloads"]) <= set(closed)
    assert {CELL, "boxgame-2p.wan-sat"} <= set(entry["workloads"])
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "transfers", "lower", "program_counter")
    assert (entry["layer"], entry["moves"]) == (
        "descriptor fill and dispatch", "session_ticks_per_s")
    listed = {m["name"] for m in BENCH["per_layer"] if run._applies(m, CELL)}
    everywhere = {m["name"] for m in BENCH["per_layer"]
                  if all(run._applies(m, c) for c, n in closed.items() if n == 1)}
    # run.py sums the program time over the chips and divides by their
    # number, roofline.py divides ALL sessions' bytes by ONE chip's
    # bandwidth: on four chips the share would read four times high
    # (PERF.md section 7), so the cell stays off that one list
    assert everywhere - listed == {"tick_program_roofline.sat"}
    rate, = [m for m in BENCH["end_to_end"] if m["name"] == "session_ticks_per_s"]
    assert CELL in rate["workloads"]


# --- the configuration ---------------------------------------------------------


def test_the_configuration_is_particles_2p_but_for_what_its_file_says():
    spec = run.load_cell(REPO, CELL)
    config, size, cell = spec["config"], spec["size"], spec["cell"]
    parent = run.load_cell(REPO, "particles-2p.wan-sat")
    told = {"name", "source", "deployment", "matches", "reduced", "assumed"}
    assert set(config) - set(parent["config"]) == {"matches"}
    for key, value in parent["config"].items():
        if key not in told:
            assert config[key] == value, key
    # what `assumed` adds is the hosting; every other assumption is the same
    assert {k: v for k, v in config["assumed"].items() if k != "hosting"} == {
        k: v for k, v in parent["config"]["assumed"].items() if k != "hosting"}
    entry, = [c for c in BENCH["configs"] if c["name"] == "particles-2p-x4"]
    assert entry["source"] == config["source"]
    assert entry["source"] != parent["config"]["source"]
    assert entry["source"].startswith(parent["config"]["source"])
    assert entry["reduced"] == list(config["reduced"]) == ["matches"]
    # the cut: a quarter of the stated deployment, the same sessions as the
    # one-chip cell so that the two differ in the chips alone
    stated = config["deployment"]["matches_stated"]
    assert (stated, config["matches"], size["matches"]) == (1024, 256, 256)
    assert size["matches"] == parent["size"]["matches"]
    assert size["trace_ticks"] == spec["traffic"]["fence_every"]
    assert size["witness_by_frame"] == parent["size"]["witness_by_frame"]
    assert cell["chips"] == 4 and cell["traffic"] == parent["cell"]["traffic"]
    assert (size["matches"] * config["players"]) % cell["chips"] == 0
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(BENCH["workloads"]) // 2)
    assert cell in BENCH["workloads"]
    # a shard's ring leaves are above the re-lay rule: the deployment's program
    from ggrs_tpu.parallel.session_pool import ring_leaf_layout

    shard = size["matches"] * config["players"] // cell["chips"]
    for words in (3, 4, 2):
        assert ring_leaf_layout(
            (shard, config["ring_length"], words, config["capacity"]), 4)
    assert ring_leaf_layout((shard, config["ring_length"], config["capacity"]), 4)
