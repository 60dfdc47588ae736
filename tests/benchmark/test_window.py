"""What a closed loop says of its own window (the fence periods, their
quartiles, the window's quarters, what follows the last fence, the
collector's runs), the per-layer metric read from it, and the executor
``Pool`` builds on one chip and across several.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import run  # noqa: E402
from benchmark.reducers import quartile_spread  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
SEED = 2**31 + 28


# --- the fence periods ------------------------------------------------------


def test_quartile_spread_of_a_hand_made_series():
    series = [1.0, 2.0, 3.0, 4.0, 5.0]  # quartiles 2, 3, 4
    facts = {"series": {"fence_period_s": series, "short": [1.0, 2.0, 3.0],
                        "zeros": [0.0] * 8}}
    args = {"series": "fence_period_s", "scale": 100.0}
    assert quartile_spread.reduce(facts, args) == pytest.approx(100 * 2 / 3)
    assert quartile_spread.reduce(facts, dict(args, scale=1.0)) == pytest.approx(2 / 3)
    # one long period of twenty moves neither quartile: the rate feels a
    # stall, this reads what the other periods did
    steady = [0.2] * 19 + [1.4]
    assert quartile_spread.reduce({"series": {"p": steady}}, {"series": "p"}) == 0.0
    for nothing in ("short", "zeros", "absent"):
        assert quartile_spread.reduce(facts, {"series": nothing}) is None


class _ClockedPool:
    """Stands in for ``run.Pool`` on a clock of its own: a tick takes a
    millisecond of it, and the ticks named in ``stalls`` that much longer."""

    def __init__(self, stalls):
        self.ticks, self.stalls, self.now = 0, stalls, 100.0

    def tick(self, row):
        self.now += 0.001 + self.stalls.get(self.ticks, 0.0)
        self.ticks += 1

    def fence(self):
        pass


@pytest.mark.parametrize("stalls, long_at, slow_tail", [
    ({}, [], False),
    ({40: 0.08}, [2], False),    # tick 40 falls in the third period of 16
    ({66: 0.08}, [], True),      # after the last fence: no period holds it
])
def test_a_stall_shows_where_it_struck_and_the_rate_counts_it(
        stalls, long_at, slow_tail, monkeypatch):
    pool = _ClockedPool(stalls)
    monkeypatch.setattr(run, "time", SimpleNamespace(perf_counter=lambda: pool.now))
    inputs = SimpleNamespace(row=lambda i: None)
    window = run.closed_loop(pool, inputs, None, 70, 16)
    assert window["ticks"] == 70
    assert window["fence_period_s"] == pytest.approx(
        [0.016 + sum(v for k, v in stalls.items() if 16 * i <= k < 16 * i + 16)
         for i in range(4)])
    assert [i for i, _ in window["long_periods"]] == long_at
    assert window["tail"]["ticks"] == 6
    assert window["tail"]["s"] == pytest.approx(0.086 if slow_tail else 0.006)
    # all the window's time is in the rate's denominator, stall and tail too
    assert window["window_s"] == pytest.approx(0.070 + sum(stalls.values()))
    assert window["tick_ms_by_quarter"] == pytest.approx(
        [1e3 * p / 16 for p in window["fence_period_s"]])


def test_a_closed_loop_reports_its_fence_periods(no_chip_needed):
    result = run.run_cell("boxgame-2p.wan-sat", SEED, 0.5, True, matches=4)
    assert result["correct"] is True, result["checks"]
    window = result["window"]
    q = window["fence_period_quartiles_s"]
    assert 0 < q["min"] <= q["p25"] <= q["p50"] <= q["p75"] <= q["max"]
    assert len(window["tick_ms_by_quarter"]) == 4
    assert window["tail"]["ticks"] == window["ticks"] % 16
    assert len(window["gc_collections"]) == 3  # the collector's runs, by generation
    assert all(p > 1.5 * q["p50"] for _, p in window["long_periods"])
    assert "fence_period_s" not in window  # the series stays out of the line
    spread = result["metrics"]["fence_period_spread.sat"]
    assert spread["unit"] == "%"
    assert spread["value"] == pytest.approx(100 * (q["p75"] - q["p25"]) / q["p50"])
    # the rate is still all fenced session-ticks over all the window's time
    untraced = run.run_cell("boxgame-2p.wan-sat", SEED, 0.25, False, matches=4)
    rate = untraced["metrics"]["session_ticks_per_s"]["value"]
    assert rate == pytest.approx(
        untraced["attempted"] / untraced["window"]["window_s"])
    assert untraced["attempted"] == untraced["window"]["ticks"] * 8


def test_the_spread_metric_is_read_in_both_sat_cells_and_no_other():
    """Fence periods exist in a closed loop only: the spread is read in the
    two first closed-loop cells and in no open-loop one, and a cell reads as
    many per-layer metrics as BENCHMARK.json's lists name it in."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == "fence_period_spread.sat"]
    loops = {w["name"]: run.load_cell(REPO, w["name"])["traffic"]["loop"]
             for w in BENCH["workloads"]}
    assert {"boxgame-2p.wan-sat", "ecs-4p.wan-sat"} <= set(entry["workloads"])
    assert all(loops[cell] == "closed" for cell in entry["workloads"])
    assert (entry["layer"], entry["moves"]) == ("whole tick", "session_ticks_per_s")
    counts = {w["name"]: len(run.load_cell(REPO, w["name"])["metrics"]["per_layer"])
              for w in BENCH["workloads"]}
    assert counts == {cell: sum(run._applies(m, cell) for m in BENCH["per_layer"])
                      for cell in loops}


# --- one chip and several ---------------------------------------------------


def test_on_one_chip_the_executor_is_built_with_the_arguments_it_had(
        no_chip_needed, monkeypatch):
    built = []
    executor = run.BatchedRequestExecutor

    def recording(*args, **kwargs):
        built.append((len(args), sorted(kwargs)))
        return executor(*args, **kwargs)

    monkeypatch.setattr(run, "BatchedRequestExecutor", recording)
    spec = run.load_cell(REPO, "boxgame-2p.wan-sat")
    pool = run.Pool(spec["config"], spec["traffic"], 2, SEED)
    assert built == [(3, ["batch_size", "max_burst", "raw_inputs_to_array",
                          "ring_length"])]
    assert pool.executor.mesh is None


@pytest.mark.parametrize("cell", ["boxgame-2p.wan-sat", "ecs-4p.wan-sat"])
def test_a_cell_on_four_chips_rehearses_correct_over_a_mesh(
        cell, no_chip_needed, monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh of tests/conftest.py")
    spec = run.load_cell(REPO, cell)
    spec["cell"] = dict(spec["cell"], chips=4)
    monkeypatch.setattr(run, "load_cell", lambda root, name: spec)
    pools = []
    init = run.Pool.__init__

    def keeping(pool, *args, **kwargs):
        init(pool, *args, **kwargs)
        pools.append(pool)

    monkeypatch.setattr(run.Pool, "__init__", keeping)
    result = run.run_cell(cell, SEED, 0.25, False, matches=4)
    assert result["correct"] is True, result["checks"]
    mesh = pools[0].executor.mesh
    assert mesh is not None and mesh.devices.size == 4
    live = pools[0].executor.live_states
    assert all(len(leaf.sharding.device_set) == 4
               for leaf in jax.tree_util.tree_leaves(live))
    assert np.asarray(jax.tree_util.tree_leaves(live)[0]).shape[0] == pools[0].sessions
