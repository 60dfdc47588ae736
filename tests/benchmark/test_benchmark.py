"""The benchmark's own tests (BENCHMARK.json ``paths``): the data resolves,
every cell rehearses on the CPU at a tiny size with ``correct`` true, a
broken timed path and the control come out not correct, and the trace
reduction and the roofline's byte count give hand-computed numbers.

The chip requirement is stubbed in ``conftest.py``, never in the benchmark:
``run.py`` itself has no CPU path.  No test describes a TPU topology.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import control, generator, roofline, run, trace_reduce  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SEED = 2**31 + 77  # the driver's seeds are large


def rehearse(cell: str, trace: bool = False, **kwargs):
    return run.run_cell(cell, SEED, 0.25, trace, matches=4, **kwargs)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    spec = run.load_cell(REPO, cell)
    config, traffic, size = spec["config"], spec["traffic"], spec["size"]
    assert size["matches"] >= 1 and size["trace_ticks"] >= 1
    assert size["why_matches"] and size["source_line"]
    if traffic["loop"] == "closed":  # the traced slice is a whole fence period
        assert size["trace_ticks"] == traffic["fence_every"]
    assert (REPO / "benchmark" / "adapters" / f"{config['adapter']}.py").is_file()
    assert (REPO / "benchmark" / "reference" / f"{config['adapter']}.py").is_file()
    for kind in ("end_to_end", "per_layer"):
        assert spec["metrics"][kind], f"{cell} reports no {kind} metric"
        for metric in spec["metrics"][kind]:
            assert metric["why"] and metric["source_line"]
            assert (REPO / "benchmark" / "reducers" / f"{metric['reducer']}.py").is_file()
    assert "setup_s" in {m["name"] for m in spec["metrics"]["end_to_end"]}
    assert traffic["why"] and traffic["source_line"]
    # the configuration is its source's but for what its file says, with a
    # reason for each: every key reduced or departed from gives one, and
    # BENCHMARK.json's entry lists the same keys reduced
    for said in ("reduced", "departs_from_source"):
        for key, reason in config.get(said, {}).items():
            assert isinstance(reason, str) and reason.strip(), (said, key)
    entry, = [c for c in BENCH["configs"] if c["name"] == spec["cell"]["config"]]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config.get("saving", "every_frame") in run.SAVING
    # the source's input delay, which the traffic's one-frame mispredictions
    # rest on, unless the file says why it departs from it
    said = "input_delay" in config.get("departs_from_source", {})
    assert config["input_delay"] == 2 or said


def test_names_and_units_are_in_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + CELLS
    for w in BENCH["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in BENCH["configs"]:
        names += c["reduced"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for name in names:
        assert NAME.match(name), name
    assert len(set(CELLS)) == len(CELLS)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    # one chip or four, and four for at most half of the cells (one always may)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    reported = {
        cell: {m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]}
        for cell in CELLS
    }
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in reported[cell], (m["name"], cell)
    for cell in CELLS:
        assert len(reported[cell]) >= 2, cell


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct_with_the_contract_keys(cell, no_chip_needed):
    result = rehearse(cell)
    assert list(result)[: len(RESULT_KEYS)] == RESULT_KEYS
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    wanted = {m["name"] for m in BENCH["end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(result["metrics"]) == wanted
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_traced_rehearsal_reads_host_layers_and_no_device_number(no_chip_needed):
    result = rehearse("boxgame-2p.wan-60hz", trace=True)
    assert result["correct"] is True, result["checks"]
    names = set(result["metrics"])
    assert {"bank_ms_p50.paced", "dispatch_ms_p50.paced", "rollback_share.paced",
            "generator_late_ms_p95.paced", "deadline_met_share.paced"} <= names
    # the CPU backend has no device plane: those readers find nothing to
    # read, and a reader that finds nothing returns nothing, never 0
    assert not names & {"tick_program_ms_p50.paced", "tick_program_roofline.paced",
                        "device_idle_share.paced", "device_peak_gb.paced"}
    assert 0 < result["metrics"]["rollback_share.paced"]["value"] < 100


@pytest.mark.parametrize("cell", CELLS)
def test_an_input_altered_where_it_is_staged_is_not_correct(
        cell, no_chip_needed, monkeypatch):
    tick = run.Pool.tick

    def altered(pool, row):
        if pool.ticks == 36:
            row = row.copy()
            row[0, 0] ^= 1
        tick(pool, row)

    monkeypatch.setattr(run.Pool, "tick", altered)
    result = rehearse(cell)
    assert result["correct"] is False
    assert result["checks"]["state_mismatch_sessions"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_digest_altered_where_it_is_produced_is_not_correct(
        cell, no_chip_needed, monkeypatch):
    from ggrs_tpu.parallel import session_pool

    digest = session_pool.checksum_device
    monkeypatch.setattr(session_pool, "checksum_device",
                        lambda state: digest(state) ^ 1)
    result = rehearse(cell)
    assert result["correct"] is False
    assert result["checks"]["digest_mismatch_samples"]["value"] > 0
    assert result["checks"]["state_mismatch_sessions"]["value"] == 0


def test_the_ecs_traffic_drives_the_contact_pass(no_chip_needed, monkeypatch):
    """Armies meet inside a full run's frames, and a run that reaches the
    cell's ``witness_by_frame`` without a contact is not correct.  The
    rehearsal's window is 40 ticks, not a time: 32 warm + 40 + 48 held = 120
    frames, before the first contact (frames 155-205) on any CPU."""
    from benchmark.reference import ecs_world

    spec = run.load_cell(REPO, "ecs-4p.wan-sat")
    config, by_frame = spec["config"], spec["size"]["witness_by_frame"]
    rows = generator.frame_inputs(
        generator.schedule(spec["traffic"], SEED, 2, 4, by_frame), 2)
    state, seen = ecs_world.init_state(config, 2), 0
    for row in rows:
        state = ecs_world.advance(config, state, row)
        seen += ecs_world.witness(state)
    assert seen > 0
    monkeypatch.setitem(spec["size"], "witness_by_frame", 40)
    monkeypatch.setattr(run, "load_cell", lambda root, cell: spec)
    loop = run.run_loop
    monkeypatch.setattr(
        run, "run_loop", lambda pool, inputs, traffic, seconds, ticks:
        loop(pool, inputs, traffic, None, 40 if ticks is None else ticks))
    result = rehearse("ecs-4p.wan-sat")
    assert result["attempted"] == 40 * 4 * 4
    assert result["checks"]["reference_saw_no_witness"]["value"] == 1
    assert result["correct"] is False


def test_reference_digest_equals_the_programs_on_every_family():
    from benchmark.reference import boxgame, digest, ecs_world
    from ggrs_tpu.ops.checksum import pytree_checksum

    rng = np.random.default_rng(5)
    for ref, config in ((boxgame, {"players": 2}),
                        (ecs_world, {"players": 4, "entities_per_player": 8})):
        state = ref.init_state(config, 2)
        for _ in range(12):
            inputs = rng.integers(0, 16, (2, config["players"])).astype(np.uint8)
            state = ref.advance(config, state, inputs)
        for m in range(2):
            one = {k: v[m] for k, v in state.items()}
            assert digest.u128(one) == pytree_checksum(one)
    with pytest.raises(TypeError):
        digest.u128({"x": np.zeros(3, np.int16)})


def test_a_schedule_too_short_for_the_host_fails_and_never_regrows():
    traffic = run.load_cell(REPO, CELLS[0])["traffic"]
    inputs = run.Inputs(traffic, SEED, 2, 2, 10, 2)
    assert inputs.frame_row(1).tolist() == [[0, 0], [0, 0]]
    np.testing.assert_array_equal(inputs.frame_row(5), inputs.row(3))
    with pytest.raises(RuntimeError, match="max_ticks_per_s"):
        inputs.row(10)
    assert len(inputs.rows) == 10


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, no_chip_needed, monkeypatch):
    import importlib

    config = run.load_cell(REPO, cell)["config"]
    adapter = importlib.import_module(f"benchmark.adapters.{config['adapter']}")
    make_game = adapter.make_game

    def stuck_game(config):
        game = make_game(config)
        return SimpleNamespace(advance=lambda state, inputs: state,
                               init_state=game.init_state)

    monkeypatch.setattr(adapter, "make_game", stuck_game)
    result = rehearse(cell)
    assert result["correct"] is False
    assert result["checks"]["state_mismatch_sessions"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    spec = run.load_cell(REPO, cell)
    config, traffic = spec["config"], spec["traffic"]
    rows = generator.schedule(traffic, SEED, 4, int(config["players"]), 160)
    rows = np.concatenate([rows, np.repeat(rows[-1:], 24, axis=0)])
    checks = control.control_checks(config, rows, SEED)
    assert checks["state_mismatch_sessions"] > 0


@pytest.mark.parametrize("family", ["boxgame", "ecs_world"])
def test_reference_copy_equals_the_repos_own_oracle(family):
    import importlib

    from ggrs_tpu.games import BoxGame, EcsWorld

    config = {"players": 4 if family == "ecs_world" else 2, "entities_per_player": 8}
    game = EcsWorld(4, 8) if family == "ecs_world" else BoxGame(2)
    ref = importlib.import_module(f"benchmark.reference.{family}")
    rng = np.random.default_rng(3)
    state, oracle = ref.init_state(config, 1), game.init_state_np()
    if family == "ecs_world":
        # crowd every unit into one 40-pixel box, so that the contact pass
        # and the respawn really fire (from the spawn corners nobody meets)
        crowd = (rng.integers(500, 540, oracle["pos"].shape) << 16).astype(np.int32)
        state["pos"], oracle["pos"] = crowd[None].copy(), crowd.copy()
    for _ in range(60):
        inputs = rng.integers(0, 16, (1, config["players"])).astype(np.uint8)
        state = ref.advance(config, state, inputs)
        oracle = game.advance_np(oracle, inputs[0])
    assert set(state) == set(oracle)
    for k in oracle:
        assert state[k].dtype == oracle[k].dtype
        np.testing.assert_array_equal(state[k][0], oracle[k])
    assert ref.state_bytes(config) == sum(v.nbytes for v in oracle.values())
    if family == "ecs_world":
        assert (oracle["health"] < 100).any()


def test_trace_reduction_gives_the_hand_computed_numbers():
    small = json.loads((Path(__file__).parent / "trace_small.json").read_text())
    got = trace_reduce.reduce_lines(small["lines"], "jit_tick", "bench.")
    want = small["expect"]
    assert got["chips"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["program_ms"] == pytest.approx(want["program_ms"])
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx(want["idle_gaps"])
    assert got["device_ops"][0] == [want["top_op"][0], pytest.approx(want["top_op"][1])]
    idle = 1 - got["busy_s"] / got["window_s"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(idle * got["window_s"])


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    host_only = [{"plane": "/host:CPU", "line": "python", "names": ["bench.fence"],
                  "start_ns": [0], "dur_ns": [10]}]
    assert trace_reduce.reduce_lines(host_only, "jit_tick", "bench.") == {}


def test_roofline_bytes_of_a_hand_made_plan():
    save, adv, load = (type(n, (), {}) for n in
                       ("SaveGameState", "AdvanceFrame", "LoadGameState"))
    plan = SimpleNamespace(
        quiet_rows=np.arange(5),                      # 5 x [save, advance]
        resim_rows=[(7, 30, 4, True, 0, 0),           # load, 4 advances, 3 saves
                    (8, 31, 2, False, 0, 0)],         # load, 2 advances, 2 saves
        save_only_rows=[(9, 33)],                     # 1 save
        eager_rows=[10],
        lists={10: [save(), load(), adv(), save(), adv()]},
    )
    counts = roofline.plan_counts(plan)
    assert counts == {"advances": 13, "saves": 13, "loads": 3}
    assert roofline.bytes_needed(counts, 40) == 40 * (2 * 13 + 13 + 3)
    share = roofline.roofline_share(819e9 * 0.5, 1.0, {"hbm_gbs": 819.0})
    assert share == pytest.approx(50.0)
    assert roofline.roofline_share(0, 1.0, {"hbm_gbs": 819.0}) is None


def test_a_plan_that_states_its_tally_is_counted_by_it():
    """A plan may carry its own count of what it asks (row kinds the column
    rule does not know, such as a sparse session's advance without a save):
    then the tally counts, not its columns; without one the rule holds."""
    save = type("SaveGameState", (), {})
    columns = dict(quiet_rows=np.arange(5), resim_rows=[(7, 30, 4, True, 0, 0)],
                   save_only_rows=[], eager_rows=[10], lists={10: [save()]})
    stated = SimpleNamespace(counts={"advances": 9, "saves": 2, "loads": 1,
                                     "rows": 6}, **columns)
    assert roofline.plan_counts(stated) == {"advances": 9, "saves": 2, "loads": 1}
    assert roofline.bytes_needed(roofline.plan_counts(stated), 40) == 40 * (18 + 3)
    assert roofline.plan_counts(SimpleNamespace(counts=None, **columns)) == {
        "advances": 9, "saves": 9, "loads": 1}
    with pytest.raises(KeyError):
        roofline.plan_counts(SimpleNamespace(counts={"advances": 9}, **columns))


def test_the_command_fails_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
