"""The ``particles`` family's own benchmark tests, and what the two cells of
PR 29 (``particles-2p.wan-sat``, ``ecs-4p.wan-60hz``) need beside the cases
that ``test_benchmark.py`` runs over every cell of ``BENCHMARK.json``.

``test_benchmark.py`` fixes its family lists, so the family's cases live here:
reference against oracle, digests, the witness, the two faults this family's
cell must catch (a steer step that ignores the mask, an expiry that is
skipped) and the ``registry_gauge`` reducer."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import generator, run  # noqa: E402
from benchmark.reducers import registry_gauge  # noqa: E402
from benchmark.reference import digest, particles  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "particles-2p.wan-sat"
CELLS = [CELL, "ecs-4p.wan-60hz"]  # the cells PR 29 brought
SEED = 2**31 + 77
SMALL = {"players": 2, "capacity": 200, "rate": 2, "ttl_frames": 50}


def rehearse(cell=CELL, trace=False):
    return run.run_cell(cell, SEED, 0.25, trace, matches=4)


# --- the two cells ----------------------------------------------------------


def kind_of(cell):
    return cell.rpartition("-")[2].replace("60hz", "paced")  # sat | paced


@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_reads_every_per_layer_metric_of_its_kind(cell):
    """The cell reads what the ``workloads`` lists name it in, each a metric
    of its kind, and among them every metric the first cell of its kind
    reads (a metric that one cell alone reads is that cell's)."""
    spec = run.load_cell(REPO, cell)
    kind = kind_of(cell)
    listed = {m["name"] for m in BENCH["per_layer"] if run._applies(m, cell)}
    read = {m["name"] for m in spec["metrics"]["per_layer"]}
    assert read == listed and all(n.endswith("." + kind) for n in read)
    first = {"sat": "boxgame-2p.wan-sat", "paced": "boxgame-2p.wan-60hz"}[kind]
    assert {m["name"] for m in BENCH["per_layer"] if run._applies(m, first)} <= read
    assert len(spec["metrics"]["end_to_end"]) == 2  # its own and setup_s
    # nothing of the source is cut; every departure from it is said
    assert spec["config"]["reduced"] == {}
    assert set(spec["config"]["departs_from_source"]) >= {"desync_detection"}


@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_traced_reads_the_host_layers_and_the_spans(
        cell, no_chip_needed):
    result = rehearse(cell, trace=True)
    assert result["correct"] is True, result["checks"]
    kind = kind_of(cell)
    host = {f"{n}.{kind}" for n in (
        "bank_ms_p50", "dispatch_ms_p50", "rollback_share",
        "bank_crossing_ms_p50", "bank_python_ms_p50", "descriptor_fill_ms_p50",
        "launch_ms_p50", "fast_slot_share", "span_coverage_share")}
    assert host <= set(result["metrics"])
    if cell == CELL:  # 8 sessions x (10 slots + 1 live) x 520,028 B and a little
        resident = result["metrics"]["ring_resident_gb.sat"]["value"]
        assert 8 * 11 * 520028e-9 < resident < 8 * 11 * 520300e-9
    # the CPU backend has no device plane: those readers return nothing
    assert f"tick_program_ms_p50.{kind}" not in result["metrics"]


# --- the family ------------------------------------------------------------


def test_reference_copy_equals_the_repos_own_oracle():
    from ggrs_tpu.games import ParticleWorld

    game = ParticleWorld(2, 200, 2, 50)
    rng = np.random.default_rng(3)
    state, oracle = particles.init_state(SMALL, 1), game.init_state_np()
    for _ in range(130):
        inputs = rng.integers(0, 16, (1, 2)).astype(np.uint8)
        state = particles.advance(SMALL, state, inputs)
        oracle = game.advance_np(oracle, inputs[0])
    assert set(state) == set(oracle)
    for k in oracle:
        assert state[k].dtype == oracle[k].dtype
        np.testing.assert_array_equal(state[k][0], oracle[k])
    assert particles.state_bytes(SMALL) == sum(v.nbytes for v in oracle.values())
    assert (oracle["ttl"] > 0).all()


def test_reference_digest_equals_the_programs():
    from ggrs_tpu.ops.checksum import pytree_checksum

    rng = np.random.default_rng(5)
    state = particles.init_state(SMALL, 2)
    for _ in range(60):
        state = particles.advance(
            SMALL, state, rng.integers(0, 16, (2, 2)).astype(np.uint8))
    for m in range(2):
        one = {k: v[m] for k, v in state.items()}
        assert digest.u128(one) == pytree_checksum(one)


def test_the_witness_needs_a_full_table_and_masks_that_steer(
        no_chip_needed, monkeypatch):
    """The cell's traffic fills the table, recycles it and moves the emitters
    off their quarter points by the cell's ``witness_by_frame``; masks that
    steer nothing are never a witness, and such a run is not correct."""
    spec = run.load_cell(REPO, CELL)
    config, by_frame = spec["config"], spec["size"]["witness_by_frame"]
    small = dict(config, capacity=200, rate=2)
    ttl = int(config["ttl_frames"])
    assert by_frame > ttl and config["capacity"] == 2 * config["rate"] * ttl
    rows = generator.frame_inputs(
        generator.schedule(spec["traffic"], SEED, 2, 2, by_frame), 2)
    for masks, steered in ((rows, True), (np.zeros_like(rows), False)):
        state, seen = particles.init_state(small, 2), []
        for row in masks:
            state = particles.advance(small, state, row)
            seen.append(particles.witness(state))
        # nothing before the table is full; then at most 4 born a match a frame
        assert seen[: ttl - 1] == [0] * (ttl - 1)
        assert all(0 <= n <= 2 * 4 for n in seen[ttl:])
        assert (sum(seen[ttl:]) > (by_frame - ttl) * 4) == steered
        assert steered or not any(seen)
    monkeypatch.setitem(spec["size"], "witness_by_frame", 60)
    monkeypatch.setattr(run, "load_cell", lambda root, cell: spec)
    monkeypatch.setattr(
        generator, "schedule", lambda *a, **k: np.zeros((a[4], a[2], a[3]), np.uint8))
    result = rehearse()
    assert result["checks"]["reference_saw_no_witness"]["value"] == 1
    assert result["correct"] is False


@pytest.mark.parametrize("step", ["_steer", "_age"])
def test_a_system_left_out_is_not_correct(step, no_chip_needed, monkeypatch):
    """A steer step that ignores the mask leaves every emitter where it
    started; an expiry that is skipped leaves the table full of the first 50
    frames' particles, since a spawn takes free slots only."""
    from ggrs_tpu.games import ParticleWorld

    monkeypatch.setattr(ParticleWorld, step, lambda self, first, *rest: first)
    result = rehearse()
    assert result["correct"] is False
    assert result["checks"]["state_mismatch_sessions"]["value"] > 0


def test_registry_gauge_reads_the_executors_size_or_nothing(monkeypatch):
    import jax.numpy as jnp

    from ggrs_tpu.obs import registry
    from ggrs_tpu.parallel import BatchedRequestExecutor

    args = {"gauge": "ggrs_executor_ring_resident_bytes", "scale": 1e-9}
    assert registry_gauge.reduce({}, {"gauge": "no_such_gauge"}) is None
    BatchedRequestExecutor(
        lambda state, inputs: state, {"x": jnp.zeros((5,), jnp.int32)},
        lambda pairs: np.zeros((2,), np.uint8),
        batch_size=4, ring_length=3, max_burst=2,
    )
    # 4 sessions x (3 slots + 1 live) x 20 bytes, + digests 4 x 3 x 16 and
    # frames 4 x 3 x 4
    resident = 4 * (3 + 1) * 20 + 4 * 3 * 16 + 4 * 3 * 4
    assert registry_gauge.reduce({}, args) == pytest.approx(resident * 1e-9)
    assert registry.default_registry().value("ggrs_executor_state_bytes") == 20
    # a program that lacks the gauge (this PR's parent) reads as nothing
    monkeypatch.setattr(registry, "DEFAULT", registry.Registry())
    assert registry_gauge.reduce({}, args) is None
