"""A configuration whose sessions save sparsely (``"saving": "sparse"``, GGRS's
``SessionBuilder::with_sparse_saving_mode``): ``run.Pool`` builds its sessions
so, and ``correct`` holds its ring to what sparse saving promises (every
frame a ring holds is confirmed, so it equals the reference whatever its age,
no session's newest save lies more than ``max_prediction`` frames back, and
no ring holds its last ``ring_length`` frames, as saving every frame leaves it).

These tests rehearse a scratch copy of ``particles-2p`` that says so, under a
root and a name of their own (``particles-2p-sparse-rehearsal``), as a
configuration added as data would be.  Sparse saving is held to those promises
on either side of the native bank: as the program builds the pool, and off the
bank, where the Python sessions that are the semantic reference run it.  On
the bank a sparse pool is held to every check an every-frame pool is, so it is
``correct`` there; off it ``native_bank_inactive`` says so, and the run is not
correct for that alone.  A configuration without the key is held to the
harness it had: the same builder calls, the same ring draws for a seed, the
same check keys.
"""

from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import generator, roofline, run  # noqa: E402
from ggrs_tpu.core.types import AdvanceFrame, LoadGameState, SaveGameState  # noqa: E402
from ggrs_tpu.parallel import host_bank  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
TWIN = "particles-2p.wan-sat"
# a name of its own: a real ``particles-2p-sparse`` may stand in BENCHMARK.json
CONFIG = "particles-2p-sparse-rehearsal"
CELL = f"{CONFIG}.wan-sat"
SEED = 2**31 + 39
# what compare() reported for every cell before a configuration could save
# sparsely; the witness check comes on top in a run that reaches its frame
WITNESS = "reference_saw_no_witness"
EVERY_FRAME_CHECKS = {
    "state_mismatch_sessions", "ring_mismatch_samples", "digest_mismatch_samples",
    "session_ticks_missing", "compiles_in_window", "bank_crossings_off_ticks",
    "plan_ticks_off_ticks", "slots_off_bank", "native_bank_inactive",
    "window_without_rollback"}
SPARSE_CHECKS = {"ring_behind_sessions", "ring_every_frame_sessions"}
HELD_TO_ZERO = ("state_mismatch_sessions", "ring_mismatch_samples",
                "digest_mismatch_samples", "ring_behind_sessions",
                "ring_every_frame_sessions", "session_ticks_missing", "compiles_in_window",
                "window_without_rollback")
# the pool as the program builds it, and the bank's gate closed to every
# builder: the Python sessions, which stay the semantic reference
SIDES = ("as_built", "off_bank")


def scratch_root(tmp_path: Path, saving="sparse") -> Path:
    """A checkout of the benchmark's data with one configuration more:
    ``particles-2p`` with ``saving`` set, and its cell under ``wan-sat``."""
    root = tmp_path / "root"
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    twin = run.load_cell(REPO, TWIN)
    config = dict(twin["config"], name=CONFIG)
    if saving is not None:
        config["saving"] = saving
    path = f"benchmark/configs/{CONFIG}.json"
    (root / path).write_text(json.dumps(config))
    (root / "benchmark" / "cells" / f"{CELL}.json").write_text(
        json.dumps(dict(twin["size"], name=CELL)))
    bench = copy.deepcopy(BENCH)
    entry = next(c for c in bench["configs"] if c["name"] == twin["cell"]["config"])
    bench["configs"].append(dict(entry, name=CONFIG, file=path))
    bench["workloads"].append(dict(twin["cell"], name=CELL, config=CONFIG))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def take_side(side: str, monkeypatch) -> None:
    """Build the pool on ``side`` of the bank (one of ``SIDES``)."""
    if side == "off_bank":
        monkeypatch.setattr(host_bank, "_bank_eligible",
                            lambda builder, hub_active=False: False)


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
def sparse_calls(monkeypatch):
    """Every ``with_sparse_saving_mode`` call a build makes."""
    from ggrs_tpu.sessions import SessionBuilder

    calls = []
    mode = SessionBuilder.with_sparse_saving_mode

    def recording(builder, on):
        calls.append(on)
        return mode(builder, on)

    monkeypatch.setattr(SessionBuilder, "with_sparse_saving_mode", recording)
    return calls


# --- the configuration's key ------------------------------------------------


@pytest.mark.parametrize("saving, calls", [(None, []), ("every_frame", []),
                                           ("sparse", [True] * 4)])
def test_the_saving_mode_is_the_files_and_absent_builds_as_before(
        saving, calls, tmp_path, sparse_calls):
    root = scratch_root(tmp_path, saving)
    spec = run.load_cell(root, CELL)
    pool = run.Pool(spec["config"], spec["traffic"], 2, SEED)
    assert sparse_calls == calls
    assert run.saves_sparsely(spec["config"]) == (saving == "sparse")
    assert pool.sessions == 4


def test_any_other_saving_is_refused_where_the_cell_is_loaded(tmp_path):
    root = scratch_root(tmp_path, "every_second_frame")
    with pytest.raises(SystemExit, match="saving must be one of"):
        run.load_cell(root, CELL)


# --- a sparse pool, rehearsed -----------------------------------------------


@pytest.mark.parametrize("side", SIDES)
def test_a_sparse_rehearsal_holds_every_ring_check_to_zero(
        side, tmp_path, no_chip_needed, monkeypatch, pools, sparse_calls):
    take_side(side, monkeypatch)
    result = run.run_cell(CELL, SEED, 0.25, False, root=scratch_root(tmp_path),
                          matches=4)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert set(checks) - {WITNESS} == EVERY_FRAME_CHECKS | SPARSE_CHECKS
    for name in HELD_TO_ZERO:
        assert checks[name] == 0, checks
    pool, = pools
    assert pool.sessions == 8
    assert sparse_calls == [True] * pool.sessions
    # the check says which side of the bank the sessions ran on; the side
    # whose gate the test closed runs them off it, so the fallback stays
    on_bank = pool.host.native_active
    assert checks["native_bank_inactive"] == int(not on_bank)
    if side == "off_bank":
        assert not on_bank
    if on_bank:
        # a sparse pool the bank serves is held to all an every-frame one
        # is: one crossing and one plan a tick, every slot on the bank
        assert checks["bank_crossings_off_ticks"] == checks["plan_ticks_off_ticks"] == 0
        assert checks["slots_off_bank"] == 0
        assert result["correct"] is True, checks
    else:
        # every session a Python session: no tick crosses into the bank,
        # and the run is not correct for that alone.  (``slot_state`` names
        # a healthy fallback slot "native", so ``slots_off_bank`` reads 0)
        assert "8 session(s) outside the bank's scope" in pool.host.native_reason
        assert checks["native_bank_inactive"] == 1
        assert checks["bank_crossings_off_ticks"] == checks["plan_ticks_off_ticks"] \
            == pool.ticks
        assert checks["slots_off_bank"] == 0
        assert result["correct"] is False
    # the sessions really saved sparsely: a ring that saves every frame holds
    # the last ring_length frames; these hold confirmed frames further apart
    held = run.ring_frames(pool)
    ring = int(run.load_cell(REPO, TWIN)["config"]["ring_length"])
    assert held.shape == (pool.sessions, ring)
    for s in range(pool.sessions):
        mine = np.sort(held[s][held[s] >= 0])
        assert mine.size and mine[-1] - mine[0] > ring - 1, mine


def test_a_held_slot_altered_on_the_device_is_caught(
        tmp_path, no_chip_needed, monkeypatch):
    """The first slot the seed draws is altered in one word after the hold,
    where the device keeps it: its digest is still the one taken when it was
    saved, its state is not."""
    compare = run.compare
    altered = {}

    def altering(pool, config, *args):
        ex = pool.executor
        (s, f), *_ = run.ring_draws(args[1], pool.sessions, pool.ticks, 1,
                                    run.ring_frames(pool))
        ring = ex._carry["ring"]
        states = ring["states"]
        leaf = sorted(states)[0]
        slot = f % int(config["ring_length"])
        ex._carry = dict(ex._carry, ring=dict(ring, states=dict(
            states, **{leaf: states[leaf].at[s, slot].add(1)})))
        altered.update(session=s, frame=f, leaf=leaf)
        return compare(pool, config, *args)

    monkeypatch.setattr(run, "compare", altering)
    result = run.run_cell(CELL, SEED, 0.25, False, root=scratch_root(tmp_path),
                          matches=4)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert altered and result["correct"] is False
    assert checks["ring_mismatch_samples"] >= 1
    assert checks["state_mismatch_sessions"] == 0  # the live state is untouched


def test_a_pool_that_saves_every_frame_under_a_sparse_file_is_not_correct(
        tmp_path, no_chip_needed, monkeypatch):
    """A program that ignores the setting: every session saves every frame.
    After the hold such a ring holds only confirmed frames, so its draws and
    digests match the reference and its newest save is the current frame;
    only ``ring_every_frame_sessions`` tells it from a sparse ring."""
    from ggrs_tpu.sessions import SessionBuilder

    monkeypatch.setattr(SessionBuilder, "with_sparse_saving_mode",
                        lambda builder, on: builder)
    result = run.run_cell(CELL, SEED, 0.25, False, root=scratch_root(tmp_path),
                          matches=4)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["ring_every_frame_sessions"] == 8, checks
    # every-frame sessions are the bank's: no other check reads wrong
    assert {k for k, v in checks.items() if v} == {"ring_every_frame_sessions"}
    assert result["correct"] is False


# --- a configuration without the key: the parent's comparison ---------------


def test_one_seeds_draws_for_the_particle_cell_are_the_parents():
    """``particles-2p.wan-sat`` saves every frame: ring 10, hold 24, so the
    draws lie in the last 9 frames.  The list is what the parent's two lines
    of ``compare`` drew for this seed at 512 sessions and 1,200 frames."""
    spec = run.load_cell(REPO, TWIN)
    hold = int(spec["traffic"]["hold_windows"]) * int(spec["config"]["max_prediction"])
    depth = max(1, min(int(spec["config"]["ring_length"]) - 1, hold))
    assert not run.saves_sparsely(spec["config"]) and depth == 9
    assert run.ring_draws(SEED, 512, 1200, depth) == [
        (459, 1192), (105, 1194), (50, 1194), (157, 1193), (506, 1197),
        (22, 1191), (136, 1192), (218, 1195), (26, 1198), (194, 1198),
        (479, 1198), (285, 1196), (180, 1195), (357, 1199), (192, 1196),
        (8, 1191)]
    # a frame before the first is no draw, as it was no sample
    assert all(f >= 0 for _, f in run.ring_draws(SEED, 512, 4, depth))


def test_a_configuration_without_the_key_is_compared_as_before(
        no_chip_needed, monkeypatch, sparse_calls):
    """The twin's rehearsal: no sparse call, the draws from the seed alone
    (no ring read), the reference kept at the drawn frames and the last, the
    parent's check keys and not ``ring_behind_sessions``."""
    seen = {}
    draws, states = run.ring_draws, run.reference_states

    def drawing(*args):
        seen["held"] = args[4:]
        seen["draws"] = draws(*args)
        return seen["draws"]

    def keeping(config, inputs, matches, frames, keep):
        seen["keep"], seen["frames"] = keep, frames
        return states(config, inputs, matches, frames, keep)

    monkeypatch.setattr(run, "ring_draws", drawing)
    monkeypatch.setattr(run, "reference_states", keeping)
    result = run.run_cell(TWIN, SEED, 0.25, False, matches=4)
    assert result["correct"] is True, result["checks"]
    assert sparse_calls == []
    assert seen["held"] == (None,)  # no ring read
    frames = seen["frames"]
    assert seen["keep"] == {frames} | {f for _, f in seen["draws"]}
    assert all(frames - 9 <= f < frames for _, f in seen["draws"])
    assert set(result["checks"]) - {WITNESS} == EVERY_FRAME_CHECKS


# --- the roofline's count of a plan ------------------------------------------


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("saving", ["sparse", pytest.param(None, id="absent")])
def test_a_plan_counts_its_own_requests(saving, side, no_chip_needed, monkeypatch):
    """What ``roofline.plan_counts`` reads of each plan the executor runs is
    what the plan's own requests ask for, taken before it runs: by its
    columns, or by the tally it states, wherever the bank hands the executor
    a plan.  Off the bank the executor gets the sessions' request lists,
    which ``run.Spans`` leaves out as it does here.  Sparse sessions save
    far fewer frames than they advance; every-frame ones save every advance
    but a resim's trailing live one."""
    take_side(side, monkeypatch)
    spec = run.load_cell(REPO, "boxgame-2p.wan-sat")
    config, traffic = dict(spec["config"]), spec["traffic"]
    if saving is not None:
        config["saving"] = saving
    pool = run.Pool(config, traffic, 2, SEED)
    tallies = []  # (what the roofline reads, what the requests ask) a plan
    execute = pool.executor.run

    def counted(plan):
        read = (roofline.plan_counts(plan)
                if getattr(plan, "quiet_rows", None) is not None else None)
        asked = dict.fromkeys(roofline.COUNTED, 0)
        for reqs in plan:  # a RequestPlan materializes each slot
            for req in reqs or ():
                for k, kind in (("advances", AdvanceFrame), ("saves", SaveGameState),
                                ("loads", LoadGameState)):
                    asked[k] += isinstance(req, kind)
        tallies.append((read, asked))
        execute(plan)

    pool.executor.run = counted
    rows = generator.schedule(traffic, SEED, 2, 2, 120)
    for row in rows:
        pool.tick(row)
    on_bank = pool.host.native_active
    if side == "off_bank":
        assert not on_bank
    assert len(tallies) == 120
    assert sum(read is not None for read, _ in tallies) == (120 if on_bank else 0)
    for read, asked in tallies:
        assert read is None or read == asked, (read, asked)
    total = {k: sum(asked[k] for _, asked in tallies) for k in roofline.COUNTED}
    assert total["loads"] > 0 and total["advances"] >= 4 * 120
    if saving == "sparse":
        assert total["saves"] < total["advances"] / 4
    else:
        assert total["saves"] >= total["advances"] - total["loads"]
