"""Structural performance pins for the hot device programs.

Tier-1 runs on the CPU backend, where wall-clock says nothing about the
chip, so perf regressions on the flagship replay and the batched-session
tick are pinned STRUCTURALLY instead, extending the pattern of
tests/test_device_executor_p2p.py's dispatch pin:

- dispatch-count pins: a steady-state chunk is exactly ONE jitted call
  (catches per-tick dispatching, chunk splitting, accidental warmup
  re-entry);
- program-shape pins: the tick program is two nested scans (outer ticks,
  inner resim window) with a bounded equation count (catches fusion
  structure loss, runaway unrolling, and graph blowup).

- primitive pins: the served pool's tick program (``session_tick`` under
  ``vmap``, each session at a frame of its own) holds no ``scatter`` and no
  more ``gather`` / ``dynamic_slice`` than its loads need, beside the burst
  loop's reads of three descriptor columns at its counter.  A per-session
  slot index under ``vmap`` IS visible to primitive counts: the write
  becomes a ``scatter`` and the read a ``gather``, and XLA:TPU runs each
  scatter as a serial loop over the sessions (PERF §6, PR 26: 67 of a 68 ms
  tick).

- source pins (no program compiled): the package graph and the census of
  environment switches, each held to ONE table in ``docs/DESIGN.md`` (§1
  "The package graph", §29), so the document and the test are the same
  table.

Known limitation, measured while building these: the ~30x
shared-vs-per-session ring-index regression of the REPLAY path
(ReplayPrograms docstring) is invisible to primitive counts — there both
forms produce identical jaxprs up to the VALUES feeding the scatter
indices — so that property stays covered by its behavioral test and the
bench deltas, not by these pins.
"""

from __future__ import annotations

import ast
import functools
import re
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ggrs_tpu.games import EcsWorld, ParticleWorld
from ggrs_tpu.games.boxgame import BoxGame
from ggrs_tpu.ops.replay import build_replay_programs
from ggrs_tpu.parallel.batch import BatchedSessions, make_mesh
from ggrs_tpu.parallel.session_pool import BatchedRequestExecutor
from ggrs_tpu.sessions.device_synctest import DeviceSyncTestSession


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of its sub-jaxprs."""
    for eq in jaxpr.eqns:
        yield eq
        for v in eq.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _walk_primitives(closed_jaxpr) -> Counter:
    """Primitive-name counts over a jaxpr, recursing into sub-jaxprs."""
    return Counter(eq.primitive.name for eq in _walk_eqns(closed_jaxpr.jaxpr))


class TestFlagshipReplayPins:
    def make_session(self):
        game = BoxGame(2)
        return game, DeviceSyncTestSession(
            game.advance, game.init_state(), jnp.zeros((2,), jnp.uint8),
            check_distance=8, max_prediction=8,
        )

    def test_steady_chunk_is_exactly_one_dispatch(self):
        """After warmup, each run_ticks(chunk) must invoke the steady
        program exactly once and the warmup program never."""
        _, sess = self.make_session()
        chunk = np.zeros((32, 2), np.uint8)
        sess.run_ticks(chunk, check=False)  # covers the warmup split
        calls = {"steady": 0, "warmup": 0}
        orig_steady = sess._programs.run_steady
        orig_warm = sess._programs.run_warmup

        def spy_steady(*a, **k):
            calls["steady"] += 1
            return orig_steady(*a, **k)

        def spy_warm(*a, **k):
            calls["warmup"] += 1
            return orig_warm(*a, **k)

        # ReplayPrograms is frozen; bypass for the spy
        object.__setattr__(sess._programs, "run_steady", spy_steady)
        object.__setattr__(sess._programs, "run_warmup", spy_warm)
        try:
            for i in range(3):
                sess.run_ticks(chunk, check=False)
        finally:
            object.__setattr__(sess._programs, "run_steady", orig_steady)
            object.__setattr__(sess._programs, "run_warmup", orig_warm)
        assert calls == {"steady": 3, "warmup": 0}, calls
        sess.verify()  # and the ticks were real (desync gate still green)

    def test_steady_program_shape(self):
        """Two nested scans (ticks outer, resim window inner), no
        while/cond, equation count bounded at ~2x today's 419."""
        game = BoxGame(2)
        progs = build_replay_programs(game.advance, 9, 8, donate=False)
        carry0 = progs.init_carry(game.init_state(), jnp.zeros((2,), jnp.uint8))
        j = jax.make_jaxpr(progs.scan_steady)(
            carry0, jnp.zeros((32, 2), jnp.uint8), np.int32(9)
        )
        counts = _walk_primitives(j)
        assert counts["scan"] == 2, counts["scan"]
        assert counts.get("while", 0) == 0
        assert counts.get("cond", 0) == 0
        total = sum(counts.values())
        assert total < 850, (
            f"steady tick program grew to {total} equations (was ~419); "
            f"check for lost fusion structure or runaway unrolling"
        )


class TestBatchedSessionsPins:
    @pytest.fixture()
    def batched(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        game = BoxGame(2)
        return game, BatchedSessions(
            game.advance, game.init_state(), jnp.zeros((2,), jnp.uint8),
            batch_size=16, mesh=make_mesh(8),
            check_distance=8, max_prediction=8,
        )

    def test_steady_chunk_is_exactly_one_dispatch(self, batched):
        _, bs = batched
        chunk = np.zeros((16, 32, 2), np.uint8)
        bs.run_ticks(chunk, check=False)  # warmup split
        calls = {"steady": 0, "warmup": 0}
        orig_steady, orig_warm = bs._run_steady, bs._run_warmup
        bs._run_steady = lambda *a: (
            calls.__setitem__("steady", calls["steady"] + 1) or orig_steady(*a)
        )
        bs._run_warmup = lambda *a: (
            calls.__setitem__("warmup", calls["warmup"] + 1) or orig_warm(*a)
        )
        try:
            for _ in range(3):
                bs.run_ticks(chunk, check=False)
        finally:
            bs._run_steady, bs._run_warmup = orig_steady, orig_warm
        assert calls == {"steady": 3, "warmup": 0}, calls
        stats = bs.verify()
        assert stats["mismatches"] == 0

    def test_sharded_steady_program_shape(self, batched):
        """The whole-pool tick lowers to ONE fused program: a single
        top-level while (the ticks scan), bounded size, and the two on-mesh
        stat reductions (psum/pmin) — no extra collectives."""
        _, bs = batched
        chunk = jnp.zeros((16, 32, 2), jnp.uint8)
        txt = bs._run_steady.lower(
            bs._carry, chunk, np.int32(9)
        ).as_text()
        lines = len(txt.splitlines())
        # exactly the two loops of the design: the outer ticks scan and the
        # (rolled, round-4 retune) inner resim scan — anything more means
        # the program split
        assert 1 <= txt.count("stablehlo.while") <= 2, "tick scan must stay fused"
        assert lines < 2000, (
            f"sharded tick program grew to {lines} stablehlo lines "
            f"(was ~950); check for structure loss"
        )
        # collectives: exactly the two stat reductions ride the mesh
        assert txt.count("all_reduce") <= 2, "unexpected extra collectives"


def _indexing(counts: Counter) -> Counter:
    return Counter({
        name: n for name, n in counts.items()
        if "scatter" in name or "gather" in name or "dynamic" in name
    })


class TestPoolTickProgramPins:
    """The served pool's one tick program (``BatchedRequestExecutor._tick``):
    every ring write is a select over the ring axis, so nothing in it is
    indexed by a per-session slot but ``ring.load``; and the burst loop,
    whose trip count is the batch's deepest plan, reads its step's
    descriptor columns at the counter the whole batch shares."""

    @pytest.mark.parametrize(
        "make_game,players,ring_length,max_burst",
        [
            (lambda: BoxGame(2), 2, 10, 9),  # boxgame-2p: 3 state leaves
            (lambda: EcsWorld(4, 8), 4, 18, 17),  # ecs-4p's shape: 5 leaves
            (lambda: ParticleWorld(2, 64, 4, 8), 2, 10, 9),  # particles-2p's: 7
        ],
        ids=["boxgame-2p", "ecs-4p", "particles-2p"],
    )
    def test_no_scatter_and_only_the_loads_gather(
        self, make_game, players, ring_length, max_burst
    ):
        game = make_game()
        batch = 4
        ex = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda inputs: np.zeros((players,), np.uint8),
            batch_size=batch, ring_length=ring_length, max_burst=max_burst,
        )
        example = np.zeros((players,), np.uint8)
        ex.warmup(example)
        tick = jax.make_jaxpr(ex._tick)(ex._carry, ex._blank_desc())
        got = _indexing(_walk_primitives(tick))
        # what the game's own step indexes (BoxGame's direction tables),
        # counted once: the burst loop's body appears once in the jaxpr
        own = _indexing(_walk_primitives(
            jax.make_jaxpr(game.advance)(game.init_state(), jnp.asarray(example))
        ))
        leaves = len(jax.tree_util.tree_leaves(game.init_state()))
        assert not [n for n in got if "scatter" in n], got
        assert got["dynamic_update_slice"] == own["dynamic_update_slice"], got
        # a read at ONE index for the whole batch (a dynamic_slice, or the
        # gather of a single start index that vmap makes of one) against a
        # read at an index of each session's own
        shared, per_session = [], []
        for eq in _walk_eqns(tick.jaxpr):
            if eq.primitive.name == "dynamic_slice":
                shared.append(eq)
            elif eq.primitive.name == "gather":
                one_index = eq.invars[1].aval.ndim == 1
                (shared if one_index else per_session).append(eq)
        # ring.load: one gather per state leaf, and nothing else
        reads = lambda c: c["gather"] + c["dynamic_slice"]
        assert len(per_session) <= reads(own) + leaves, (got, own, leaves)
        # the burst loop's step: inputs, save_mask and save_frame at the
        # loop counter, each a column [batch, max_burst, ...] of the
        # descriptor; whatever else is shared is the game's own
        columns = [eq for eq in shared
                   if eq.invars[0].aval.shape[:2] == (batch, max_burst)]
        assert len(columns) == 3, shared
        assert len(shared) - len(columns) <= reads(own), (shared, own)

    def test_the_burst_loop_has_no_static_length(self):
        """The program's one loop takes its trip count from the descriptor
        (the batch's deepest plan): a scan, or a fori_loop of constant
        bounds, which lowers to one, over ``max_burst`` steps would run them
        all whatever the plans ask (PERF §6, PR 30: 9 steps where 2 were
        asked for)."""
        game = BoxGame(2)
        ex = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda inputs: np.zeros((2,), np.uint8),
            batch_size=4, ring_length=10, max_burst=7,
        )
        ex.warmup(np.zeros((2,), np.uint8))
        tick = jax.make_jaxpr(ex._tick)(ex._carry, ex._blank_desc())
        loops = [eq for eq in _walk_eqns(tick.jaxpr)
                 if eq.primitive.name in ("scan", "while")]
        assert [eq.primitive.name for eq in loops] == ["while"], loops
        (compare,) = loops[0].params["cond_jaxpr"].jaxpr.eqns
        assert compare.primitive.name == "lt", compare
        # counter < bound, both carried or closed over: no literal 7
        assert not [v for v in compare.invars if hasattr(v, "val")], compare

    @pytest.mark.parametrize("shards", [1, 4])
    def test_the_tick_takes_one_descriptor_operand_beside_the_carry(
        self, shards
    ):
        """A dispatch sends one descriptor buffer, ``u8[B, W]`` (PR 38: ten
        arrays were 40 transfers on four chips): the program's operands are
        the carry's leaves and that buffer, nothing else."""
        if len(jax.devices()) < shards:
            pytest.skip("needs the virtual CPU mesh of tests/conftest.py")
        game = BoxGame(2)
        ex = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda inputs: np.zeros((2,), np.uint8),
            batch_size=8, ring_length=10, max_burst=9,
            mesh=make_mesh(shards) if shards > 1 else None,
        )
        ex.warmup(np.zeros((2,), np.uint8))
        desc = ex._blank_desc()
        tick = jax.make_jaxpr(ex._tick)(ex._carry, desc)
        *carry, packed = tick.jaxpr.invars
        assert len(carry) == len(jax.tree_util.tree_leaves(ex._carry))
        # 4 int32 frames and counts, 9 save frames, 9 x 2 input bytes,
        # 3 + 9 masks: 82 bytes, 84 at int32 alignment
        assert packed.aval.shape == (8, 84) == desc.packed.shape
        assert packed.aval.dtype == np.uint8


# ----------------------------------------------------------------------
# source pins: the package graph and the switch census, against DESIGN.md
# ----------------------------------------------------------------------

REPO = Path(__file__).resolve().parents[1]
PACKAGE = REPO / "ggrs_tpu"
# every top-level entry of the package: its twelve sub-packages and the two
# modules beside them (``chaos``, ``__init__``)
UNITS = sorted(
    p.stem if p.is_file() else p.name
    for p in PACKAGE.iterdir()
    if p.suffix == ".py" or (p / "__init__.py").is_file()
)


def _design_table(heading: str) -> list:
    """The rows (lists of cells) of the first table under ``heading`` in
    docs/DESIGN.md, header and ruler dropped."""
    lines = (REPO / "docs" / "DESIGN.md").read_text().splitlines()
    at = lines.index(heading)
    start = next(i for i in range(at, len(lines)) if lines[i].startswith("|"))
    rows = []
    for line in lines[start:]:
        if not line.startswith("|"):
            break
        rows.append([c.strip() for c in line.strip("|").split("|")])
    return rows[2:]


def _unit_arrows(unit: str) -> dict:
    """``{imported unit: {importing files}}`` over every ``import`` statement
    of ``unit``'s files (function-level ones too), itself left out."""
    files = (
        [PACKAGE / f"{unit}.py"]
        if (PACKAGE / f"{unit}.py").is_file()
        else sorted((PACKAGE / unit).rglob("*.py"))
    )
    arrows: dict = {}
    for path in files:
        here = ("ggrs_tpu",) + path.relative_to(PACKAGE).parts[:-1]
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                targets = [a.name.split(".") for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # level 1 starts from the file's own package
                base = list(here[: len(here) - node.level + 1]) if node.level else []
                base += node.module.split(".") if node.module else []
                # ``from .. import x`` names the unit in the alias
                targets = (
                    [base + [a.name] for a in node.names]
                    if len(base) == 1 else [base]
                )
            else:
                continue
            for target in targets:
                if target[0] == "ggrs_tpu" and len(target) > 1 and target[1] != unit:
                    arrows.setdefault(target[1], set()).add(
                        str(path.relative_to(PACKAGE))
                    )
    return arrows


class TestPackageGraph:
    """docs/DESIGN.md §1 "The package graph": bottom to top, each row lists
    what the unit may import, and only rows above it — a total order, so no
    cycle but the exception the table names."""

    @pytest.fixture(scope="class")
    def table(self):
        rows = _design_table("### The package graph")
        table = {}
        for unit_cell, may_cell, _ in rows:
            (unit,) = re.findall(r"`(\w+)`", unit_cell)
            allowed, _, exception = may_cell.partition("exception:")
            assert set(re.findall(r"`(\w+)`", allowed)) <= set(table), (
                f"{unit}'s row names a unit that is not above it"
            )
            table[unit] = (
                set(re.findall(r"`(\w+)`", allowed)),
                re.findall(r"`([\w/.]+)`", exception),
            )
        return table

    @pytest.mark.parametrize("unit", UNITS)
    def test_unit_imports_only_what_its_row_lists(self, table, unit):
        assert unit in table, f"{unit} has no row in DESIGN.md's package table"
        allowed, exception = table[unit]
        arrows = _unit_arrows(unit)
        if exception:
            target, source = exception
            assert arrows.pop(target, None) == {source}, (
                f"the named exception {source} -> {target} is not what the "
                f"tree holds: delete it from the table or restore it"
            )
        assert set(arrows) <= allowed, {
            t: sorted(arrows[t]) for t in set(arrows) - allowed
        }


@functools.cache
def _switches_read() -> dict:
    """``{GGRS_TPU_* name: {files}}`` for every such string constant under
    ``ggrs_tpu/`` (a name in a docstring or a message is part of a longer
    string and does not match)."""
    found: dict = {}
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and re.fullmatch(r"GGRS_TPU_[A-Z0-9_]+", node.value)
            ):
                found.setdefault(node.value, set()).add(
                    str(path.relative_to(REPO))
                )
    return found


class TestSwitchCensus:
    """docs/DESIGN.md §29: every ``GGRS_TPU_*`` variable the package reads
    has a row saying what it forces and who needs it, and no row outlives
    its reader."""

    @pytest.fixture(scope="class")
    def table(self):
        rows = _design_table("## 29. Environment switches")
        return {
            re.fullmatch(r"`(GGRS_TPU_\w+)`", name).group(1): (forces, needs)
            for name, forces, needs in rows
        }

    @pytest.mark.parametrize("switch", sorted(_switches_read()))
    def test_switch_has_a_row(self, table, switch):
        assert switch in table, (
            f"{switch} is read by {sorted(_switches_read()[switch])} and has "
            f"no row in DESIGN.md §29: say what it forces and who needs it"
        )
        forces, needs = table[switch]
        assert forces and needs
        for user in re.findall(r"`((?:tests|scripts)/[\w/.]+)`", needs):
            assert switch in (REPO / user).read_text(), (
                f"§29 names {user} as a user of {switch}; it is not"
            )

    def test_no_row_without_a_reader(self, table):
        assert set(table) <= set(_switches_read())
