"""The ring's layout at the tick program's boundary, and the save that
writes one slot of a leaf held so (DESIGN.md §3).

Four things, none on a chip: (e) the in-place write against the select,
byte for byte, under Pallas's interpreter; (a) the rule as a function of shapes; (b) the
tick compiled for a v5e that is described, not attached, from shapes alone
(nothing of 2.9 GB is allocated): a count of what the compiler wrote, never
a time; (c) on the CPU, that holding the ring in another layout changes no
value.  Every compile for the described chip lives in THIS file, behind one
fixture: a process loads the TPU's library once.
"""

import functools
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.experimental.layout import Format

from ggrs_tpu.games import BoxGame, EcsWorld, ParticleWorld
from ggrs_tpu.obs.registry import default_registry
from ggrs_tpu.ops.ring import DeviceStateRing, writes_slot_in_place
from ggrs_tpu.parallel import BatchedRequestExecutor, session_pool
from ggrs_tpu.parallel.session_pool import (
    blank_desc,
    ring_leaf_layout,
    tick_program,
)

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "scripts") not in sys.path:
    sys.path.insert(0, str(REPO / "scripts"))

from test_session_pool import _drive, _make_matches, _to_arr  # noqa: E402

# the benchmark's three configurations at their cells' populations:
# sessions, ring length, burst, game
CELLS = {
    "particles-2p": (512, 10, 9, lambda: ParticleWorld(2, 10000, 100, 50)),
    "boxgame-2p": (512, 10, 9, lambda: BoxGame(2)),
    "ecs-4p": (256, 18, 17, lambda: EcsWorld(4, 32)),
}
# the five 10,000-wide leaves of a particle state and the tile of each
PARTICLE_TILES = {
    "translation": 4, "rotation": 4, "scale": 4, "ttl": 1, "velocity": 2,
}


def _program(cell, sessions=None, **where):
    b, ring, _burst, make = CELLS[cell]
    game = make()
    return tick_program(
        game.advance, game.init_state(), sessions or b, ring, **where
    )


def _layouts(formats):
    """path -> Layout or None, over a carry-shaped tree of formats."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        formats, is_leaf=lambda f: f is None
    )
    return {
        "/".join(str(k.key) for k in path): f if f is None else f.layout
        for path, f in flat
    }


# ---------------------------------------------------------------------------
# (a) the rule, from shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("leaf", sorted(PARTICLE_TILES))
def test_wide_particle_ring_leaves_are_held_row_major(leaf):
    program = _program("particles-2p")
    layout = _layouts(program.formats)[f"ring/states/{leaf}"]
    # ttl, [B, R, N] by its state, is held [B, R, 1, N] (DESIGN.md §3)
    rank = len(program.carry["ring"]["states"][leaf].shape)
    assert rank == 4
    assert layout.major_to_minor == tuple(range(rank))
    assert layout.tiling == ((PARTICLE_TILES[leaf], 128),)
    held = program.carry["ring"]["states"][leaf]
    assert isinstance(held.format, Format) and held.format.layout == layout


def test_every_other_particle_leaf_keeps_the_default():
    layouts = _layouts(_program("particles-2p").formats)
    relaid = {p for p, l in layouts.items() if l is not None}
    assert relaid == {f"ring/states/{k}" for k in PARTICLE_TILES}
    for kept in ("ring/states/emitter", "ring/states/resources",
                 "ring/checksums", "ring/frames", "live/ttl",
                 "live/rotation", "live/emitter"):
        assert layouts[kept] is None
    assert sum(p.startswith("live/") for p in layouts) == 7


@pytest.mark.parametrize("cell", ["boxgame-2p", "ecs-4p"])
def test_the_small_cells_keep_the_default_everywhere(cell):
    program = _program(cell)
    assert set(_layouts(program.formats).values()) == {None}
    if cell == "ecs-4p":  # lane-wide and lane-narrow leaves, both small
        shapes = {
            l.shape[2:]
            for l in jax.tree_util.tree_leaves(program.carry["ring"]["states"])
        }
        assert {(128,), (128, 2)} <= shapes


@pytest.mark.parametrize(
    "shape, itemsize, tile",
    [
        ((512, 10, 4, 10000), 4, 4),
        ((512, 10, 3, 10000), 4, 4),
        ((512, 10, 2, 10000), 4, 2),
        ((512, 10, 1, 10000), 4, 1),
        # the second-minor is the ring axis: what the rule says of the shape;
        # the pool holds such a leaf as the line before (DESIGN.md §3)
        ((512, 10, 10000), 4, 8),
        ((512, 10, 16, 10000), 4, 8),
        ((128, 10, 4, 10000), 4, 4),  # a quarter of the pool: still large
        ((256, 18, 128), 4, None),  # ecs-4p: lane-wide, 2.4 MB
        ((256, 18, 128, 2), 4, None),  # 2 wide: 64 times the bytes row-major
        ((512, 10, 10000, 2), 4, None),  # large, and as narrow
        ((512, 10, 4, 100), 4, None),  # under a tile's 128 lanes
        ((8, 10, 4, 10000), 4, None),  # 12.8 MB: under the threshold
        ((512, 10), 4, None),  # frames: no state dimension
        ((512, 10, 4, 10000), 2, None),  # other widths tile otherwise
    ],
)
def test_the_rule_reads_trailing_dimensions_and_bytes(shape, itemsize, tile):
    layout = ring_leaf_layout(shape, itemsize)
    if tile is None:
        assert layout is None
    else:
        assert layout.major_to_minor == tuple(range(len(shape)))
        assert layout.tiling == ((tile, 128),)


def test_a_shard_of_a_mesh_decides_as_one_device_of_its_size():
    from ggrs_tpu.parallel import make_mesh

    mesh = make_mesh(4)
    one = _layouts(_program("particles-2p").formats)
    # 2,048 sessions over four devices: each holds what one device holds
    across = _program("particles-2p", sessions=2048, mesh=mesh)
    assert _layouts(across.formats) == one
    shardings = {
        f.sharding for f in jax.tree_util.tree_leaves(across.formats)
    }
    assert len(shardings) == 1 and shardings.pop().mesh == mesh
    # 24 sessions: the whole `ttl` ring (9.6 MB x 4) would pass on one
    # device of 96, a shard of 24 does not
    assert ring_leaf_layout((96, 10, 10000), 4) is not None
    small = _layouts(_program("particles-2p", sessions=96, mesh=mesh).formats)
    assert small["ring/states/ttl"] is None
    assert small["ring/states/rotation"] is not None  # 38 MB a shard


# ---------------------------------------------------------------------------
# (b) what the compiler writes for a described v5e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def _compiled(program, cell, chip, tick=None):
    from jax.sharding import SingleDeviceSharding

    b, _ring, burst, _ = CELLS[cell]
    players = 4 if cell == "ecs-4p" else 2
    desc = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=SingleDeviceSharding(chip)
        ),
        blank_desc(b, burst, (players,), np.uint8),
    )
    return (tick or program.tick).lower(program.carry, desc).compile()


@pytest.fixture(scope="module")
def particle_tick(chip):
    program = _program("particles-2p", device=chip)
    return program, _compiled(program, "particles-2p", chip)


def _ring_sized(text, sessions=512):
    """(operation, K) -> count, over EVERY computation of a compiled text, of
    the instructions whose result is a whole wide ring leaf
    ``s32[sessions, 10, K, 10000]`` (K None: a leaf of rank 3, which no
    carry holds any more)."""
    found = {}
    # (a write kernel's result is the ring leaf AND its savers' lane sums)
    for m in re.finditer(
        rf"= \(?s32\[{sessions},10,(?:(\d+),)?10000\]\S*"
        rf"(?: s32\[{4 * sessions}\]\S*\))? ([\w-]+)\(", text
    ):
        key = (m.group(2), m.group(1) and int(m.group(1)))
        found[key] = found.get(key, 0) + 1
    return found


# the three writes of a tick: before the load, after it, in the burst loop
_WRITES_A_TICK = 3


def _assert_written_in_place(text, sessions=512):
    found = _ring_sized(text, sessions)
    assert found, "the census found no ring-sized result: it reads nothing"
    moved = {k: n for k, n in found.items()
             if k[0] in ("copy", "transpose", "select", "fusion")}
    assert moved == {}
    # one kernel a re-laid leaf a write, the ring its operand AND its result
    # (K: rotation 4, scale and translation 3, velocity 2, ttl 1)
    calls = {k[1]: n for k, n in found.items() if k[0] == "custom-call"}
    assert calls == {
        4: _WRITES_A_TICK, 3: 2 * _WRITES_A_TICK, 2: _WRITES_A_TICK,
        1: _WRITES_A_TICK,
    }
    kernels = re.findall(r".*custom_call_target=\"tpu_custom_call\".*", text)
    assert len(kernels) == 5 * _WRITES_A_TICK
    for line in kernels:
        assert "output_to_operand_aliasing={{0}: (4, {})}" in line
        assert "ring_write_slot" in line


def test_the_particle_tick_holds_no_ring_transpose_at_its_boundary(
        chip, particle_tick):
    from profile_tick import entry_copies

    program, compiled = particle_tick
    text = compiled.as_text()
    copies = entry_copies(text)
    assert copies, "the census found no copy at all: it reads nothing"
    ring_sized = [s for s in copies if re.match(r"\w+\[512,10,.*10000\]", s)]
    assert ring_sized == []
    # the ten live-sized ones stay: the live leaves keep the default
    assert sum(n for s, n in copies.items() if "10000]" in s) == 10
    # aliased whole: every leaf of the donated carry is a result's buffer
    leaves = len(jax.tree_util.tree_leaves(program.carry))
    assert text.splitlines()[0].count("may-alias") == leaves == 16
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 4096
    # the padded row-major ring: 3.37 GB (T(4,128) pads the 3-word leaves to
    # 4 rows; ttl's unit axis ended its padding, 3.50 GB with it), and under
    # a gigabyte beside it
    assert 3.3e9 < memory.argument_size_in_bytes < 3.5e9
    assert memory.temp_size_in_bytes < 1.0e9
    # and it is made so: the initialiser writes 3.4 GB once, with no temporary
    made = program.init.lower(
        jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
            ParticleWorld(2, 10000, 100, 50).init_state(),
        )
    ).compile()
    assert made.memory_analysis().temp_size_in_bytes == 0
    out = _layouts(made.output_formats)
    assert {p: l for p, l in out.items() if l.major_to_minor[0] == 0} == {
        p: l for p, l in _layouts(program.formats).items() if l is not None
    }


def test_the_particle_tick_writes_its_wide_leaves_in_place(particle_tick):
    """The ring passes through a kernel inside the burst loop's body and
    inside the conditional's branch: no computation holds a copy, a
    transposition or a select of a whole wide leaf around it."""
    program, compiled = particle_tick
    assert program.in_place == {
        "emitter": False, "resources": False, "ttl": True,
        "rotation": True, "scale": True, "translation": True, "velocity": True,
    }
    _assert_written_in_place(compiled.as_text())


def test_the_particle_tick_digests_no_wide_leaf_outside_its_write_kernels(
        particle_tick):
    """``jax.vmap(checksum_device)`` over the batch was a multiply and a
    reduce over every ``[512, K, 10000]`` leaf at each write; the kernels
    digest the slot they write, and what XLA still digests is the two small
    leaves (7 words a session)."""
    _program_, compiled = particle_tick
    text = compiled.as_text()
    wide = re.compile(r"[su]32\[512,(?:\d+,)?10000\]")
    digest_ops = [
        line for line in text.splitlines()
        if "/digest/" in line and wide.search(line)
    ]
    assert digest_ops == []
    # no arithmetic of the lanes (a u32 multiply, a u32 reduce) on an
    # operand of that size anywhere in the text, under any name
    u32_wide = re.compile(r"u32\[512,(?:\d+,)?10000\]")
    assert not [
        line for line in text.splitlines()
        if u32_wide.search(line) and re.search(r" (multiply|reduce)\(", line)
        and "/advance/" not in line
    ]
    # what is left under the scope is small: the two leaves the select
    # writes, the salt and the finalizer
    left = [line for line in text.splitlines() if "/digest/" in line]
    assert left, "the census found no digest at all: it reads nothing"
    assert all(
        int(np.prod([int(d) for d in dims.split(",")])) <= 512 * 7 * 4
        for line in left
        for dims in re.findall(r"= \(?[a-z]+\d*\[([\d,]+)\]", line)
    )
    # and every kernel hands its savers' lane sums out beside the ring
    kernels = re.findall(r".*custom_call_target=\"tpu_custom_call\".*", text)
    assert len(kernels) == 15
    assert all(re.search(r", s32\[2048\]\S*\) custom-call\(", k)
               for k in kernels)


def test_a_profile_names_the_write_kernels_and_what_is_left_of_the_digest(
        particle_tick):
    """``scripts/profile_tick.py``'s scope table: the kernels that write and
    digest under ``write_slot``, XLA's remainder under ``digest``."""
    from profile_tick import hlo_scopes, scope_of

    assert scope_of(
        "jit(tick)/while/body/ring.save/cond/branch_1_fun/write_slot/"
        "ring_write_slot/pallas_call") == "ring.save > write_slot"
    scopes = hlo_scopes(particle_tick[1].as_text())
    kernels = [v for k, v in scopes.items() if k.startswith("ring_write_slot")]
    assert sorted(set(kernels)) == [
        "ring.pre_save > write_slot", "ring.save > write_slot"]
    assert kernels.count("ring.pre_save > write_slot") == 5
    assert {"ring.pre_save > digest", "ring.save > digest"} <= set(
        scopes.values())


def test_the_tick_over_a_mesh_of_four_chips_holds_none_either(chip):
    """2,048 sessions over the four chips of the described host: each shard
    is the one-chip program, and no collective joins them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from profile_tick import entry_copies

    mesh = Mesh(np.asarray(chip.client.devices()[:4]), ("sessions",))
    program = _program("particles-2p", sessions=2048, mesh=mesh)
    across = NamedSharding(mesh, PartitionSpec(("sessions",)))
    desc = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=across),
        blank_desc(2048, 9, (2,), np.uint8),
    )
    compiled = program.tick.lower(program.carry, desc).compile()
    text = compiled.as_text()
    copies = entry_copies(text)
    assert copies and not [
        s for s in copies if re.match(r"\w+\[512,10,.*10000\]", s)
    ]
    assert not re.search(r"all-reduce|all-gather|collective-permute", text)
    assert 3.3e9 < compiled.memory_analysis().argument_size_in_bytes < 3.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    assert text.splitlines()[0].count("may-alias") == 16
    # and each shard writes its own 512 sessions in place
    _assert_written_in_place(text)


@pytest.mark.parametrize("cell", ["boxgame-2p", "ecs-4p"])
def test_the_small_cells_compile_to_the_program_of_before(cell, chip):
    program = _program(cell, device=chip)
    # "before": the donated jit with nothing said about the carry
    plain = jax.jit(program.tick.__wrapped__, donate_argnums=(0,))
    text = _compiled(program, cell, chip).as_text()
    assert text == _compiled(program, cell, chip, tick=plain).as_text()
    # every leaf under the rule: the select as before, no kernel
    assert set(jax.tree_util.tree_leaves(program.in_place)) == {False}
    assert "tpu_custom_call" not in text


# ---------------------------------------------------------------------------
# (c) the layout never changes a value
# ---------------------------------------------------------------------------


def _run_particles(min_bytes, monkeypatch, seed, ticks=36):
    monkeypatch.setattr(session_pool, "_RELAY_MIN_BYTES", min_bytes)
    sessions, schedules = _make_matches(3, seed=seed)
    game = ParticleWorld(2, 256, 8, 16)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), _to_arr,
        batch_size=len(sessions), ring_length=10, max_burst=9,
    )
    relaid = default_registry().value("ggrs_executor_ring_relaid_bytes")
    direct = default_registry().value("ggrs_executor_ring_inplace_bytes")
    digested = default_registry().value("ggrs_executor_digest_at_write_bytes")
    pool.warmup(np.zeros((2,), np.uint8))
    loads = default_registry().value("ggrs_executor_rollback_loads_total")
    _drive(sessions, schedules, pool.run, ticks)
    loads = default_registry().value("ggrs_executor_rollback_loads_total") - loads
    frames = [s.current_frame for s in sessions]
    live = jax.device_get(pool.live_states)
    one = pool.live_state(1)
    saved = {
        (b, f): (pool.ring_state(b, f), pool.ring_checksum(b, f))
        for b in range(len(sessions))
        for f in range(frames[b] - 8, frames[b])
    }
    ring = jax.device_get(pool._carry["ring"])
    held = {
        k: leaf.format.layout.tiling
        for k, leaf in pool._carry["ring"]["states"].items()
    }
    return {
        "relaid": relaid, "in place": direct, "digested at the write": digested,
        "loads": loads, "frames": frames, "live": live,
        "one": one, "saved": saved, "ring": ring, "held": held,
    }


@pytest.mark.parametrize("seed", [23, 57])
def test_an_executor_above_and_below_the_rule_gives_equal_values(
        seed, monkeypatch):
    """Above the rule the wide leaves are held row-major and saved in place
    (the kernel, under the interpreter); below it the default layout and the
    select: the same live states, ring states and digests."""
    below = _run_particles(1 << 25, monkeypatch, seed)
    above = _run_particles(1, monkeypatch, seed)
    # the rule engaged in one and not in the other
    assert below["relaid"] == below["in place"] == 0
    wide = 6 * 10 * (3 + 4 + 3 + 2 + 1) * 256 * 4
    assert above["relaid"] == wide
    assert above["in place"] == wide  # ttl too, held [B, R, 1, N]
    # the same leaves of one batch's states: digested by the write kernels
    assert above["digested at the write"] == wide // 10
    assert below["digested at the write"] == 0
    assert above["ring"]["states"]["ttl"].shape == (6, 10, 1, 256)
    assert below["ring"]["states"]["ttl"].shape == (6, 10, 256)
    assert above["held"]["rotation"] == ((4, 128),)
    assert above["held"]["ttl"] == ((1, 128),)
    assert above["held"]["velocity"] == ((2, 128),)
    assert above["held"]["emitter"] == below["held"]["emitter"]
    # a rollback-heavy run, and the same one
    assert above["loads"] == below["loads"] > 20
    assert above["frames"] == below["frames"]
    for key in ("live", "one", "saved", "ring"):
        a, b = (jax.tree_util.tree_leaves(r[key]) for r in (above, below))
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            x, y = np.asarray(x), np.asarray(y)
            np.testing.assert_array_equal(x.reshape(y.shape), y, key)
    assert int(np.asarray(above["live"]["ttl"]).sum()) > 0


# ---------------------------------------------------------------------------
# (d) the persistent compilation cache and a result with a layout of its own
# ---------------------------------------------------------------------------

# one child process for both cases: it points JAX's persistent cache at a
# directory of its own and empties the in-memory caches between two uses,
# neither of which a worker of this suite should do to itself
_CACHE_PROBE = r"""
import json, os, sys, tempfile
import numpy as np, jax, jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_platforms", "cpu")
cache = tempfile.mkdtemp()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_compilation_cache_dir", cache)
out = {}

# what jaxlib does: the same jitted function, compiled here and then loaded
want = Layout((1, 2, 0))
made = jax.jit(
    lambda s: jnp.arange(24, dtype=jnp.int32).reshape(2, 3, 4) + s,
    out_shardings=Format(want, SingleDeviceSharding(jax.devices()[0])),
)
out["compiled_here"] = made(np.int32(1)).format.layout.major_to_minor
jax.clear_caches()
again = made(np.int32(1))
out["from_the_cache"] = again.format.layout.major_to_minor
out["values"] = bool(
    (np.asarray(again) == np.arange(24).reshape(2, 3, 4) + 1).all())

# what the executor does about it: two pools above the rule, one cache
from ggrs_tpu.games import ParticleWorld
from ggrs_tpu.parallel import BatchedRequestExecutor, session_pool
from test_session_pool import _drive, _make_matches, _to_arr

session_pool._RELAY_MIN_BYTES = 1
digests = []
for _ in range(2):
    sessions, schedules = _make_matches(2, seed=5)
    game = ParticleWorld(2, 256, 8, 16)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), _to_arr,
        batch_size=4, ring_length=10, max_burst=9,
    )
    pool.warmup(np.zeros((2,), np.uint8))
    _drive(sessions, schedules, pool.run, 12)
    digests.append(hex(pool.ring_checksum(0, sessions[0].current_frame - 1)))
    out["held"] = list(pool._carry["ring"]["states"]["rotation"]
                       .format.layout.tiling[0])
    jax.clear_caches()
out["digests"] = digests
out["cached"] = sorted(name.split("-")[0] for name in os.listdir(cache))
print("PROBE " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def cache_probe():
    import json
    import subprocess

    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO), str(REPO / "tests"), os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True,
        text=True, timeout=600,
    )
    lines = [l for l in done.stdout.splitlines() if l.startswith("PROBE ")]
    assert done.returncode == 0 and lines, done.stderr[-3000:]
    return json.loads(lines[-1][len("PROBE "):])


def test_jaxlib_mislabels_a_cached_executables_result(cache_probe):
    """The reason for ``_compiled_in_process``: a result that has a layout
    of its own keeps it, and says so, when its program was compiled in this
    process; loaded from the persistent cache the values are right and the
    label is the default's.  When a newer jaxlib makes this case FAIL, the
    cache has learned layouts: delete ``_compiled_in_process`` and
    ``_IN_PROCESS_COMPILER_OPTIONS``, and this case with them."""
    assert cache_probe["compiled_here"] == [1, 2, 0]
    assert cache_probe["values"] is True
    assert cache_probe["from_the_cache"] == [0, 1, 2]


def test_a_relaid_pool_never_comes_from_the_persistent_cache(cache_probe):
    assert cache_probe["held"] == [4, 128]
    # the second pool found the first one's cache and still holds its ring so
    assert len(set(cache_probe["digests"])) == 1
    # the two programs that return re-laid leaves were never written to it;
    # what reads the ring (eager slices: results in the default layout) was
    assert "jit_tick" not in cache_probe["cached"]
    assert "jit_fresh" not in cache_probe["cached"]
    assert "jit__fetch" in cache_probe["cached"]


# ---------------------------------------------------------------------------
# (e) the in-place write is the select, byte for byte
# ---------------------------------------------------------------------------

_RING = 10  # slots
_WIDE = 256  # the minor dimension: two tiles of lanes

# frames [B] and predicates [B] of one write; -1 is an idle row's frame
_WRITES = {
    "slots that differ per session": ([13, 4, 27, 9, 0], [1, 1, 1, 1, 1]),
    "pred false in some rows": ([13, 4, 27, 9, 0], [1, 0, 1, 0, 0]),
    "pred false in every row": ([13, 4, 27, 9, 0], [0, 0, 0, 0, 0]),
    "frame -1 beside real ones": ([-1, 4, -1, 9, 19], [1, 1, 0, 1, 1]),
    "one session": ([17], [1]),
    "one session, idle": ([-1], [1]),
}


def _ring_and_state(rng, sessions, rest):
    """A ring of one wide leaf ``[B, R, *rest]`` and one narrow one, every
    word distinct, and a state to save into it."""
    dring = DeviceStateRing(_RING)

    def words(*shape):
        return rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)

    ring = {
        "states": {
            "wide": words(sessions, _RING, *rest),
            "narrow": words(sessions, _RING, 3),
        },
        "checksums": rng.integers(
            0, 2**32 - 1, (sessions, _RING, 4), dtype=np.uint32),
        "frames": words(sessions, _RING),
    }
    state = {"wide": words(sessions, *rest), "narrow": words(sessions, 3)}
    digest = rng.integers(0, 2**32 - 1, (sessions, 4), dtype=np.uint32)
    return dring, ring, state, digest


def _assert_trees_equal(got, want):
    flat_got, tree = jax.tree_util.tree_flatten(jax.device_get(got))
    flat_want, tree_want = jax.tree_util.tree_flatten(jax.device_get(want))
    assert tree == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize(
    "rest", [(4, _WIDE), (3, _WIDE), (2, _WIDE), (1, _WIDE), (_WIDE,)],
    ids=["K=4", "K=3", "K=2", "unit axis", "rank 3"])
@pytest.mark.parametrize("case", sorted(_WRITES))
def test_the_in_place_write_equals_the_select(case, rest):
    frames, preds = (np.asarray(c) for c in _WRITES[case])
    rng = np.random.default_rng(len(case) * 7 + len(rest))
    dring, ring, state, digest = _ring_and_state(rng, len(frames), rest)
    if rest[0] == 1:
        # as the pool holds a leaf [B, R, N]: the slot [1, N], the state [N]
        state["wide"] = state["wide"][:, 0]
    # a leaf held as rank 3 is not the kernel's to take: save_where_batch is
    # then the select, and still the same bytes
    direct = writes_slot_in_place(ring["states"]["wide"].shape, 4)
    assert direct == (len(rest) == 2)
    args = (frames.astype(np.int32), state, digest, preds.astype(bool))
    want = jax.jit(jax.vmap(dring.save_where))(ring, *args)
    got = jax.jit(
        lambda ring, *a: dring.save_where_batch(
            ring, *a, in_place={"wide": direct, "narrow": False},
            interpret=True)
    )(ring, *args)
    _assert_trees_equal(got, want)
    # and it wrote exactly the slots it should have, and nothing else
    before = ring["states"]["wide"]
    after = np.asarray(got["states"]["wide"])
    for b, (f, p) in enumerate(zip(frames.tolist(), preds.tolist())):
        for r in range(_RING):
            hit = bool(p) and f >= 0 and f % _RING == r
            np.testing.assert_array_equal(
                after[b, r],
                state["wide"][b].reshape(rest) if hit else before[b, r])


@pytest.mark.parametrize("rest", [(4, _WIDE), (3, _WIDE), (2, _WIDE)],
                         ids=["K=4", "K=3", "K=2"])
def test_one_slot_written_twice_in_a_tick_holds_the_second_state(rest):
    """The frame-0 tick saves frame 0 before the burst and again in it."""
    rng = np.random.default_rng(41 + rest[0])
    dring, ring, first, digest = _ring_and_state(rng, 4, rest)
    second = jax.tree_util.tree_map(lambda l: l + 1, first)
    frames = np.asarray([0, 20, 7, -1], np.int32)
    twice = np.asarray([True, True, False, True])
    once = np.ones((4,), bool)

    def two_writes(write):
        def run(ring):
            ring = write(ring, frames, first, digest, once)
            return write(ring, frames, second, digest + 1, twice)
        return jax.jit(run)(ring)

    got = two_writes(lambda *a: dring.save_where_batch(
        *a, in_place={"wide": True, "narrow": False}, interpret=True))
    _assert_trees_equal(got, two_writes(jax.vmap(dring.save_where)))
    wide = np.asarray(got["states"]["wide"])
    np.testing.assert_array_equal(wide[0, 0], second["wide"][0])
    np.testing.assert_array_equal(wide[1, 0], second["wide"][1])
    np.testing.assert_array_equal(wide[2, 7], first["wide"][2])
    np.testing.assert_array_equal(wide[3], ring["states"]["wide"][3])


@pytest.mark.parametrize(
    "shape, takes",
    [
        ((512, 10, 4, 10000), True),
        ((128, 10, 2, 10000), True),
        ((512, 10, 1, 10000), True),
        ((512, 10, 10000), False),  # a slot is one row of a tile of slots
        ((512, 10), False),
        ((4, 10, 64, 10000), False),  # 2.56 MB a slot: six would not fit
    ],
)
def test_the_kernel_says_which_leaves_it_takes(shape, takes):
    assert writes_slot_in_place(shape, 4) is takes


# ---------------------------------------------------------------------------
# (f) the digest taken where the slot is written is checksum_device's
# ---------------------------------------------------------------------------

# the wide leaves of a particle state, by what stands before the slot axis
_DIGEST_LEAVES = {"K=4": (4,), "K=3": (3,), "K=2": (2,), "unit axis": ()}
# 10,000 words are 78 tiles of 128 lanes and 16 words of a 79th
_DIGEST_WIDTHS = {"N=10000": 10000, "N=256": 256}
_DIGEST_SESSIONS = 29
_SAVERS = {
    "one saver": [11],
    "every session": list(range(_DIGEST_SESSIONS)),
    "a scattered 7%": [4, 19],
}


@functools.lru_cache(maxsize=None)
def _digest_programs(wide, slots):
    """The two programs of ``_digested_at_the_write`` for one set of wide
    leaves (``wide``: sorted ``(name, shape)`` pairs), compiled once for all
    the predicates they are run with."""
    from ggrs_tpu.ops.checksum import checksum_device

    dring = DeviceStateRing(slots)
    in_place = {"first": False, **{k: True for k, _ in wide}, "zlast": False}

    def at_the_write(ring, frames, state, pred):
        return dring.save_where_batch(
            ring, frames, state, None, pred, in_place, interpret=True)

    def plain(ring, frames, state, pred):
        digests = jax.vmap(checksum_device)(state)
        return jax.vmap(dring.save_where)(
            ring, frames, state, digests, pred), digests

    return jax.jit(at_the_write), jax.jit(plain)


def _digested_at_the_write(rng, wide, savers, slots=3):
    """One write of a state ``{first, *wide, zlast}`` (``wide``: name ->
    shape of one session's leaf; two small leaves the select writes stand
    around them, so no wide leaf starts at word 0 and the salt has seven
    kinds of leaf to mix) into a ring that holds other digests: what
    ``save_where_batch`` leaves when it takes the digest itself, and what the
    select leaves when handed ``jax.vmap(checksum_device)``'s."""
    sessions = _DIGEST_SESSIONS

    def words(*shape):
        return rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)

    shapes = {"first": (2, 2), **wide, "zlast": (3,)}
    state = {k: words(sessions, *shape) for k, shape in shapes.items()}
    plain = {
        "states": {k: words(sessions, slots, *shape)
                   for k, shape in shapes.items()},
        "checksums": rng.integers(
            0, 2**32 - 1, (sessions, slots, 4), dtype=np.uint32),
        "frames": words(sessions, slots),
    }
    # as the pool holds it: a leaf of one state dimension with a unit axis
    held = {**plain, "states": {
        k: v[:, :, None] if len(shapes[k]) == 1 and k in wide else v
        for k, v in plain["states"].items()}}
    for k in wide:
        assert writes_slot_in_place(held["states"][k].shape, 4)
    frames = ((7 * np.arange(sessions) + 3) % 23).astype(np.int32)
    frames[19] = -1  # an idle row: saves nothing whatever its predicate says
    pred = np.isin(np.arange(sessions), savers)
    at_the_write, by_select = _digest_programs(
        tuple(sorted(wide.items())), slots)
    got = at_the_write(held, frames, state, pred)
    want, digests = by_select(plain, frames, state, pred)
    return got, want, plain, np.asarray(digests), frames, pred


@pytest.mark.parametrize("savers", sorted(_SAVERS))
@pytest.mark.parametrize("width", sorted(_DIGEST_WIDTHS))
@pytest.mark.parametrize("leaf", sorted(_DIGEST_LEAVES))
def test_the_digest_at_the_write_is_checksum_device_s(leaf, width, savers):
    rng = np.random.default_rng(
        sum(map(ord, leaf + width + savers)))
    shape = _DIGEST_LEAVES[leaf] + (_DIGEST_WIDTHS[width],)
    got, want, before, digests, frames, pred = _digested_at_the_write(
        rng, {"wide": shape}, _SAVERS[savers])
    np.testing.assert_array_equal(
        np.asarray(got["checksums"]), np.asarray(want["checksums"]))
    # row by row: a saver's slot holds its state's digest, every other row
    # of every session the digest the ring held
    kept = np.asarray(got["checksums"])
    wrote = 0
    for b in range(_DIGEST_SESSIONS):
        for r in range(kept.shape[1]):
            hit = bool(pred[b]) and frames[b] >= 0 and frames[b] % 3 == r
            wrote += hit
            np.testing.assert_array_equal(
                kept[b, r], digests[b] if hit else before["checksums"][b, r])
    assert wrote == len(set(_SAVERS[savers]) - {19}) > 0
    for k, leaf_want in want["states"].items():
        np.testing.assert_array_equal(
            np.asarray(got["states"][k]).reshape(leaf_want.shape),
            np.asarray(leaf_want))


@pytest.mark.parametrize("width", sorted(_DIGEST_WIDTHS))
def test_the_partial_sums_of_five_kernels_and_two_small_leaves_add_up(width):
    """A whole particle state: five leaves digested by the kernels that write
    them, each from its own word offset, two by ``lane_sums`` in XLA."""
    n = _DIGEST_WIDTHS[width]
    wide = {"rotation": (4, n), "scale": (3, n), "translation": (3, n),
            "ttl": (n,), "velocity": (2, n)}
    got, want, _before, _digests, _frames, _pred = _digested_at_the_write(
        np.random.default_rng(n), wide, _SAVERS["a scattered 7%"] + [0, 28])
    np.testing.assert_array_equal(
        np.asarray(got["checksums"]), np.asarray(want["checksums"]))
    assert (np.asarray(got["checksums"]) != 0).any()


def test_one_slot_digested_twice_in_a_tick_holds_the_second_digest():
    from ggrs_tpu.ops.checksum import checksum_device

    rng = np.random.default_rng(77)
    dring, ring, first, _ = _ring_and_state(rng, 4, (3, _WIDE))
    second = jax.tree_util.tree_map(lambda l: l ^ 5, first)
    frames = np.asarray([0, 20, 7, -1], np.int32)
    twice = np.asarray([True, True, False, True])
    once = np.ones((4,), bool)
    in_place = {"wide": True, "narrow": False}

    def run(ring):
        ring = dring.save_where_batch(
            ring, frames, first, None, once, in_place, True)
        return dring.save_where_batch(
            ring, frames, second, None, twice, in_place, True)

    kept = np.asarray(jax.jit(run)(ring)["checksums"])
    of_first, of_second = (
        np.asarray(jax.vmap(checksum_device)(s)) for s in (first, second))
    np.testing.assert_array_equal(kept[0, 0], of_second[0])
    np.testing.assert_array_equal(kept[1, 0], of_second[1])
    np.testing.assert_array_equal(kept[2, 7], of_first[2])
    np.testing.assert_array_equal(kept[3], ring["checksums"][3])
    assert (of_first != of_second).all()


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, those inside the branches
    of its conditionals and the bodies of its loops too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_pallas_calls(inner))
    return found


@pytest.mark.parametrize("with_checksums", [True, False])
def test_a_pool_that_keeps_no_digests_runs_none_in_the_kernel(
        with_checksums, monkeypatch):
    monkeypatch.setattr(session_pool, "_RELAY_MIN_BYTES", 1)
    sessions, schedules = _make_matches(2, seed=9)
    game = ParticleWorld(2, 256, 8, 16)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), _to_arr, batch_size=len(sessions),
        ring_length=10, max_burst=9, with_checksums=with_checksums,
    )
    value = default_registry().value
    state_wide = len(sessions) * (3 + 4 + 3 + 2 + 1) * 256 * 4
    assert value("ggrs_executor_ring_inplace_bytes") == 10 * state_wide
    assert value("ggrs_executor_digest_at_write_bytes") == (
        state_wide if with_checksums else 0)
    pool.warmup(np.zeros((2,), np.uint8))
    desc = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pool._blank_desc())
    kernels = _pallas_calls(
        jax.make_jaxpr(pool._tick.__wrapped__)(pool._carry, desc).jaxpr)
    # five wide leaves at each of the three writes; the second result is
    # the savers' lane sums
    assert len(kernels) == 15
    assert {len(k.outvars) for k in kernels} == {2 if with_checksums else 1}
    _drive(sessions, schedules, pool.run, 14)
    digests = np.asarray(pool._carry["ring"]["checksums"])
    assert digests.any() == with_checksums
    if with_checksums:
        from ggrs_tpu.ops.checksum import checksum_device, checksum_to_u128

        frame = sessions[0].current_frame - 1
        assert pool.ring_checksum(0, frame) == checksum_to_u128(
            checksum_device(pool.ring_state(0, frame)))


def test_with_no_leaf_in_place_the_digest_at_the_write_is_the_plain_one():
    got, want, *_ = _digested_at_the_write(
        np.random.default_rng(3), {}, _SAVERS["a scattered 7%"])
    _assert_trees_equal(got, want)
