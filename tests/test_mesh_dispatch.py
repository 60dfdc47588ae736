"""One pool over a mesh of four devices (PR 33; DESIGN.md §3, "How a
dispatch reaches four devices").

On the virtual CPU mesh of ``tests/conftest.py``, never a chip: (a) a
particle pool whose session axis is sharded over four devices against the
same pool on one device and against the plain reference, bit for bit, with
the re-lay rule in force on a shard's ring leaves and shards that roll back
to different depths in one dispatch; (b) what ``device.launch`` says of a
dispatch (``shards``, ``transfers``, ``dispatches``) on one device and on
four, against what the runtime really sends; (c) ``scripts/profile_tick.py``'s
tables, a column a device, on a hand-made profile of two devices.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from ggrs_tpu.games import ParticleWorld
from ggrs_tpu.obs import default_tracer
from ggrs_tpu.obs.registry import default_registry
from ggrs_tpu.ops.checksum import checksum_to_u128
from ggrs_tpu.parallel import BatchedRequestExecutor, make_mesh, session_pool

REPO = Path(__file__).resolve().parents[1]
for extra in (REPO, REPO / "scripts"):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))

from benchmark.reference import particles as reference  # noqa: E402
from test_session_pool import _NumpySession, _plan, _to_arr  # noqa: E402

SHARDS = 4
SESSIONS = 8  # two a shard: the peers of one match
RING, BURST = 10, 9  # boxgame-2p's and particles-2p's ring and burst
SMALL = {"players": 2, "capacity": 256, "rate": 8, "ttl_frames": 16}
# the deepest plan a shard's sessions may draw at a tick: shard 0 never
# rolls back, shard 3 rolls back up to the whole window
DEEPEST = (1, 2, 5, BURST)
TICKS = 40


@pytest.fixture
def mesh():
    if len(jax.devices()) < SHARDS:
        pytest.skip("needs the virtual CPU mesh of tests/conftest.py")
    return make_mesh(SHARDS)


class _PlainReference:
    """``benchmark/reference/particles.py`` (NumPy, imports nothing of the
    program) behind the two calls ``_NumpySession`` makes of a game."""

    @staticmethod
    def init_state_np():
        return {k: v[0] for k, v in reference.init_state(SMALL, 1).items()}

    @staticmethod
    def advance_np(state, inputs):
        batched = {k: v[None] for k, v in state.items()}
        out = reference.advance(SMALL, batched, np.asarray(inputs)[None])
        return {k: v[0] for k, v in out.items()}


def _pool(mesh=None):
    game = ParticleWorld(2, SMALL["capacity"], SMALL["rate"], SMALL["ttl_frames"])
    where = {} if mesh is None else {"mesh": mesh}
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), _to_arr,
        batch_size=SESSIONS, ring_length=RING, max_burst=BURST, **where,
    )
    pool.warmup(np.zeros((2,), np.uint8))
    return pool


def _traffic(pool, seed):
    """``TICKS`` descriptors drawn from ``seed``: each session, at its own
    frame, saves only, advances quietly or rolls back, as deep as its shard
    allows (``DEEPEST``) and its frame has history for."""
    rng = np.random.default_rng(seed)
    frames = [0] * SESSIONS
    descs, depths = [], []
    for _ in range(TICKS):
        desc, row = pool._blank_desc(), []
        for b in range(SESSIONS):
            deepest = min(DEEPEST[b * SHARDS // SESSIONS], frames[b] + 1)
            depth = int(rng.integers(0, deepest + 1))
            _plan(desc, b, rng, frames[b], depth)
            frames[b] += depth >= 1
            row.append(depth)
        descs.append(desc)
        depths.append(row)
    return descs, depths


@pytest.mark.parametrize("rule", ["above", "below"])
def test_a_pool_over_four_devices_equals_one_device_and_the_reference(
        rule, mesh, monkeypatch):
    """Above the rule a shard's wide ring leaves are held row-major and saved
    in place (``ops/ring.py`` ``write_slot``, each shard its own kernel under
    ``shard_map``, which also takes the digest of what it writes); below it
    they keep the default layout, the select and ``jax.vmap(checksum_device)``.
    Either way: the reference's bytes and the reference's digests, on four
    devices as on one."""
    per_device = default_registry().value  # the gauges: the newest executor's
    wide = RING * (3 + 4 + 3 + 2 + 1) * SMALL["capacity"] * 4
    if rule == "above":
        # the threshold lowered, as tests/test_ring_layout.py does: the rule
        # is in force on a SHARD's leaf ([2, 10, ..., 256]), not on the
        # whole one's
        monkeypatch.setattr(session_pool, "_RELAY_MIN_BYTES", 1)
    else:
        wide = 0
    one = _pool()
    assert per_device("ggrs_executor_ring_relaid_bytes") == SESSIONS * wide
    assert per_device("ggrs_executor_ring_inplace_bytes") == SESSIONS * wide
    # (one batch's states of those leaves: digested by the write kernels)
    assert per_device("ggrs_executor_digest_at_write_bytes") == (
        SESSIONS * wide // RING)
    across = _pool(mesh)
    assert per_device("ggrs_executor_ring_relaid_bytes") == 2 * wide
    assert per_device("ggrs_executor_ring_inplace_bytes") == 2 * wide
    assert per_device("ggrs_executor_digest_at_write_bytes") == 2 * wide // RING
    assert per_device("ggrs_executor_mesh_devices") == SHARDS
    for leaf in ("rotation", "ttl", "velocity"):
        held = across._carry["ring"]["states"][leaf]
        assert held.format.layout == one._carry["ring"]["states"][leaf].format.layout
        # (the CPU's default is row-major too: below the rule this says
        # nothing, above it the carry was made as the Format asked)
        assert held.format.layout.major_to_minor == tuple(range(held.ndim))
        assert len(held.sharding.device_set) == SHARDS
        assert {s.data.shape[0] for s in held.addressable_shards} == {2}

    descs, depths = _traffic(one, seed=33)
    deepest = np.asarray(depths).reshape(TICKS, SHARDS, -1).max(axis=2)
    # shards of different depths in ONE dispatch, the deepest anywhere
    assert (deepest[:, 0] <= 1).all() and deepest[:, 3].max() == BURST
    assert sum(len(set(row)) > 2 for row in deepest.tolist()) > TICKS // 2
    model = [_NumpySession(_PlainReference) for _ in range(SESSIONS)]
    for desc in descs:
        for b, session in enumerate(model):
            session.tick(desc, b)
        for pool in (across, one):
            # a fresh copy each: the call may alias a host array
            pool._launch(jax.tree_util.tree_map(np.copy, desc))

    got, want = jax.device_get(across._carry), jax.device_get(one._carry)
    flat_got, tree = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree == tree_want and len(flat_got) == 16
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    ring = got["ring"]
    for b, session in enumerate(model):
        for k, ref in session.live.items():
            np.testing.assert_array_equal(got["live"][k][b], ref, f"live {b} {k}")
        assert ring["frames"][b].tolist() == session.frames, b
        for s in range(RING):
            for k, ref in session.slots[s].items():
                np.testing.assert_array_equal(
                    # (ttl's slot is held [1, N] above the rule)
                    ring["states"][k][b, s].reshape(ref.shape), ref,
                    f"session {b} slot {s} {k}")
            assert checksum_to_u128(ring["checksums"][b, s]) == session.digests[s]
    # and through the accessors the benchmark's comparison reads
    live = jax.device_get(across.live_states)
    for b, session in enumerate(model):
        newest = max(session.frames)
        for pool in (across, one):
            saved = pool.ring_state(b, newest)
            for k, ref in session.slots[newest % RING].items():
                np.testing.assert_array_equal(saved[k], ref)
                np.testing.assert_array_equal(pool.live_state(b)[k], session.live[k])
                np.testing.assert_array_equal(live[k][b], session.live[k])
            assert pool.ring_checksum(b, newest) == session.digests[newest % RING]
    assert int(live["ttl"].sum()) > 0  # particles were born


# ---------------------------------------------------------------------------
# what device.launch says of a dispatch
# ---------------------------------------------------------------------------


@pytest.fixture
def ring():
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    yield tracer
    tracer.switch(False)
    tracer.clear()


def _sent_by_the_runtime(monkeypatch):
    """Counts the per-device buffers JAX makes of host arrays at a call
    (``pxla.batched_device_put``: one array's shards and their devices)."""
    from jax._src.interpreters import pxla

    put = getattr(pxla, "batched_device_put", None)
    if put is None:
        pytest.skip("this jax shards host arguments elsewhere")
    sent = []

    def counting(aval, sharding, shards, devices, *args, **kwargs):
        sent.append(len(shards))
        return put(aval, sharding, shards, devices, *args, **kwargs)

    monkeypatch.setattr(pxla, "batched_device_put", counting)
    return sent


@pytest.mark.parametrize("shards", [1, SHARDS])
def test_the_launch_span_counts_what_the_dispatch_sends(shards, ring, monkeypatch):
    if len(jax.devices()) < shards:
        pytest.skip("needs the virtual CPU mesh of tests/conftest.py")
    pool = _pool(make_mesh(shards) if shards > 1 else None)
    assert default_registry().value("ggrs_executor_mesh_devices") == shards
    descs, _ = _traffic(pool, seed=5)
    sent = _sent_by_the_runtime(monkeypatch)
    ring.switch(True)
    for desc in descs[:6]:
        pool._launch(desc)
    ring.switch(False)
    launches = [e[6] for e in ring.events() if e[1] == "device.launch"]
    assert len(launches) == 6
    # ten fields, one buffer
    assert len(descs[0]) == 10 and len(jax.tree_util.tree_leaves(descs[0])) == 1
    for args in launches:
        assert (args["shards"], args["dispatches"]) == (shards, 1)
        assert args["transfers"] == shards
    # over a mesh the descriptor went out as one block a device, and
    # nothing else did (one device: the call's C++ path sends the buffer
    # whole, where no Python can count)
    assert sent == ([shards] * 6 if shards > 1 else [])
    # asleep, the tracer records nothing and the dispatch is the same
    pool._launch(descs[6])
    assert len([e for e in ring.events() if e[1] == "device.launch"]) == 6


# ---------------------------------------------------------------------------
# scripts/profile_tick.py: a column a device
# ---------------------------------------------------------------------------


def _ev(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats.items()))


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=n, events=events) for n, events in lines])


def _device(n, start, program):
    """Two ticks of a device whose program starts ``start`` ns into each
    5 us tick: a loop (its body's fusion nested in it) and a copy."""
    modules, ops = [], []
    for t in (0, 5000):
        modules.append(_ev("jit_tick(7)", t + start, program))
        ops += [_ev("%while.3 = (s32[]) while(...)", t + start, program - 500),
                _ev("%fusion.1 = s32[4] fusion(...)", t + start + 100, 1000),
                _ev("%copy.2 = s32[4] copy(...)", t + start + program - 500, 500)]
    return _plane(f"/device:TPU:{n}", [("XLA Modules", modules), ("XLA Ops", ops),
                                       ("Steps", [_ev("step", 0, 1)])])


def test_the_profile_tables_hold_a_column_a_device(capsys):
    import profile_tick

    host = _plane("/host:CPU", [("python", [
        _ev("ggrs.hosted.tick", 0, 900, tick=1, perf_ns=5),
        _ev("ggrs.device.launch", 100, 200, tick=1),
        _ev("ggrs.device.fence", 1000, 2500, tick=1),
        _ev("ggrs.device.launch", 5100, 200, tick=2),
        _ev("ggrs.device.fence", 6000, 2500, tick=2),
        _ev("bench.bank", 0, 50)])])
    found = profile_tick.read_profile(
        [host, _device(1, 600, 2200), _device(0, 400, 2000)])
    spans, modules, ops, op_events = found
    assert sorted(spans) == ["device.fence", "device.launch", "hosted.tick"]
    assert modules["/device:TPU:0"] == [(400, 2000), (5400, 2000)]
    assert ops["/device:TPU:1"]["while.3"] == pytest.approx(3.4e-6)
    assert len(op_events["/device:TPU:0"]) == 6
    scopes = {"while.3": "ring.save", "fusion.1": "ring.save > digest", "copy.2": "-"}
    profile_tick.print_profile(*found, scopes)
    out = capsys.readouterr().out
    assert "on 2 device(s)" in out and "device:TPU:0, device:TPU:1" in out
    def row(name):
        line, = [l for l in out.splitlines() if l.startswith(f"    {name}  ")]
        return line[len(name) + 4:].split()

    # launch at 100 ns, programs at 400 and 600: 0.3 and 0.5 us later
    assert row("launch start -> program start")[0::4] == ["0.3", "0.5"]
    assert row("program (jit_tick)")[0::4] == ["2.0", "2.2"]
    assert row("%while.3")[0] == "ring.save"
    # own time: the loop's less its body's fusion, a device's share each
    assert row("ring.save > digest")[2::3] == ["50.0%", "45.5%"]
    assert row("ring.save")[2::3] == ["25.0%", "31.8%"]
    # one device prints one column, and a profile without a device plane says so
    profile_tick.print_profile(*profile_tick.read_profile(
        [host, _device(0, 400, 2000)]), scopes)
    assert "on 1 device(s)" in capsys.readouterr().out
    profile_tick.print_profile(*profile_tick.read_profile([host]), scopes)
    assert "no device plane" in capsys.readouterr().out
