"""ParticleWorld (bevy_ggrs's particle stress test as a slot table): parity
with the NumPy oracle, the table's steady state, the emitter that keeps an
input, and a rollback through the pooled executor."""

import numpy as np

import jax

from ggrs_tpu.core.sync_layer import GameStateCell
from ggrs_tpu.core.types import (
    AdvanceFrame,
    InputStatus,
    LoadGameState,
    SaveGameState,
)
from ggrs_tpu.games import ParticleWorld
from ggrs_tpu.ops.checksum import pytree_checksum
from ggrs_tpu.parallel import BatchedRequestExecutor

PLAYERS, CAPACITY, RATE, TTL = 2, 200, 2, 50


def _world():
    return ParticleWorld(PLAYERS, CAPACITY, RATE, TTL)


def _masks(n, seed):
    return np.random.default_rng(seed).integers(0, 16, (n, PLAYERS)).astype(np.uint8)


def _replay_np(world, masks):
    state = world.init_state_np()
    for row in masks:
        state = world.advance_np(state, row)
    return state


def _differ(a, b):
    """Two states (host or device arrays) differ in any word."""
    return any(not np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


class TestParticleWorld:
    def test_jax_matches_numpy_oracle_bit_for_bit(self):
        world = _world()
        s_j, s_n = world.init_state(), world.init_state_np()
        adv = jax.jit(world.advance)
        for row in _masks(120, seed=2):
            s_j = adv(s_j, row)
            s_n = world.advance_np(s_n, row)
            assert set(s_j) == set(s_n)
            for k, want in s_n.items():
                got = np.asarray(s_j[k])
                assert got.dtype == want.dtype == np.int32, k
                np.testing.assert_array_equal(got, want, err_msg=k)

    def test_table_fills_by_frame_ttl_and_then_recycles(self):
        world = _world()
        state = world.init_state_np()
        for f, row in enumerate(_masks(3 * TTL, seed=3), start=1):
            before = state["ttl"].copy()
            state = world.advance_np(state, row)
            live = int((state["ttl"] > 0).sum())
            assert live == min(f, TTL) * PLAYERS * RATE
            if f > TTL:
                # the table stays full: what expired was born again at once
                assert live == CAPACITY
                assert int((before == 1).sum()) == PLAYERS * RATE
                assert int((state["ttl"] == TTL).sum()) == PLAYERS * RATE
        assert int(state["resources"][2]) == 3 * TTL

    def test_one_altered_mask_still_shows_200_frames_later(self):
        world = _world()
        masks = _masks(260, seed=4)
        altered = masks.copy()
        altered[40, 1] ^= 8  # player 1 presses right in one frame only
        a, b = _replay_np(world, masks), _replay_np(world, altered)
        # every particle born under the altered mask died 150 frames ago;
        # the emitter kept the displacement and so does everything born since
        assert not np.array_equal(a["emitter"], b["emitter"])
        assert not np.array_equal(a["translation"], b["translation"])
        np.testing.assert_array_equal(a["ttl"], b["ttl"])
        np.testing.assert_array_equal(a["resources"], b["resources"])

    def test_a_rollback_through_the_pooled_executor_resimulates_to_the_same_bits(self):
        world = _world()
        pool = BatchedRequestExecutor(
            world.advance, world.init_state(),
            lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
            batch_size=2, ring_length=10, max_burst=9,
        )
        pool.warmup(np.zeros((PLAYERS,), np.uint8))
        masks = _masks(70, seed=5)
        cells = [[GameStateCell() for _ in range(10)] for _ in range(2)]

        def adv(row):
            return AdvanceFrame([(int(v), InputStatus.CONFIRMED) for v in row])

        # both sessions save and advance 60 frames, session 1 the last 6 on
        # a mispredicted mask; it then rolls back 6 frames and resimulates
        # them on the true ones while session 0 idles
        wrong = masks.copy()
        wrong[54:60, 0] ^= 8
        for f in range(60):
            pool.run([[SaveGameState(cells[b][f % 10], f),
                       adv((masks, wrong)[b][f])] for b in range(2)])
        assert _differ(pool.live_state(0), pool.live_state(1))
        resim = [LoadGameState(cells[1][54 % 10], 54)]
        for f in range(54, 60):
            resim += [adv(masks[f]), SaveGameState(cells[1][(f + 1) % 10], f + 1)]
        pool.run([[], resim])
        want = _replay_np(world, masks[:60])
        for b in range(2):
            got = pool.live_state(b)
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]), want[k], err_msg=k)
        # the slot saved in the resimulation holds the state and its digest
        assert not _differ(pool.ring_state(1, 60), want)
        assert pool.ring_checksum(1, 60) == pytree_checksum(want)
        # and one more frame from the loaded state differs from no rollback
        # only if the resimulation did: both sessions go on alike
        pool.run([[adv(masks[60])], [adv(masks[60])]])
        assert not _differ(pool.live_state(0), pool.live_state(1))


# ---- sparse saving through the served path ---------------------------------

SPARSE_MATCHES, DELAY, WINDOW = 4, 2, 8
# masks change until STEADY, then every player holds its last: frames past
# it take that mask whatever the tick that pressed it, so the reference
# needs no account of the frames a stalled session dropped
STEADY, BLACKOUT, SPARSE_TICKS = 40, range(50, 62), 100


def _held_rows(seed):
    """``[tick, match, player]`` masks, each held for 4-11 ticks."""
    rng = np.random.default_rng(seed)
    rows = np.zeros((SPARSE_TICKS, SPARSE_MATCHES, PLAYERS), np.uint8)
    for m in range(SPARSE_MATCHES):
        for p in range(PLAYERS):
            t = 0
            while t < STEADY:
                hold = int(rng.integers(4, 12))
                rows[t : t + hold, m, p] = rng.integers(0, 16)
                t += hold
    rows[STEADY:] = rows[STEADY - 1]
    return rows


def _frame_input(rows, f):
    """What frame ``f`` is simulated with: the row pressed ``DELAY`` ticks
    earlier, the blank input before any could arrive."""
    if f < DELAY:
        return np.zeros(rows.shape[1:], np.uint8)
    return rows[min(f - DELAY, STEADY)]


def _serve_sparse(rows):
    """``SPARSE_MATCHES`` particle matches saving sparsely, both peers of a
    match in one ``HostedPool`` over one link of 3 ticks that goes dark for
    ``BLACKOUT`` (the sessions stall at the prediction limit, and the
    resimulation check_last_saved_state forces saves right after its load).
    Returns the host pool, the executor and the plans the executor ran."""
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.games import boxgame_config
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.obs import Registry
    from ggrs_tpu.parallel import HostedPool, HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder
    import random

    world = _world()
    clock = [0]
    net = InMemoryNetwork(seed=5, latency_ticks=3)
    host = HostSessionPool(metrics=Registry())
    for m in range(SPARSE_MATCHES):
        for k in range(PLAYERS):
            b = (
                SessionBuilder(boxgame_config())
                .with_num_players(PLAYERS)
                .with_clock(lambda: clock[0])
                .with_rng(random.Random(7 * m + k))
                .with_max_prediction_window(WINDOW)
                .with_input_delay(DELAY)
                .with_sparse_saving_mode(True)
            )
            for j in range(PLAYERS):
                b = b.add_player(Local() if j == k else Remote(f"m{m}p{j}"), j)
            host.add_session(b, net.socket(f"m{m}p{k}"))
    executor = BatchedRequestExecutor(
        world.advance, world.init_state(),
        lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
        batch_size=SPARSE_MATCHES * PLAYERS, ring_length=WINDOW + 2,
        max_burst=WINDOW + 1,
        raw_inputs_to_array=lambda blobs, statuses: blobs[:, :, 0],
    )
    executor.warmup(np.zeros((PLAYERS,), np.uint8))
    hosted = HostedPool(host, executor)
    plans = []
    run = executor.run

    def recording(plan):
        if getattr(plan, "quiet_rows", None) is not None:
            plans.append((plan.tick_no, len(plan.eager_rows),
                          int(plan.adv_only_rows.size),
                          list(plan.sparse_resim_rows)))
        run(plan)

    executor.run = recording
    for t in range(len(rows)):
        clock[0] = (t * 1000) // 60
        net.loss = 1.0 if t in BLACKOUT else 0.0
        hosted.tick([(m * PLAYERS + k, k, int(rows[t, m, k]))
                     for m in range(SPARSE_MATCHES) for k in range(PLAYERS)])
        net.tick()
    hosted.block_until_ready()
    return host, executor, plans


class TestSparseSavingPool:
    def test_sparse_sessions_on_the_bank_match_the_oracle_and_the_python_sessions(
            self, monkeypatch):
        """Bank -> plan -> executor for sessions that save sparsely: every
        live state and every ring slot equals the NumPy oracle's replay, and
        the same pool run off the bank (its Python sessions, the policy's
        reference) holds the same frames in the same slots.  The plans take
        the sparse row kinds, none goes to the eager decoder once the
        sessions run, and a save right after a load occurs."""
        from ggrs_tpu.parallel import host_bank

        world = _world()
        rows = np.concatenate(
            [_held_rows(6), np.repeat(_held_rows(6)[-1:], 3 * WINDOW, 0)])
        host, on_bank, plans = _serve_sparse(rows)
        assert host.native_active
        assert all(host.slot_state(s) == "native" for s in range(len(host)))
        running = [p for p in plans if p[0] > 8]
        assert running and all(eager == 0 for _, eager, _, _ in running)
        assert sum(adv for _, _, adv, _ in running) > 0
        sparse = [row for *_, resims in running for row in resims]
        assert sparse
        assert any(save == load for _, load, *_, save in sparse)  # post-load

        monkeypatch.setattr(host_bank, "_bank_eligible",
                            lambda builder, hub_active=False: False)
        off, off_bank, _ = _serve_sparse(rows)
        assert not off.native_active

        frames = on_bank.ring_frames()
        np.testing.assert_array_equal(frames, off_bank.ring_frames())
        last = max(host.current_frame(s) for s in range(len(host)))
        ref = [world.init_state_np()]
        for f in range(last):
            ref.append(world.advance_np(ref[-1], _frame_input(rows, f)[0]))
        for s in range(len(host)):
            m, f = s // PLAYERS, host.current_frame(s)
            assert f == off.current_frame(s)
            want = _replay_np(world, [_frame_input(rows, g)[m] for g in range(f)])
            for ex in (on_bank, off_bank):
                assert not _differ(ex.live_state(s), want), s
            held = sorted(int(g) for g in frames[s] if g >= 0)
            # a sparse ring: confirmed frames further apart than a window
            assert held and held[-1] - held[0] > WINDOW + 1, held
            for g in held:
                want = _replay_np(world, [_frame_input(rows, h)[m]
                                          for h in range(g)])
                for ex in (on_bank, off_bank):
                    assert not _differ(ex.ring_state(s, g), want), (s, g)
                    assert ex.ring_checksum(s, g) == pytree_checksum(want)

    def test_ring_frames_are_the_carrys_frame_tags(self):
        world = _world()
        pool = BatchedRequestExecutor(
            world.advance, world.init_state(),
            lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
            batch_size=2, ring_length=4, max_burst=3,
        )
        pool.warmup(np.zeros((PLAYERS,), np.uint8))
        cells = [GameStateCell() for _ in range(4)]
        row = AdvanceFrame([(0, InputStatus.CONFIRMED)] * PLAYERS)
        for f in range(6):
            pool.run([[SaveGameState(cells[f % 4], f), row], [row]])
        frames = pool.ring_frames()
        np.testing.assert_array_equal(
            frames, np.asarray(jax.device_get(pool._carry["ring"]["frames"])))
        assert sorted(frames[0].tolist()) == [2, 3, 4, 5]
        assert (frames[1] < 0).all()
