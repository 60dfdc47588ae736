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
