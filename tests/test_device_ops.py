"""Unit tests for the device primitives: checksum, state ring, fused replay."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ggrs_tpu.ops import (
    CHECKSUM_LANES,
    DeviceStateRing,
    build_replay_programs,
    checksum_device,
    checksum_to_u128,
    pytree_checksum,
)


class TestChecksum:
    def test_shape_and_dtype(self):
        cs = checksum_device({"a": jnp.arange(7), "b": jnp.ones((2, 3))})
        assert cs.shape == (CHECKSUM_LANES,)
        assert cs.dtype == jnp.uint32

    def test_deterministic(self):
        state = {"x": jnp.arange(100, dtype=jnp.int32), "y": jnp.float32(3.5)}
        assert pytree_checksum(state) == pytree_checksum(state)

    def test_empty_pytree(self):
        # regression (ADVICE r5): _INIT_LANES holds ints above int32 max and
        # jnp.asarray's int32 default raised OverflowError on the leafless path
        cs = checksum_device({})
        assert cs.shape == (CHECKSUM_LANES,)
        assert cs.dtype == jnp.uint32
        assert pytree_checksum({}) == pytree_checksum({})
        assert pytree_checksum({}) != pytree_checksum({"a": jnp.arange(2)})

    def test_sensitive_to_values(self):
        a = jnp.arange(16, dtype=jnp.int32)
        assert pytree_checksum(a) != pytree_checksum(a.at[3].add(1))

    def test_sensitive_to_position(self):
        # same multiset of words, different order
        a = jnp.asarray([1, 2, 3, 4], jnp.uint32)
        b = jnp.asarray([4, 3, 2, 1], jnp.uint32)
        assert pytree_checksum(a) != pytree_checksum(b)

    def test_float_bitcast_not_rounded(self):
        # two floats equal under fp-tolerance but not bitwise must differ
        a = jnp.float32(1.0)
        b = jnp.float32(1.0 + 1.2e-7)
        assert pytree_checksum(a) != pytree_checksum(b)

    @pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int16, jnp.int32, jnp.float32])
    def test_small_dtypes_supported(self, dtype):
        x = jnp.arange(5).astype(dtype)
        assert isinstance(pytree_checksum(x), int)

    def test_u128_composition(self):
        lanes = np.asarray([1, 2, 3, 4], np.uint32)
        v = checksum_to_u128(lanes)
        assert v == 1 | (2 << 32) | (3 << 64) | (4 << 96)
        assert 0 <= v < (1 << 128)

    def test_jittable_inside_scan(self):
        def body(c, _):
            return c + 1, checksum_device({"s": c})

        _, css = jax.lax.scan(body, jnp.int32(0), None, length=4)
        assert css.shape == (4, CHECKSUM_LANES)
        # different states digest differently
        assert not np.array_equal(np.asarray(css[0]), np.asarray(css[1]))


class TestDeviceStateRing:
    def _mk(self, length=4):
        ring = DeviceStateRing(length)
        template = {"a": jnp.zeros((3,), jnp.int32), "b": jnp.zeros((), jnp.float32)}
        return ring, ring.init(template)

    def test_init_frames_null(self):
        ring, buf = self._mk()
        assert np.all(np.asarray(buf["frames"]) == -1)

    def test_save_load_roundtrip(self):
        ring, buf = self._mk()
        state = {"a": jnp.asarray([1, 2, 3], jnp.int32), "b": jnp.float32(7.5)}
        cs = checksum_device(state)
        buf = ring.save(buf, jnp.int32(5), state, cs)
        got = ring.load(buf, jnp.int32(5))
        assert np.array_equal(np.asarray(got["a"]), [1, 2, 3])
        assert float(got["b"]) == 7.5
        assert int(ring.frame_at(buf, jnp.int32(5))) == 5
        assert np.array_equal(
            np.asarray(ring.load_checksum(buf, jnp.int32(5))), np.asarray(cs)
        )

    def test_ring_wraparound_overwrites(self):
        ring, buf = self._mk(length=4)
        s = lambda v: {"a": jnp.full((3,), v, jnp.int32), "b": jnp.float32(v)}
        for f in range(6):  # frames 4,5 overwrite slots 0,1
            buf = ring.save(buf, jnp.int32(f), s(f), checksum_device(s(f)))
        assert int(ring.frame_at(buf, jnp.int32(4))) == 4
        got = ring.load(buf, jnp.int32(4))
        assert np.all(np.asarray(got["a"]) == 4)
        # frame 0's slot now holds frame 4 — frame_at exposes the overwrite
        assert int(ring.frame_at(buf, jnp.int32(0))) == 4


# -- save_where: the served pool's predicated write -------------------------
#
# Each scenario is steps of (frames, preds), one entry a session.  The plain
# semantics it is held to: fold ``save`` over the steps where ``pred`` holds,
# the identity where not.

_SW_R = 4  # ring length of the save_where cases
_SW_B = 4  # sessions in the batched mode


def _sw_template():
    """Leaves of rank 0-3 in the four dtypes the games use."""
    return {
        "s": jnp.zeros((), jnp.float32),
        "v": jnp.zeros((5,), jnp.uint8),
        "m": jnp.zeros((2, 3), jnp.int32),
        "t": jnp.zeros((2, 2, 3), jnp.uint32),
    }


def _sw_value(rng):
    """One session's state and checksum row, drawn bit-wise at random."""
    state = {
        "s": np.float32(rng.normal()),
        "v": rng.integers(0, 256, (5,), dtype=np.uint8),
        "m": rng.integers(-(2**31), 2**31, (2, 3)).astype(np.int32),
        "t": rng.integers(0, 2**32, (2, 2, 3), dtype=np.uint64).astype(np.uint32),
    }
    cs = rng.integers(0, 2**32, (4,), dtype=np.uint64).astype(np.uint32)
    return state, cs


_SW_SCENARIOS = {
    # every session walks past the ring's end twice, each from its own frame
    "wraparound": [
        ([f + 3 * b for b in range(_SW_B)], [True] * _SW_B)
        for f in range(2 * _SW_R + 3)
    ],
    # a populated ring, then saves that are all refused: the reference folds
    # nothing over them, so every buffer has to come back bit-identical
    "all_false": [([b, b + 1, b + 2, b + 3], [True] * _SW_B) for b in range(_SW_R)]
    + [([7 + b, 2, 13, 5], [False] * _SW_B) for b in range(5)],
    # what an idle descriptor row carries: frame -1 (and 0), never saved;
    # sessions 0 and 3 have to end as they were initialised
    "idle_frame_minus_one": [
        ([-1, 5, -1, 0], [False, True, False, False]),
        ([-1, -1, 9, 0], [False, False, True, False]),
        ([-1, 6, -1, -1], [False, True, False, False]),
    ],
    # frames f and f + R share a slot: the later save and its tag win,
    # a refused one between them changes nothing
    "same_slot": [
        ([1, 2, 3, 0], [True] * _SW_B),
        ([1 + _SW_R, 2 + _SW_R, 3 + _SW_R, _SW_R], [False, True, False, True]),
        ([1 + 2 * _SW_R, 2, 3 + 3 * _SW_R, 0], [True, False, True, False]),
    ],
    # a frame and a pred of each session's own, drawn at random
    "mixed": [
        (list(fr), list(pr))
        for fr, pr in zip(
            np.random.default_rng(7).integers(0, 5 * _SW_R, (12, _SW_B)),
            np.random.default_rng(8).random((12, _SW_B)) < 0.6,
        )
    ],
}


def _sw_bits(tree):
    return [np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(tree)]


class TestSaveWhere:
    @pytest.mark.parametrize("mode", ["unbatched", "vmap"])
    @pytest.mark.parametrize("scenario", sorted(_SW_SCENARIOS))
    def test_matches_fold_of_plain_save(self, scenario, mode):
        """``save_where`` == ``save`` where ``pred``, identity where not:
        every states leaf, ``checksums`` row and ``frames`` tag bit for bit,
        one session at a time and under ``vmap`` with a frame and a ``pred``
        of each session's own."""
        ring = DeviceStateRing(_SW_R)
        steps = _SW_SCENARIOS[scenario]
        sessions = _SW_B if mode == "vmap" else 1
        rng = np.random.default_rng(len(scenario))
        values = [[_sw_value(rng) for _ in range(sessions)] for _ in steps]

        # the reference: plain ``save`` at a concrete slot, session by session
        want = [ring.init(_sw_template()) for _ in range(sessions)]
        for (frames, preds), vals in zip(steps, values):
            for b in range(sessions):
                if preds[b]:
                    state, cs = vals[b]
                    want[b] = ring.save(want[b], jnp.int32(frames[b]), state, cs)

        if mode == "unbatched":
            got = ring.init(_sw_template())
            fn = jax.jit(ring.save_where)
            for (frames, preds), vals in zip(steps, values):
                state, cs = vals[0]
                got = fn(got, jnp.int32(frames[0]), state, cs, jnp.bool_(preds[0]))
            got = [got]
        else:
            stack = lambda trees: jax.tree_util.tree_map(
                lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]), *trees
            )
            got = stack([ring.init(_sw_template()) for _ in range(sessions)])
            fn = jax.jit(jax.vmap(ring.save_where))
            for (frames, preds), vals in zip(steps, values):
                got = fn(
                    got,
                    jnp.asarray(frames, jnp.int32),
                    stack([v[0] for v in vals]),
                    stack([v[1] for v in vals]),
                    jnp.asarray(preds, bool),
                )
            got = [
                jax.tree_util.tree_map(lambda l: l[b], got) for b in range(sessions)
            ]

        for b in range(sessions):
            assert (
                jax.tree_util.tree_structure(got[b])
                == jax.tree_util.tree_structure(want[b])
            )
            assert _sw_bits(got[b]) == _sw_bits(want[b]), (scenario, mode, b)

    def test_holds_no_dynamic_index(self):
        """The write is a select over the ring axis: no scatter, gather or
        dynamic slice in its jaxpr, batched or not."""
        ring = DeviceStateRing(_SW_R)
        buf = ring.init(_sw_template())
        state, cs = _sw_value(np.random.default_rng(0))
        one = jax.make_jaxpr(ring.save_where)(
            buf, jnp.int32(1), state, cs, jnp.bool_(True)
        )
        batched = jax.vmap(ring.save_where, in_axes=(None, 0, None, None, 0))
        many = jax.make_jaxpr(batched)(
            buf, jnp.arange(3, dtype=jnp.int32), state, cs, jnp.ones((3,), bool)
        )
        for jp in (one, many):
            names = {eq.primitive.name for eq in jp.jaxpr.eqns}
            assert not {
                n for n in names
                if "scatter" in n or "gather" in n or "dynamic" in n
            }, names


class _CounterGame:
    """Trivial deterministic game: state {count, acc}; input (1,) int32."""

    @staticmethod
    def advance(state, inp):
        return {
            "count": state["count"] + 1,
            "acc": state["acc"] * 3 + inp[0],
        }

    @staticmethod
    def init():
        return {"count": jnp.int32(0), "acc": jnp.int32(0)}


class TestReplayPrograms:
    def _run(self, n_ticks, d=2, ring_len=9):
        progs = build_replay_programs(_CounterGame.advance, ring_len, d)
        carry = progs.init_carry(_CounterGame.init(), jnp.zeros((1,), jnp.int32))
        inputs = jnp.arange(n_ticks, dtype=jnp.int32).reshape(n_ticks, 1)
        w = min(progs.warmup_ticks, n_ticks)
        carry = progs.run_warmup(carry, inputs[:w])
        if n_ticks > w:
            carry = progs.run_steady(carry, inputs[w:])
        return progs, carry

    def test_warmup_advances_frames(self):
        progs, carry = self._run(3, d=2)
        assert int(carry["frame"]) == 3
        assert int(carry["mismatches"]) == 0

    def test_steady_matches_plain_simulation(self):
        n = 40
        progs, carry = self._run(n, d=3)
        # plain forward simulation of the same inputs
        state = _CounterGame.init()
        for i in range(n):
            state = _CounterGame.advance(state, jnp.asarray([i], jnp.int32))
        live = jax.device_get(carry["live"])
        assert int(live["count"]) == int(state["count"]) == n
        assert int(live["acc"]) == int(state["acc"])
        assert int(carry["mismatches"]) == 0

    def test_nondeterminism_detected(self):
        # a game whose advance depends on how many times it has been called
        # (hidden Python-side state) is exactly what synctest must catch —
        # emulate via a frame-independent RNG-free trick: advance uses
        # state["count"] *squared* only when count is the live pass; instead
        # we corrupt determinism by making advance read the ring slot parity
        # through its own input history — simplest honest case: flip a value
        # in the saved ring between ticks and watch the compare fire.
        progs = build_replay_programs(_CounterGame.advance, 5, 2)
        carry = progs.init_carry(_CounterGame.init(), jnp.zeros((1,), jnp.int32))
        inputs = jnp.ones((3, 1), jnp.int32)
        carry = progs.run_warmup(carry, inputs)
        # corrupt the first-seen history for frame 2 → next steady tick's
        # resimulation of frame 2 must mismatch
        carry["hist"] = carry["hist"].at[2].set(jnp.uint32(0xDEAD))
        carry = progs.run_steady(carry, jnp.ones((1, 1), jnp.int32))
        assert int(carry["mismatches"]) >= 1
        assert int(carry["first_bad"]) == 2

    def test_requests_per_tick_accounting(self):
        progs, _ = self._run(2, d=2)
        assert progs.warmup_ticks == 3

    def test_check_distance_one_still_detects(self):
        # at d=1 the reference's scheme has nothing to compare (each frame is
        # resimulated exactly once); our live-advance digest makes even d=1
        # meaningful — corrupting the saved state a rollback reloads must be
        # caught on the next tick
        progs = build_replay_programs(_CounterGame.advance, 4, 1)
        carry = progs.init_carry(_CounterGame.init(), jnp.zeros((1,), jnp.int32))
        inputs = jnp.ones((6, 1), jnp.int32)
        carry = progs.run_warmup(carry, inputs[: progs.warmup_ticks])
        carry = progs.run_steady(carry, inputs[progs.warmup_ticks :])
        assert int(carry["mismatches"]) == 0
        frame = int(carry["frame"])  # next steady tick reloads frame-1
        slot = (frame - 1) % 4
        carry["ring"]["states"]["acc"] = (
            carry["ring"]["states"]["acc"].at[slot].add(1)
        )
        carry = progs.run_steady(carry, jnp.ones((1, 1), jnp.int32))
        assert int(carry["mismatches"]) >= 1
        assert int(carry["first_bad"]) == frame


class TestDigestPathEquivalence:
    """checksum_device routes small states through one concatenated
    reduction and large states through per-leaf offset sums; both must
    produce identical lanes (lane_sums' chunk-additivity contract)."""

    def test_concat_and_offset_sum_paths_agree(self):
        from ggrs_tpu.ops import checksum as cs

        rng = np.random.default_rng(3)
        # total words straddle the fuse threshold from both sides
        big = {
            "a": jnp.asarray(rng.integers(0, 2**31, size=(3000,), dtype=np.int64)),
            "b": jnp.asarray(rng.integers(0, 255, size=(2500,), dtype=np.uint8)),
            "c": jnp.asarray(rng.random((700,)).astype(np.float32)),
        }
        small = {k: v[:50] for k, v in big.items()}
        for state in (big, small):
            words = [
                cs._as_u32_words(jnp.asarray(l))
                for l in jax.tree_util.tree_leaves(state)
            ]
            concat_lanes = cs.lane_sums(jnp.concatenate(words))
            acc = jnp.zeros((4,), jnp.uint32)
            off = 0
            for w in words:
                acc = acc + cs.lane_sums(w, off)
                off += w.shape[0]
            np.testing.assert_array_equal(np.asarray(concat_lanes), np.asarray(acc))
            np.testing.assert_array_equal(
                np.asarray(cs._digest_words(words)), np.asarray(concat_lanes)
            )

    def test_leaf_structure_still_distinguished(self):
        # same concatenated words, different leaf boundaries -> the structure
        # salt must keep the digests distinct
        a = {"a": jnp.asarray([1, 2], jnp.uint32)}
        b = {"a": jnp.asarray([1], jnp.uint32), "b": jnp.asarray([2], jnp.uint32)}
        assert pytree_checksum(a) != pytree_checksum(b)
