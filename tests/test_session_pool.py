"""BatchedRequestExecutor: massed fulfillment of live sessions' requests.

Oracle: a pool of B sessions fulfilled by ONE BatchedRequestExecutor must be
bit-identical to the same B sessions each fulfilled by its own
``ops.DeviceRequestExecutor`` (which is itself equivalence-tested against the
host path).  Covers heterogeneous ticks — different rollback depths per
session in the same dispatch — plus desync checksum fulfillment and sparse
saving.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from ggrs_tpu.core import DesyncDetection, Local, Remote
from ggrs_tpu.games import BoxGame, boxgame_config
from ggrs_tpu.net import InMemoryNetwork
from ggrs_tpu.ops import DeviceRequestExecutor, ExecutorPrograms
from ggrs_tpu.ops.checksum import checksum_to_u128
from ggrs_tpu.parallel import BatchedRequestExecutor
from ggrs_tpu.sessions import SessionBuilder

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# the benchmark's plain NumPy copy of the digest: imports nothing of the program
from benchmark.reference import digest as reference_digest  # noqa: E402


def _to_arr(pairs):
    return np.asarray([p[0] for p in pairs], np.uint8)


def _make_matches(n_matches, seed, sparse=False, desync_interval=0):
    """n_matches 2-peer BoxGame matches over one in-memory net.  Returns
    (sessions, schedules): flat lists, session 2*m is match m's peer A."""
    net = InMemoryNetwork()
    sessions, schedules = [], []
    for m in range(n_matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            b = (
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(seed + 7 * m + me))
                .with_sparse_saving_mode(sparse)
            )
            if desync_interval:
                b = b.with_desync_detection_mode(
                    DesyncDetection(True, desync_interval)
                )
            b = b.add_player(Local(), me).add_player(
                Remote(names[1 - me]), 1 - me
            )
            sessions.append(b.start_p2p_session(net.socket(names[me])))
            # per-session input schedule; offsets differ per match so the
            # pool sees heterogeneous rollback depths in one tick
            schedules.append(
                lambda i, m=m, me=me: ((i + 2 * m + me) // (2 + m % 3)) % 16
            )
    return sessions, schedules


def _drive(sessions, schedules, fulfill, ticks, drain=14):
    for i in range(ticks + drain):
        for s in sessions:
            s.poll_remote_clients()
        all_reqs = []
        for handle_owner, (s, sched) in enumerate(zip(sessions, schedules)):
            s.add_local_input(handle_owner % 2, sched(min(i, ticks - 1)))
            all_reqs.append(s.advance_frame())
        fulfill(all_reqs)


def _run_pool(n_matches, ticks, seed, sparse=False, desync_interval=0):
    sessions, schedules = _make_matches(
        n_matches, seed, sparse=sparse, desync_interval=desync_interval
    )
    game = BoxGame(2)
    B = len(sessions)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), _to_arr,
        batch_size=B, ring_length=10, max_burst=9,
    )
    pool.warmup(np.zeros((2,), np.uint8))
    _drive(sessions, schedules, pool.run, ticks)
    states = [pool.live_state(b) for b in range(B)]
    frames = [s.current_frame for s in sessions]
    events = [list(s.events()) for s in sessions]
    return states, frames, events, pool


def _run_individual(n_matches, ticks, seed, sparse=False, desync_interval=0):
    sessions, schedules = _make_matches(
        n_matches, seed, sparse=sparse, desync_interval=desync_interval
    )
    game = BoxGame(2)
    programs = ExecutorPrograms(game.advance)
    executors = [
        DeviceRequestExecutor(
            game.advance, game.init_state(), _to_arr, programs=programs
        )
        for _ in sessions
    ]

    def fulfill(all_reqs):
        for ex, reqs in zip(executors, all_reqs):
            ex.run(reqs)

    _drive(sessions, schedules, fulfill, ticks)
    states = [jax.device_get(ex.state) for ex in executors]
    frames = [s.current_frame for s in sessions]
    events = [list(s.events()) for s in sessions]
    return states, frames, events


def _assert_states_equal(got, want, label):
    for b, (g, w) in enumerate(zip(got, want)):
        for k in w:
            np.testing.assert_array_equal(
                np.asarray(g[k]), np.asarray(w[k]),
                err_msg=f"{label}: session {b} key {k}",
            )


class TestBatchedRequestExecutor:
    def test_pool_matches_individual_executors(self):
        """4 matches (8 sessions) with different rollback cadences: pooled
        fulfillment must be bit-identical to per-session executors."""
        pool_states, pool_frames, _, _ = _run_pool(4, 40, seed=11)
        ind_states, ind_frames, _ = _run_individual(4, 40, seed=11)
        assert pool_frames == ind_frames
        _assert_states_equal(pool_states, ind_states, "pool-vs-individual")

    def test_peers_converge_within_each_match(self):
        states, frames, _, _ = _run_pool(3, 36, seed=23)
        for m in range(3):
            assert frames[2 * m] == frames[2 * m + 1]
            for k in states[0]:
                np.testing.assert_array_equal(
                    np.asarray(states[2 * m][k]),
                    np.asarray(states[2 * m + 1][k]),
                    err_msg=f"match {m} key {k}",
                )

    def test_sparse_saving_through_the_pool(self):
        pool_states, pool_frames, _, _ = _run_pool(2, 36, seed=31, sparse=True)
        ind_states, ind_frames, _ = _run_individual(2, 36, seed=31, sparse=True)
        assert pool_frames == ind_frames
        _assert_states_equal(pool_states, ind_states, "sparse")

    def test_desync_detection_rides_lazy_ring_checksums(self):
        """With desync detection on, sessions exchange checksums the pool
        serves lazily from the digest ring — no DesyncDetected events for
        honest peers, and the checksum values match the individual path."""
        _, _, events, _ = _run_pool(2, 40, seed=43, desync_interval=8)
        for evs in events:
            assert not any(
                type(e).__name__ == "EvDesyncDetected" for e in evs
            ), evs

    def test_ring_accessors_validate_frames(self):
        import pytest

        states, frames, _, pool = _run_pool(1, 20, seed=5)
        # a recent frame is retrievable and consistent with its checksum
        f = frames[0] - 1
        st = pool.ring_state(0, f)
        assert set(st) == set(states[0])
        cs = pool.ring_checksum(0, f)
        assert isinstance(cs, int) and cs > 0
        # a frame that has rolled out of the ring is refused
        with pytest.raises(RuntimeError):
            pool.ring_state(0, max(0, f - 50))

    def test_pool_sharded_over_virtual_mesh(self):
        """The same pooled fulfillment sharded over the 8-device virtual
        mesh: bit-identical to the unsharded pool (sessions are independent —
        no collectives, linear scaling)."""
        import jax as _jax

        from ggrs_tpu.parallel import make_mesh

        if len(_jax.devices()) < 8:
            import pytest

            pytest.skip("needs the 8-device virtual mesh")

        sessions, schedules = _make_matches(4, seed=11)
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=8, ring_length=10, max_burst=9,
            mesh=make_mesh(8),
        )
        pool.warmup(np.zeros((2,), np.uint8))
        _drive(sessions, schedules, pool.run, 40)
        states = [pool.live_state(b) for b in range(8)]
        frames = [s.current_frame for s in sessions]

        ind_states, ind_frames, _ = _run_individual(4, 40, seed=11)
        assert frames == ind_frames
        _assert_states_equal(states, ind_states, "sharded-pool")

    def test_undersized_ring_fails_loudly(self):
        """A pool whose ring_length can't cover the sessions' prediction
        window must raise at parse time — the device gather would otherwise
        silently load a newer frame that aliased into the slot.  Rollback
        depth must exceed ring_length for staleness to be possible (each
        rollback re-saves its whole window), so delay delivery to deepen the
        prediction tail."""
        import pytest

        net = InMemoryNetwork(latency_ticks=4)
        sessions = []
        for me, other, h in (("A", "B", 0), ("B", "A", 1)):
            sessions.append(
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(11 + h))
                .add_player(Local(), h)
                .add_player(Remote(other), 1 - h)
                .start_p2p_session(net.socket(me))
            )
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=2, ring_length=3, max_burst=9,
        )
        pool.warmup(np.zeros((2,), np.uint8))
        with pytest.raises(RuntimeError, match="too small"):
            for i in range(40):
                net.tick()
                for s in sessions:
                    s.poll_remote_clients()
                reqs = []
                for h, s in enumerate(sessions):
                    s.add_local_input(h, (i // 2) % 16)
                    reqs.append(s.advance_frame())
                pool.run(reqs)
        # the aborted tick left fulfilled cells pointing at slots it never
        # wrote — the pool must refuse ALL further use, not serve stale state
        with pytest.raises(RuntimeError, match="invalidated"):
            pool.run([[] for _ in range(2)])
        with pytest.raises(RuntimeError, match="invalidated"):
            pool.ring_state(0, 0)

    def test_spectator_follows_through_the_pool(self):
        """The pool serves ANY session emitting the request grammar: a
        spectator (advance-only requests, sometimes none while waiting on the
        host) shares the batch with its two P2P peers and tracks their
        simulation bit-exactly."""
        from ggrs_tpu.core import PredictionThreshold, Spectator

        net = InMemoryNetwork()
        clock = lambda: 0
        host = (
            SessionBuilder(boxgame_config())
            .with_clock(clock)
            .with_rng(random.Random(7))
            .add_player(Local(), 0)
            .add_player(Remote("B"), 1)
            .add_player(Spectator("SPEC"), 2)
            .start_p2p_session(net.socket("A"))
        )
        peer = (
            SessionBuilder(boxgame_config())
            .with_clock(clock)
            .with_rng(random.Random(8))
            .add_player(Remote("A"), 0)
            .add_player(Local(), 1)
            .start_p2p_session(net.socket("B"))
        )
        spec = (
            SessionBuilder(boxgame_config())
            .with_clock(clock)
            .start_spectator_session("A", net.socket("SPEC"))
        )
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=3, ring_length=10, max_burst=9,
        )
        pool.warmup(np.zeros((2,), np.uint8))

        for i in range(60):
            host.poll_remote_clients()
            peer.poll_remote_clients()
            host.add_local_input(0, (min(i, 45) // 4) % 16)
            reqs = [host.advance_frame()]
            peer.add_local_input(1, (min(i, 45) // 3) % 16)
            reqs.append(peer.advance_frame())
            try:
                reqs.append(spec.advance_frame())
            except PredictionThreshold:
                reqs.append([])  # still waiting on host input
            pool.run(reqs)

        assert spec.current_frame > 40, "spectator never followed"
        # the spectator's live state after advancing frame f equals the
        # host's save of frame f+1 (saves label the pre-advance frame, the
        # spectator counts completed advances)
        f = spec.current_frame
        want = pool.ring_state(0, f + 1)
        got = pool.live_state(2)
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]), err_msg=k
            )

    def test_disconnect_mid_match_through_the_pool(self):
        """One pooled match loses a player mid-run (manual disconnect_player,
        the reference's p2p_session.rs:485-511): the surviving peer rolls
        back to the disconnect frame with dummy inputs and keeps simulating;
        the OTHER pooled match must be completely unaffected — bit-identical
        to running it alone."""
        sessions, schedules = _make_matches(2, seed=17)
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=4, ring_length=10, max_burst=9,
        )
        pool.warmup(np.zeros((2,), np.uint8))

        for i in range(50):
            for s in sessions:
                s.poll_remote_clients()
            reqs = []
            for h, (s, sched) in enumerate(zip(sessions, schedules)):
                if h == 1 and i >= 30:
                    reqs.append([])  # match 0's peer B went away
                    continue
                if h == 0 and i == 32:
                    s.disconnect_player(1)  # survivor drops the silent peer
                s.add_local_input(h % 2, sched(min(i, 39)))
                reqs.append(s.advance_frame())
            pool.run(reqs)

        # the survivor kept advancing past the disconnect with dummy inputs
        assert sessions[0].current_frame > 35
        # match 1 (sessions 2,3) is unaffected: its peers still agree
        assert sessions[2].current_frame == sessions[3].current_frame
        for k in ("pos", "vel", "rot"):
            np.testing.assert_array_equal(
                np.asarray(pool.live_state(2)[k]),
                np.asarray(pool.live_state(3)[k]),
                err_msg=f"match 1 {k}",
            )

    def test_lockstep_and_input_delay_through_the_pool(self):
        """A lockstep match (max_prediction=0: no saves, no rollbacks —
        fork delta #3) and an input-delay match share one pool with a
        default match; all three shapes normalize into the same program."""
        net = InMemoryNetwork()
        clock = lambda: 0
        sessions = []
        variants = [
            lambda b: b.with_max_prediction_window(0),  # lockstep
            lambda b: b.with_input_delay(2),
            lambda b: b,
        ]
        for m, variant in enumerate(variants):
            names = (f"A{m}", f"B{m}")
            for me in (0, 1):
                b = (
                    SessionBuilder(boxgame_config())
                    .with_clock(clock)
                    .with_rng(random.Random(71 + 3 * m + me))
                )
                b = variant(b)
                b = b.add_player(Local(), me).add_player(
                    Remote(names[1 - me]), 1 - me
                )
                sessions.append(b.start_p2p_session(net.socket(names[me])))
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=6, ring_length=10, max_burst=9,
        )
        pool.warmup(np.zeros((2,), np.uint8))

        for i in range(50):
            for s in sessions:
                s.poll_remote_clients()
            reqs = []
            for h, s in enumerate(sessions):
                s.add_local_input(h % 2, (min(i, 39) // (2 + h // 2)) % 16)
                reqs.append(s.advance_frame())
            pool.run(reqs)

        for m in range(3):
            a, b = sessions[2 * m], sessions[2 * m + 1]
            # deterministic fixture (fixed clock, seeded rng, in-memory net):
            # both peers reach the same frame exactly
            assert a.current_frame == b.current_frame, (
                m, a.current_frame, b.current_frame
            )
            for k in ("pos", "vel", "rot"):
                np.testing.assert_array_equal(
                    np.asarray(pool.live_state(2 * m)[k]),
                    np.asarray(pool.live_state(2 * m + 1)[k]),
                    err_msg=f"match {m} {k}",
                )

    def test_one_dispatch_per_tick(self):
        """The pool's whole point: a tick with B heterogeneous request lists
        costs exactly one program dispatch (zero when all-empty)."""
        sessions, schedules = _make_matches(3, seed=3)
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=6, ring_length=10, max_burst=9,
        )
        pool.warmup(np.zeros((2,), np.uint8))
        calls = {"n": 0}
        real_tick = pool._tick

        def counting(carry, desc):
            calls["n"] += 1
            return real_tick(carry, desc)

        pool._tick = counting
        _drive(sessions, schedules, pool.run, 20, drain=0)
        assert calls["n"] == 20
        pool.run([[] for _ in range(6)])
        assert calls["n"] == 20, "an all-empty tick must not dispatch"


# ---------------------------------------------------------------------------
# the burst loop ends at the deepest plan of the batch (PERF §6, PR 30)
# ---------------------------------------------------------------------------

_R, _D, _B = 10, 9, 6  # boxgame-2p's ring and burst; six sessions
_PRELUDE = 9  # quiet ticks before the descriptor under test: frames 0..8 saved


class _NumpySession:
    """One session's tick as the descriptor grammar states it, in plain
    NumPy: ``[pre-save] [load [post-load save]] (advance, save?)*``."""

    def __init__(self, game):
        self.game = game
        self.live = game.init_state_np()
        self.slots = [dict(self.live) for _ in range(_R)]
        self.digests = [0] * _R
        self.frames = [-1] * _R

    def save(self, frame, state):
        s = int(frame) % _R
        self.slots[s] = {k: v.copy() for k, v in state.items()}
        self.digests[s] = reference_digest.u128(state)
        self.frames[s] = int(frame)

    def tick(self, desc, b):
        st = self.live
        if desc["pre_save"][b]:
            self.save(desc["pre_frame"][b], st)
        if desc["do_load"][b]:
            st = self.slots[int(desc["load_frame"][b]) % _R]
            if desc["postload_save"][b]:
                self.save(desc["postload_frame"][b], st)
        for j in range(int(desc["n_adv"][b])):
            st = self.game.advance_np(st, desc["inputs"][b, j])
            if desc["save_mask"][b, j]:
                self.save(desc["save_frame"][b, j], st)
        self.live = st


def _plan(desc, b, rng, frame, depth, *, postload=False):
    """Fill row ``b`` as a session at ``frame`` would: depth 0 a save-only
    tick, 1 a quiet tick (pre-save + advance), d >= 2 a rollback of d - 1
    frames (load, d advances, a save after each but the last)."""
    desc["inputs"][b] = rng.integers(0, 16, desc["inputs"][b].shape)
    if depth <= 1:
        desc["pre_save"][b] = True
        desc["pre_frame"][b] = frame
        desc["n_adv"][b] = depth
        return
    lf = frame - (depth - 1)
    desc["do_load"][b] = True
    desc["load_frame"][b] = lf
    if postload:  # sparse saving's save of the just-loaded state
        desc["postload_save"][b] = True
        desc["postload_frame"][b] = lf
    desc["n_adv"][b] = depth
    desc["save_mask"][b, : depth - 1] = True
    desc["save_frame"][b, : depth - 1] = lf + 1 + np.arange(depth - 1)


_DEPTH_CASES = {
    # name: (depth per session; None leaves the row idle), post-load save
    "save_only": ([0] * _B, False),
    "quiet": ([1] * _B, False),
    "one_frame_rollback": ([2] * _B, False),
    "max_burst": ([_D] * _B, False),
    "mixed": ([None, 0, 1, 2, 5, _D], False),
    "mixed_deepest_first": ([_D, 2, 1, 1, 0, None], False),
    "mixed_shallow_postload": ([1, 2, 3, 1, None, 2], True),
}


def _prelude_and_case(pool, depths, postload, seed):
    """The descriptors of ``_PRELUDE`` quiet ticks, then the one under
    test."""
    rng = np.random.default_rng(seed)
    descs = []
    for f in range(_PRELUDE):
        desc = pool._blank_desc()
        for b in range(pool.batch_size):
            _plan(desc, b, rng, f, 1)
        descs.append(desc)
    desc = pool._blank_desc()
    for b, depth in enumerate(depths):
        if depth is not None:
            _plan(desc, b, rng, _PRELUDE, depth, postload=postload)
    descs.append(desc)
    return descs


class TestBurstLoopEndsAtTheDeepestPlan:
    @pytest.mark.parametrize("case", sorted(_DEPTH_CASES))
    def test_tick_equals_the_numpy_replay(self, case):
        """Live state, every ring slot, its digest and its frame tag, bit
        for bit, whatever the deepest plan of the batch is."""
        depths, postload = _DEPTH_CASES[case]
        game = BoxGame(2)
        pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=_B, ring_length=_R, max_burst=_D,
        )
        pool.warmup(np.zeros((2,), np.uint8))
        model = [_NumpySession(game) for _ in range(_B)]
        for desc in _prelude_and_case(pool, depths, postload, seed=30):
            for b, session in enumerate(model):
                session.tick(desc, b)
            pool._carry = pool._tick(pool._carry, desc)
        carry = jax.device_get(pool._carry)
        ring = carry["ring"]
        for b, session in enumerate(model):
            for k, want in session.live.items():
                np.testing.assert_array_equal(
                    carry["live"][k][b], want, err_msg=f"{case} live {b} {k}")
            assert ring["frames"][b].tolist() == session.frames, (case, b)
            for s in range(_R):
                for k, want in session.slots[s].items():
                    np.testing.assert_array_equal(
                        ring["states"][k][b, s], want,
                        err_msg=f"{case} session {b} slot {s} {k}")
                got = checksum_to_u128(ring["checksums"][b, s])
                assert got == session.digests[s], (case, b, s)
