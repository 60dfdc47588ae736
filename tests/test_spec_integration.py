"""Speculative rollback wired into the live P2P path.

BASELINE config 3's integration contract: a P2P
rollback is fulfilled by a branch hit with no replay dispatch; a miss falls
back to the fused replay; states stay bit-identical to a non-speculative peer
either way.  The replay loop being replaced is the reference's rollback hot
loop (/root/reference/src/sessions/p2p_session.rs:658-714).
"""

import random

import numpy as np

import jax.numpy as jnp

from ggrs_tpu.core import LoadGameState
from ggrs_tpu.games import BoxGame, boxgame_config
from ggrs_tpu.net import InMemoryNetwork
from ggrs_tpu.ops import DeviceRequestExecutor
from ggrs_tpu.parallel import SpeculativeRollback
from ggrs_tpu.sessions import SessionBuilder
from ggrs_tpu.core import Local, Remote


def _inputs_to_array(pairs):
    return jnp.asarray(np.asarray([p[0] for p in pairs], np.uint8))


def _count_bursts(executor):
    """Wrap the executor's replay dispatch with a call counter."""
    counter = {"n": 0}
    original = executor._do_burst

    def counting(pairs, saves, **kwargs):
        counter["n"] += 1
        return original(pairs, saves, **kwargs)

    executor._do_burst = counting
    return counter


def _make_2p_pair(net, spec_factory):
    """Two P2P BoxGame peers; peer A's executor gets ``spec_factory(game)``."""
    game = BoxGame(2)
    sessions, executors = [], []
    for me, other, local_handle in (("A", "B", 0), ("B", "A", 1)):
        sess = (
            SessionBuilder(boxgame_config())
            .with_clock(lambda: 0)
            .with_rng(random.Random(3 + local_handle))
            .add_player(Local(), local_handle)
            .add_player(Remote(other), 1 - local_handle)
            .start_p2p_session(net.socket(me))
        )
        spec = spec_factory(game) if me == "A" else None
        executors.append(
            DeviceRequestExecutor(
                game.advance, game.init_state(), _inputs_to_array,
                speculation=spec,
            )
        )
        sessions.append(sess)
    return game, sessions, executors


def _a_sched(i):
    return (i // 4) % 16


def _b_sched(i):
    # changes every 3 frames: repeat-last mispredicts at every transition,
    # forcing regular rollbacks
    return (i // 3) % 16


def _drive(sessions, executors, ticks, record_loads=None, drain=12):
    """Run ``ticks`` scheduled frames, then ``drain`` constant-input frames so
    repeat-last predictions become correct and both live states converge to
    the true simulation (predicted tails otherwise legitimately differ)."""
    sess_a, sess_b = sessions
    ex_a, ex_b = executors
    for i in range(ticks + drain):
        a_in = _a_sched(min(i, ticks - 1))
        b_in = _b_sched(min(i, ticks - 1))
        sess_a.poll_remote_clients()
        sess_b.poll_remote_clients()
        sess_a.add_local_input(0, a_in)
        reqs_a = sess_a.advance_frame()
        if record_loads is not None:
            record_loads["n"] += sum(
                1 for r in reqs_a if isinstance(r, LoadGameState)
            )
        ex_a.run(reqs_a)
        sess_b.add_local_input(1, b_in)
        ex_b.run(sess_b.advance_frame())


def _assert_peers_identical(sessions, executors):
    """Both peers reached the same frame with bit-identical device states."""
    assert sessions[0].current_frame == sessions[1].current_frame
    ex_a, ex_b = executors
    for k in ("pos", "vel", "rot"):
        np.testing.assert_array_equal(
            np.asarray(ex_a.state[k]), np.asarray(ex_b.state[k]), err_msg=k
        )


def _oracle_spec(game):
    """K=2: branch 0 trusts the session's prediction, branch 1 knows peer B's
    actual schedule (a deterministic stand-in for a good guesser)."""

    def branch_inputs(k, frame, arr):
        if k == 0:
            return jnp.asarray(arr, jnp.uint8)
        return jnp.asarray(arr, jnp.uint8).at[1].set(np.uint8(_b_sched(frame)))

    return SpeculativeRollback(game.advance, 2, branch_inputs, max_window=8)


def _hopeless_spec(game):
    """K=2 hypotheses that never match B's schedule once it leaves 9:
    branch 1 guesses a constant B never presses mid-run; branch 0 repeats the
    prediction, which is wrong at every schedule transition."""

    def branch_inputs(k, frame, arr):
        if k == 0:
            return jnp.asarray(arr, jnp.uint8)
        return jnp.asarray(arr, jnp.uint8).at[1].set(np.uint8(9))

    return SpeculativeRollback(game.advance, 2, branch_inputs, max_window=8)


class TestSpeculativeP2P:
    def test_branch_hit_fulfills_rollback_without_replay(self):
        net = InMemoryNetwork()
        game, sessions, executors = _make_2p_pair(net, _oracle_spec)
        ex_a, ex_b = executors
        bursts = _count_bursts(ex_a)
        loads = {"n": 0}

        _drive(sessions, executors, 40, record_loads=loads)

        # rollbacks really happened, and every one was served by a branch
        assert loads["n"] > 5, "schedule transitions must cause rollbacks"
        assert ex_a.spec_hits == loads["n"]
        assert ex_a.spec_misses == 0
        assert bursts["n"] == 0, "a hit must not dispatch the replay scan"

        # speculative fulfillment is bit-identical to peer B's plain replay
        _assert_peers_identical(sessions, executors)

    def test_rollback_tick_is_one_fused_dispatch(self):
        """A speculative rollback tick whose burst ends in a saveless live
        advance must cost exactly ONE device dispatch: fulfill_and_refill is
        invoked with the live inputs fused in, and neither the plain advance
        nor advance_and_extend runs for that tick — dispatch parity with the
        plain path's single load+replay+advance burst."""
        net = InMemoryNetwork()
        game, sessions, executors = _make_2p_pair(net, _oracle_spec)
        ex_a, _ = executors
        bursts = _count_bursts(ex_a)

        calls = {"fused_live": 0, "unfused": 0, "advances": 0, "adv_ext": 0}
        spec = ex_a._spec
        orig_fulfill = spec.fulfill_and_refill
        orig_advance = ex_a._advance
        orig_adv_ext = spec.advance_and_extend

        def spy_fulfill(frame, confirmed, load_state, wc, live_inputs=None):
            calls["fused_live" if live_inputs is not None else "unfused"] += 1
            return orig_fulfill(
                frame, confirmed, load_state, wc, live_inputs=live_inputs
            )

        def spy_advance(state, inputs):
            calls["advances"] += 1
            return orig_advance(state, inputs)

        def spy_adv_ext(state, inputs):
            out = orig_adv_ext(state, inputs)
            if out is not None:  # None = no dispatch (caller advances plainly)
                calls["adv_ext"] += 1
            return out

        spec.fulfill_and_refill = spy_fulfill
        ex_a._advance = spy_advance
        spec.advance_and_extend = spy_adv_ext
        loads = {"n": 0}

        _drive(sessions, executors, 40, record_loads=loads)

        assert loads["n"] > 5
        assert calls["fused_live"] > 0, "live advance must ride the fulfill"
        assert bursts["n"] == 0
        # the separate advance program may only run on non-rollback ticks and
        # unrooted fallbacks — never once per rollback on top of the fused
        # dispatch (ticks = 40 scheduled + 12 drain; every dispatch beyond
        # one-per-tick would show up here)
        total_ticks = 52
        assert calls["fused_live"] + calls["unfused"] == loads["n"]
        assert (
            calls["advances"]
            + calls["adv_ext"]
            + calls["fused_live"]
            + calls["unfused"]
            == total_ticks
        ), "a tick must cost exactly one device dispatch"
        _assert_peers_identical(sessions, executors)

    def test_miss_falls_back_to_replay(self):
        net = InMemoryNetwork()
        game, sessions, executors = _make_2p_pair(net, _hopeless_spec)
        ex_a, ex_b = executors
        bursts = _count_bursts(ex_a)
        loads = {"n": 0}

        _drive(sessions, executors, 40, record_loads=loads)

        assert loads["n"] > 5
        assert ex_a.spec_misses > 0
        # misses dispatch the fused replay (depth-1 rollbacks use the single-
        # advance path, so bursts may be fewer than misses but states must
        # still match)
        _assert_peers_identical(sessions, executors)

    def test_sparse_saving_with_speculation_stays_correct(self):
        """Sparse saving produces rollback bursts with few (or oddly placed)
        saves — paths where speculation cannot re-anchor and must invalidate
        rather than trust a stale window (round-1 review finding)."""
        net = InMemoryNetwork()
        game = BoxGame(2)
        sessions, executors = [], []
        for me, other, local_handle in (("A", "B", 0), ("B", "A", 1)):
            sess = (
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(29 + local_handle))
                .with_sparse_saving_mode(True)
                .add_player(Local(), local_handle)
                .add_player(Remote(other), 1 - local_handle)
                .start_p2p_session(net.socket(me))
            )
            spec = _oracle_spec(game) if me == "A" else None
            executors.append(
                DeviceRequestExecutor(
                    game.advance, game.init_state(), _inputs_to_array,
                    speculation=spec,
                )
            )
            sessions.append(sess)

        _drive(sessions, executors, 40)
        _assert_peers_identical(sessions, executors)

    def test_speculation_under_packet_loss_mixes_hits_and_fallbacks(self):
        """Lossy network + a deterministically IMPERFECT oracle (wrong on
        every 5th frame): rollback windows containing a bad-guess frame take
        the miss/fallback + invalidate + re-anchor path, the rest hit — both
        paths must execute under loss-deepened irregular rollbacks, and the
        peers must still drain to bit-identical states."""

        def flaky_oracle(game):
            def branch_inputs(k, frame, arr):
                if k == 0:
                    return jnp.asarray(arr, jnp.uint8)
                guess = _b_sched(frame) ^ (0 if frame % 5 else 1)
                return jnp.asarray(arr, jnp.uint8).at[1].set(np.uint8(guess))

            return SpeculativeRollback(
                game.advance, 2, branch_inputs, max_window=8
            )

        net = InMemoryNetwork(loss=0.25, seed=37)
        game, sessions, executors = _make_2p_pair(net, flaky_oracle)
        ex_a, ex_b = executors

        _drive(sessions, executors, 120, drain=40)

        assert ex_a.spec_hits > 0, "clean windows must hit a branch"
        assert ex_a.spec_misses > 0, (
            "windows containing a bad-guess frame must take the fallback path"
        )
        _assert_peers_identical(sessions, executors)

    def test_four_players_eight_branches(self):
        """BASELINE config 3's exact shape: 4 players, 8-frame prediction,
        8 branches; peer 0 speculates, the other three replay."""
        net = InMemoryNetwork()
        game = BoxGame(4)
        peers = ["P0", "P1", "P2", "P3"]

        def sched(player, i):
            return ((i + player) // 3) % 16

        def branch_inputs(k, frame, arr):
            arr = jnp.asarray(arr, jnp.uint8)
            if k < 7:
                # "held buttons" style guesses on the remote lanes
                return arr.at[1:].set(np.uint8(k))
            # branch 7: the oracle for all three remotes
            vals = np.asarray(
                [sched(p, frame) for p in (1, 2, 3)], np.uint8
            )
            return arr.at[1:].set(jnp.asarray(vals))

        sessions, executors = [], []
        for me in range(4):
            b = (
                SessionBuilder(boxgame_config())
                .with_num_players(4)
                .with_max_prediction_window(8)
                .with_clock(lambda: 0)
                .with_rng(random.Random(17 + me))
            )
            for p in range(4):
                if p == me:
                    b = b.add_player(Local(), p)
                else:
                    b = b.add_player(Remote(peers[p]), p)
            sessions.append(b.start_p2p_session(net.socket(peers[me])))
            spec = (
                SpeculativeRollback(game.advance, 8, branch_inputs, max_window=8)
                if me == 0
                else None
            )
            executors.append(
                DeviceRequestExecutor(
                    game.advance, game.init_state(), _inputs_to_array,
                    speculation=spec,
                )
            )

        loads = {"n": 0}
        for i in range(48):  # 36 scheduled + 12 constant drain ticks
            for s in sessions:
                s.poll_remote_clients()
            for p, (s, ex) in enumerate(zip(sessions, executors)):
                s.add_local_input(p, sched(p, min(i, 35)))
                reqs = s.advance_frame()
                if p == 0:
                    loads["n"] += sum(
                        1 for r in reqs if isinstance(r, LoadGameState)
                    )
                ex.run(reqs)

        assert loads["n"] > 0
        assert executors[0].spec_hits > 0
        # all peers that reached the same frame agree bit-exactly
        frames = {s.current_frame for s in sessions}
        assert len(frames) == 1
        for other in (1, 2, 3):
            for k in ("pos", "vel", "rot"):
                np.testing.assert_array_equal(
                    np.asarray(executors[0].state[k]),
                    np.asarray(executors[other].state[k]),
                    err_msg=f"peer {other} {k}",
                )
