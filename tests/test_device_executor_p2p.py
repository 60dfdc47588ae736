"""P2P peers fulfilled on the device: ``DeviceRequestExecutor`` under rollback.

``tests/test_device_executor.py`` drives the reference executor through
SyncTest only; here live P2P peers over the in-memory net roll back for real
(schedules that defeat repeat-last prediction, sparse saving, packet loss,
four players), and every peer must drain to the same frame with
bit-identical device states.  Each scenario runs under two pairings: every
peer on the reference executor, and peer A on the reference with the others
on a ONE-session ``BatchedRequestExecutor`` — the served pool's program at
batch size 1, held to the same reference.

The dispatch pin: a rollback tick of the reference executor is ONE call of
``ExecutorPrograms.burst`` — load, resimulation, saves with their checksums
and the live advance in a single dispatch.
"""

import random

import numpy as np
import pytest

import jax

from ggrs_tpu.core import LoadGameState, Local, Remote
from ggrs_tpu.games import BoxGame, boxgame_config
from ggrs_tpu.net import InMemoryNetwork
from ggrs_tpu.ops import DeviceRequestExecutor
from ggrs_tpu.parallel import BatchedRequestExecutor
from ggrs_tpu.sessions import SessionBuilder


def _to_arr(pairs):
    return np.asarray([p[0] for p in pairs], np.uint8)


def _sched(player, i):
    # player 0 changes every 4 frames, the others every 3 (offset by player):
    # repeat-last mispredicts at every transition, forcing regular rollbacks
    return (i // 4) % 16 if player == 0 else ((i + player) // 3) % 16


class _Reference:
    """Peer on ``ops.DeviceRequestExecutor``."""

    def __init__(self, game):
        self.executor = DeviceRequestExecutor(
            game.advance, game.init_state(), _to_arr
        )
        self.run = self.executor.run

    def state(self):
        return jax.device_get(self.executor.state)


class _PoolOfOne:
    """Peer on a one-session ``parallel.BatchedRequestExecutor``."""

    def __init__(self, game):
        self.pool = BatchedRequestExecutor(
            game.advance, game.init_state(), _to_arr,
            batch_size=1, ring_length=10, max_burst=9,
        )
        self.pool.warmup(np.zeros((game.num_players,), np.uint8))

    def run(self, requests):
        self.pool.run([requests])

    def state(self):
        return self.pool.live_state(0)


# scenario -> (players, sparse saving, packet loss, scheduled ticks, drain)
SCENARIOS = {
    "mispredict_every_third_frame": (2, False, 0.0, 40, 12),
    "sparse_saving": (2, True, 0.0, 40, 12),
    "packet_loss_10pct": (2, False, 0.10, 120, 40),
    "four_players": (4, False, 0.0, 36, 12),
}
PAIRINGS = {
    "reference_vs_reference": _Reference,
    "reference_vs_pool_of_one": _PoolOfOne,
}


def _make_peers(scenario, other_peers):
    players, sparse, loss, ticks, drain = SCENARIOS[scenario]
    net = InMemoryNetwork(loss=loss, seed=37)
    game = BoxGame(players)
    names = [f"P{p}" for p in range(players)]
    sessions, fulfillers = [], []
    for me in range(players):
        b = (
            SessionBuilder(boxgame_config())
            .with_num_players(players)
            .with_max_prediction_window(8)
            .with_sparse_saving_mode(sparse)
            .with_clock(lambda: 0)
            .with_rng(random.Random(17 + me))
        )
        for p in range(players):
            b = b.add_player(Local() if p == me else Remote(names[p]), p)
        sessions.append(b.start_p2p_session(net.socket(names[me])))
        fulfillers.append((_Reference if me == 0 else other_peers)(game))
    return sessions, fulfillers, ticks, drain


def _drive(sessions, fulfillers, ticks, drain):
    """Run ``ticks`` scheduled frames, then ``drain`` constant-input frames so
    repeat-last predictions become correct and every live state converges to
    the true simulation (predicted tails otherwise legitimately differ).
    Returns the number of ``LoadGameState`` requests peer A was handed."""
    loads = 0
    for i in range(ticks + drain):
        for s in sessions:
            s.poll_remote_clients()
        for p, (s, f) in enumerate(zip(sessions, fulfillers)):
            s.add_local_input(p, _sched(p, min(i, ticks - 1)))
            reqs = s.advance_frame()
            if p == 0:
                loads += sum(isinstance(r, LoadGameState) for r in reqs)
            f.run(reqs)
    return loads


@pytest.mark.parametrize("pairing", PAIRINGS)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_peers_drain_to_identical_device_states(scenario, pairing):
    sessions, fulfillers, ticks, drain = _make_peers(
        scenario, PAIRINGS[pairing]
    )
    loads = _drive(sessions, fulfillers, ticks, drain)

    assert loads >= 1, "the scenario must roll peer A back"
    assert len({s.current_frame for s in sessions}) == 1
    want = fulfillers[0].state()
    for p, f in enumerate(fulfillers[1:], start=1):
        got = f.state()
        for k in ("pos", "vel", "rot"):
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]),
                err_msg=f"peer {p} {k}",
            )


def test_rollback_tick_is_one_burst_dispatch():
    sessions, fulfillers, ticks, drain = _make_peers(
        "mispredict_every_third_frame", _Reference
    )
    peer_a = fulfillers[0]
    ex = peer_a.executor
    calls = {"advance": 0, "checksum": 0, "burst": 0}
    for name in calls:
        original = getattr(ex, f"_{name}")

        def spy(*args, _name=name, _original=original):
            calls[_name] += 1
            return _original(*args)

        setattr(ex, f"_{name}", spy)

    rollback_ticks = []

    def run_counted(reqs):
        before = dict(calls)
        ex.run(reqs)
        if any(isinstance(r, LoadGameState) for r in reqs):
            rollback_ticks.append(
                {k: calls[k] - before[k] for k in calls}
            )

    peer_a.run = run_counted
    _drive(sessions, fulfillers, ticks, drain)

    assert len(rollback_ticks) > 5, "schedule transitions must cause rollbacks"
    for got in rollback_ticks:
        assert got == {"advance": 0, "checksum": 0, "burst": 1}
