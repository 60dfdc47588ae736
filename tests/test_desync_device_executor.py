"""Desync detection over the wire with device-resident state.

The r3 perf redesign made save checksums lazy (``DeviceChecksum`` handles
that materialize only when the desync exchange reports one).  These tests
close the loop the unit tests can't: two live P2P peers fulfilled by device
executors exchange real checksum reports through the session's interval
machinery, and synchronized simulations must produce ZERO
DesyncDetected events (while a deliberately corrupted peer must produce
one).  Reference flow: /root/reference/src/sessions/p2p_session.rs:904-975.
"""

import random

import numpy as np

from ggrs_tpu.core import DesyncDetected, DesyncDetection, Local, Remote
from ggrs_tpu.games import BoxGame, boxgame_config
from ggrs_tpu.net import InMemoryNetwork
from ggrs_tpu.ops import DeviceRequestExecutor
from ggrs_tpu.sessions import SessionBuilder


def _to_arr(pairs):
    return np.asarray([p[0] for p in pairs], np.uint8)


def _b_sched(i):
    return (i // 3) % 16  # transitions force regular rollbacks


def _make_pair(interval=10):
    game = BoxGame(2)
    net = InMemoryNetwork()
    sessions, executors = [], []
    for me, other, local_handle in (("A", "B", 0), ("B", "A", 1)):
        sess = (
            SessionBuilder(boxgame_config())
            .with_clock(lambda: 0)
            .with_rng(random.Random(41 + local_handle))
            .with_desync_detection_mode(DesyncDetection.on(interval))
            .add_player(Local(), local_handle)
            .add_player(Remote(other), 1 - local_handle)
            .start_p2p_session(net.socket(me))
        )
        executors.append(
            DeviceRequestExecutor(game.advance, game.init_state(), _to_arr)
        )
        sessions.append(sess)
    return game, sessions, executors


def _drive(sessions, executors, ticks):
    events = [[], []]
    for i in range(ticks):
        for p, (s, ex) in enumerate(zip(sessions, executors)):
            s.poll_remote_clients()
            s.add_local_input(p, (i // 4) % 16 if p == 0 else _b_sched(i))
            ex.run(s.advance_frame())
            events[p].extend(s.events())
    return events


class TestDeviceExecutorDesyncExchange:
    def test_synchronized_peers_report_no_desync(self):
        """Lazy device checksums materialize at the send interval, cross the
        wire as u128s, and compare equal on both peers (save cells filled by
        single saves and by the fused burst's per-step outputs)."""
        game, sessions, executors = _make_pair(interval=10)
        events = _drive(sessions, executors, 80)
        for p in (0, 1):
            desyncs = [e for e in events[p] if isinstance(e, DesyncDetected)]
            assert desyncs == [], f"peer {p} saw false desyncs: {desyncs}"
        # the exchange really happened: both peers sent interval checksums
        for s in sessions:
            assert s._last_sent_checksum_frame >= 10

    def test_corrupted_peer_is_detected(self):
        """Corrupt peer B's live state mid-run: the checksum exchange must
        surface DesyncDetected with crossed checksums (the device analog of
        the reference's frame-200 desync test)."""
        import jax.numpy as jnp

        game, sessions, executors = _make_pair(interval=5)
        _drive(sessions, executors, 30)
        # nudge B's simulation off-course (bit-level corruption)
        ex_b = executors[1]
        ex_b._state = {**ex_b.state, "pos": ex_b.state["pos"] + jnp.int32(1)}
        events = _drive(sessions, executors, 60)
        desyncs = [
            e
            for p in (0, 1)
            for e in events[p]
            if isinstance(e, DesyncDetected)
        ]
        assert desyncs, "corruption must surface as DesyncDetected"
        assert any(e.local_checksum != e.remote_checksum for e in desyncs)
