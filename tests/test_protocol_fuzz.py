"""Session-level adversarial fuzz.

The codec and message layers are property-tested in isolation
(tests/test_compression.py, tests/test_messages.py); this module attacks the
layer above: arbitrary and mutated datagrams flowing through a live
``PeerProtocol`` and a polled P2P session.  The reference hardens
decode-of-arbitrary-bytes at the codec (compression.rs:205-213) and drops
undecodable datagrams at the socket (udp_socket.rs:70-72); our contract is
stronger — no exception may escape, session state stays consistent, and
memory stays bounded, no matter what bytes arrive.
"""

from __future__ import annotations

import random

import pytest

pytest.importorskip("hypothesis")  # fuzz-only dep: absent on lean CI images

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from ggrs_tpu.core.config import Config
from ggrs_tpu.core.frame_info import PlayerInput
from ggrs_tpu.core.types import DesyncDetection, Local, Remote
from ggrs_tpu.net.messages import (
    ConnectionStatus,
    InputMessage,
    Message,
)
from ggrs_tpu.net.protocol import PENDING_OUTPUT_SIZE, PeerProtocol
from ggrs_tpu.net.sockets import InMemoryNetwork
from ggrs_tpu.sessions.builder import SessionBuilder
from ggrs_tpu.games.boxgame import boxgame_config

FUZZ_SETTINGS = settings(
    max_examples=120,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


def make_proto(seed: int = 7) -> PeerProtocol:
    return PeerProtocol(
        config=Config.for_uint(bits=8),
        handles=[1],
        peer_addr="B",
        num_players=2,
        local_players=1,
        max_prediction=8,
        disconnect_timeout_ms=2000,
        disconnect_notify_start_ms=500,
        fps=60,
        desync_detection=DesyncDetection.off(),
        clock=lambda: 0,
        rng=random.Random(seed),
    )


def realistic_input_message(rng: random.Random) -> bytes:
    """A well-formed InputMessage with randomized fields, as mutation
    seed material."""
    statuses = [
        ConnectionStatus(rng.random() < 0.2, rng.randrange(-1, 100))
        for _ in range(2)
    ]
    body = InputMessage(
        peer_connect_status=statuses,
        disconnect_requested=rng.random() < 0.05,
        start_frame=rng.randrange(-1, 50),
        ack_frame=rng.randrange(-1, 50),
        bytes=bytes(rng.randrange(256) for _ in range(rng.randrange(0, 24))),
    )
    return Message(rng.randrange(1, 1 << 16), body).encode()


def checked_pump(proto: PeerProtocol, datagrams) -> None:
    """Feed datagrams then poll; nothing may raise, and bounded-memory
    invariants must hold."""
    status = [ConnectionStatus(), ConnectionStatus()]
    for data in datagrams:
        proto.handle_datagram(bytes(data))
    proto.poll(status)
    # memory bounds: the pending window and event queue cannot be grown by
    # inbound garbage; the recv ring is bounded by construction
    assert proto._core.pending_len() <= PENDING_OUTPUT_SIZE + 1
    assert len(proto._event_queue) <= 4096


class TestArbitraryDatagrams:
    @FUZZ_SETTINGS
    @given(st.lists(st.binary(min_size=0, max_size=96), max_size=24))
    def test_random_bytes_never_crash(self, blobs):
        proto = make_proto()
        checked_pump(proto, blobs)

    @FUZZ_SETTINGS
    @given(
        st.integers(0, 2**32 - 1),
        st.lists(
            st.tuples(st.integers(0, 400), st.integers(0, 255)), max_size=12
        ),
    )
    def test_mutated_real_messages_never_crash(self, seed, flips):
        """Start from well-formed wire bytes, then flip bytes — the
        highest-yield corruption class (passes length prefixes and tag
        checks more often than pure noise)."""
        rng = random.Random(seed)
        proto = make_proto()
        datagrams = []
        for _ in range(6):
            data = bytearray(realistic_input_message(rng))
            for pos, val in flips:
                if data:
                    data[pos % len(data)] ^= val
            datagrams.append(bytes(data))
        checked_pump(proto, datagrams)

    @FUZZ_SETTINGS
    @given(st.integers(0, 2**32 - 1), st.integers(1, 40))
    def test_truncations_and_splices_never_crash(self, seed, cut):
        rng = random.Random(seed)
        proto = make_proto()
        a = realistic_input_message(rng)
        b = realistic_input_message(rng)
        datagrams = [
            a[: cut % (len(a) + 1)],            # truncated
            a + b[: cut % (len(b) + 1)],        # trailing garbage
            b[cut % len(b):],                   # missing header
            a[: len(a) // 2] + b[len(b) // 2:],  # spliced halves
        ]
        checked_pump(proto, datagrams)

    def test_huge_claimed_lengths_do_not_allocate(self):
        """Length prefixes claiming enormous payloads must be rejected
        before any allocation of that size (memory-amplification)."""
        proto = make_proto()
        # InputMessage header + uvarint byte-length claiming ~2^60 bytes
        evil = bytes.fromhex("aabb00") + b"\x00" + b"\x00" + b"\x00\x00" + (
            b"\xff\xff\xff\xff\xff\xff\xff\xff\x0f"
        )
        checked_pump(proto, [evil] * 8)


class TestFuzzedLiveSession:
    def drive_session_under_attack(self, mutate, require_liveness=True) -> None:
        """Two honest peers + an attacker spoofing peer B's address into
        peer A's socket.  Nothing may raise, and memory stays bounded.

        With ``require_liveness`` the match must also keep advancing —
        right for injected *garbage*, which can never decode to a valid
        message.  Mutated-but-valid protocol messages are a different
        contract: the wire carries no authentication (the reference fork
        does not even verify the magic, p2p_session.rs:433-440), so a
        spoofed valid disconnect/status message MAY legitimately
        disconnect a player; the required outcome then is a *clean*
        protocol disconnect, never a crash or corruption."""
        net = InMemoryNetwork()
        sessions = []
        for me, other, h in (("A", "B", 0), ("B", "A", 1)):
            sessions.append(
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(21 + h))
                .add_player(Local(), h)
                .add_player(Remote(other), 1 - h)
                .start_p2p_session(net.socket(me))
            )
        attacker = net.socket("EVIL")
        rng = random.Random(5)
        state = [0, 0]
        for i in range(120):
            # attacker spoofs B→A traffic every tick
            for data in mutate(rng):
                q = net._queues["A"]
                q.append((net._tick, "B", bytes(data)))
            for s in sessions:
                s.poll_remote_clients()
            for h, s in enumerate(sessions):
                s.add_local_input(h, (i + h) % 16)
                for r in s.advance_frame():
                    k = type(r).__name__
                    if k == "SaveGameState":
                        r.cell.save(r.frame, state[h], None)
                    elif k == "LoadGameState":
                        state[h] = r.cell.data()
        frames = [s.current_frame for s in sessions]
        if require_liveness:
            assert all(f == 120 for f in frames), frames
        else:
            disconnected = any(
                st.disconnected
                for s in sessions
                for st in s.local_connect_status
            )
            # either the match survived, or the spoofed control data caused
            # a CLEAN disconnect (attacked peer keeps simulating; the stalled
            # peer sits at its prediction threshold awaiting a timeout)
            assert all(f == 120 for f in frames) or (
                disconnected and max(frames) == 120
            ), (frames, [s.local_connect_status for s in sessions])
        _ = attacker  # the spoof path uses the queue directly

    def test_session_survives_random_garbage(self):
        def mutate(rng):
            return [
                bytes(rng.randrange(256) for _ in range(rng.randrange(0, 64)))
                for _ in range(2)
            ]

        self.drive_session_under_attack(mutate)

    def test_session_survives_mutated_protocol_traffic(self):
        def mutate(rng):
            out = []
            for _ in range(2):
                data = bytearray(realistic_input_message(rng))
                for _ in range(rng.randrange(0, 4)):
                    data[rng.randrange(len(data))] ^= rng.randrange(1, 256)
                out.append(bytes(data))
            return out

        self.drive_session_under_attack(mutate, require_liveness=False)


class TestFuzzedHandshake:
    def pump_pair(self, net, protos, socks, ticks, clock_now):
        status = [ConnectionStatus(), ConnectionStatus()]
        for _ in range(ticks):
            net.tick()
            for me in protos:
                p = protos[me]
                for _, data in socks[me].receive_all_datagrams():
                    p.handle_datagram(data)
                p.poll(status)
                p.send_all_messages(socks[me])

    def test_handshake_survives_truncated_and_reordered_probes(self):
        """Opt-in sync handshake under attack: truncated / duplicated /
        reordered Sync packets plus spoofed garbage must not crash it or
        complete it spuriously; the honest exchange still synchronizes."""
        net = InMemoryNetwork(seed=3, duplicate=0.3, reorder=0.4)
        clock_now = [0]
        protos, socks = {}, {}
        for me, other, h in (("A", "B", 0), ("B", "A", 1)):
            protos[me] = PeerProtocol(
                config=Config.for_uint(bits=8),
                handles=[1 - h],
                peer_addr=other,
                num_players=2,
                local_players=1,
                max_prediction=8,
                disconnect_timeout_ms=2000,
                disconnect_notify_start_ms=500,
                fps=60,
                desync_detection=DesyncDetection.off(),
                clock=lambda: clock_now[0],
                rng=random.Random(33 + h),
                sync_required=True,
            )
            socks[me] = net.socket(me)
        rng = random.Random(12)
        # interleave hostile packets with the honest handshake
        for step in range(40):
            clock_now[0] += 250  # past the sync retry interval
            q = net._queues["A"]
            q.append((net._tick, "B", bytes(
                rng.randrange(256) for _ in range(rng.randrange(0, 12))
            )))
            # truncated SyncReply-shaped bytes
            q.append((net._tick, "B", b"\xaa\xbb\x07"))
            self.pump_pair(net, protos, socks, 1, clock_now)
            if all(p.is_running() for p in protos.values()):
                break
        assert all(p.is_running() for p in protos.values()), (
            protos["A"]._state, protos["B"]._state
        )
