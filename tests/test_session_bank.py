"""Parity pin for the native session bank (native/session_bank.cpp via
parallel/host_bank.py): the pooled one-crossing-per-tick path must be
indistinguishable — bit-identical wire bytes, frames, request lists, and
events — from B independent Python sessions driven with identical seeded
traffic, including loss/duplication/reordering.  Mirrors the role
tests/test_native_sync.py and tests/test_native_endpoint.py play one layer
down.

Also pinned here: the one-crossing-per-tick invariant (a crossing-count
test), the Python fallback's identical behavior when the native bank is
unavailable, and the bank's disconnect handling.
"""

from __future__ import annotations

import random

import pytest

from ggrs_tpu.core import Local, Remote
from ggrs_tpu.core.config import Config
from ggrs_tpu.core.types import Disconnected, NetworkInterrupted
from ggrs_tpu.net import InMemoryNetwork, _native
from ggrs_tpu.parallel.host_bank import HostSessionPool
from ggrs_tpu.sessions import SessionBuilder

needs_native = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)


class RecordingSocket:
    """Wraps a FakeSocket, recording every (addr, wire bytes) sent."""

    def __init__(self, inner):
        self.inner = inner
        self.sent = []

    def send_to(self, msg, addr):
        self.sent.append((addr, msg.encode()))
        self.inner.send_to(msg, addr)

    def receive_all_datagrams(self):
        return self.inner.receive_all_datagrams()

    def receive_all_messages(self):
        return self.inner.receive_all_messages()


def two_peer_builders(net, clock, n_matches, input_delay=0, bits=16):
    """2·n_matches sessions (n_matches 2-peer matches) over ``net``; the
    SAME construction for the bank and the reference sessions."""
    out = []
    for m in range(n_matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            b = (
                SessionBuilder(Config.for_uint(bits))
                .with_clock(lambda: clock[0])
                .with_rng(random.Random(3 + 5 * m + me))
                .with_input_delay(input_delay)
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            out.append((b, RecordingSocket(net.socket(names[me]))))
    return out


def four_peer_builders(net, clock):
    """One 4-peer match: 4 sessions, 3 remote endpoints each."""
    names = [f"N{h}" for h in range(4)]
    out = []
    for h in range(4):
        b = (
            SessionBuilder(Config.for_uint(16))
            .with_num_players(4)
            .with_clock(lambda: clock[0])
            .with_rng(random.Random(40 + h))
        )
        for o in range(4):
            b = b.add_player(Local() if o == h else Remote(names[o]), o)
        out.append((b, RecordingSocket(net.socket(names[h]))))
    return out


def fulfill_saves(requests):
    for r in requests:
        if type(r).__name__ == "SaveGameState":
            r.cell.save(r.frame, None, None)


def assert_requests_equal(py_reqs, bank_reqs, context):
    assert len(py_reqs) == len(bank_reqs), (
        f"{context}: request count {len(py_reqs)} != {len(bank_reqs)}"
    )
    for a, b in zip(py_reqs, bank_reqs):
        assert type(a).__name__ == type(b).__name__, (context, py_reqs, bank_reqs)
        if type(a).__name__ == "AdvanceFrame":
            assert a.inputs == b.inputs, (context, a.inputs, b.inputs)
        else:
            assert a.frame == b.frame, (context, a.frame, b.frame)


def run_parity(builders_fn, faults, ticks, local_of, sched):
    """Drive the bank and the per-session Python reference with identical
    traffic on identically-seeded fault networks; compare everything."""
    clock = [0]
    net_bank = InMemoryNetwork(**faults)
    net_py = InMemoryNetwork(**faults)
    bank_builders = builders_fn(net_bank, clock)
    py_builders = builders_fn(net_py, clock)

    pool = HostSessionPool()
    for b, s in bank_builders:
        pool.add_session(b, s)
    py_sessions = [b.start_p2p_session(s) for b, s in py_builders]
    assert pool.native_active, "native bank did not engage"

    n = len(py_sessions)
    for i in range(ticks):
        clock[0] += 16
        for idx in range(n):
            py_sessions[idx].add_local_input(local_of(idx), sched(i, idx))
            pool.add_local_input(idx, local_of(idx), sched(i, idx))
        py_reqs = []
        for s in py_sessions:
            r = s.advance_frame()
            fulfill_saves(r)
            py_reqs.append(r)
        bank_reqs = pool.advance_all()
        for r in bank_reqs:
            fulfill_saves(r)
        net_bank.tick()
        net_py.tick()
        for idx in range(n):
            ps = py_builders[idx][1].sent
            bs = bank_builders[idx][1].sent
            assert ps == bs, (
                f"tick {i} session {idx}: wire bytes diverged "
                f"(py {len(ps)} datagrams, bank {len(bs)})"
            )
            assert_requests_equal(
                py_reqs[idx], bank_reqs[idx], f"tick {i} session {idx}"
            )
            assert py_sessions[idx].events() == pool.events(idx), (
                f"tick {i} session {idx}: events diverged"
            )
            assert py_sessions[idx].current_frame == pool.current_frame(idx)
            assert (
                py_sessions[idx]._sync_layer.last_confirmed_frame
                == pool.last_confirmed_frame(idx)
            )
    assert all(pool.current_frame(i) >= ticks - 64 for i in range(n)), (
        "a pooled session stalled short of the horizon"
    )
    return pool


@needs_native
class TestCrossCoreParityFuzz:
    @pytest.mark.parametrize("seed", [1, 7, 23])
    def test_two_peer_matches_under_faults(self, seed):
        """The headline pin: 3 matches, seeded loss/dup/reorder, 300 ticks,
        bit-identical wire bytes / requests / events / frames."""
        run_parity(
            lambda net, clock: two_peer_builders(net, clock, n_matches=3),
            dict(seed=seed, loss=0.05, duplicate=0.03, reorder=0.03,
                 latency_ticks=1),
            ticks=300,
            local_of=lambda idx: idx % 2,
            sched=lambda i, idx: ((i + 2 * idx) // (2 + idx % 3)) % 16,
        )

    def test_two_peer_matches_faultless(self):
        run_parity(
            lambda net, clock: two_peer_builders(net, clock, n_matches=2),
            dict(latency_ticks=1),
            ticks=200,
            local_of=lambda idx: idx % 2,
            sched=lambda i, idx: ((i + idx) // 2) % 16,
        )

    def test_four_peer_match_under_faults(self):
        """Multi-endpoint sessions: 4 peers, 3 endpoints each."""
        run_parity(
            four_peer_builders,
            dict(seed=99, loss=0.04, duplicate=0.02, reorder=0.04,
                 latency_ticks=1),
            ticks=250,
            local_of=lambda idx: idx,
            sched=lambda i, idx: ((i * 7 + idx) // 3) % 16,
        )

    def test_input_delay(self):
        run_parity(
            lambda net, clock: two_peer_builders(
                net, clock, n_matches=2, input_delay=2
            ),
            dict(seed=5, loss=0.03, duplicate=0.02, reorder=0.02,
                 latency_ticks=1),
            ticks=200,
            local_of=lambda idx: idx % 2,
            sched=lambda i, idx: ((i + idx) // (2 + idx % 2)) % 16,
        )

    def test_blackout_exercises_retry_and_interrupt_timers(self):
        """A 60-tick total blackout mid-run: the 200 ms retry timer
        resends the pending window, prediction-threshold skips stall both
        paths identically, NetworkInterrupted fires at 500 ms of silence,
        NetworkResumed on the first packet after restore — all bit-identical
        (the steady-traffic fuzz never reaches these timers)."""
        clock = [0]
        net_bank = InMemoryNetwork(latency_ticks=1)
        net_py = InMemoryNetwork(latency_ticks=1)
        bank_builders = two_peer_builders(net_bank, clock, n_matches=2)
        py_builders = two_peer_builders(net_py, clock, n_matches=2)
        pool = HostSessionPool()
        for b, s in bank_builders:
            pool.add_session(b, s)
        py_sessions = [b.start_p2p_session(s) for b, s in py_builders]
        assert pool.native_active

        n = len(py_sessions)
        interrupted = resumed = 0
        for i in range(260):
            clock[0] += 16
            if i == 100:
                net_bank.loss = net_py.loss = 1.0
            if i == 160:
                net_bank.loss = net_py.loss = 0.0
            for idx in range(n):
                py_sessions[idx].add_local_input(idx % 2, (i + idx) % 16)
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            py_reqs = []
            for s in py_sessions:
                r = s.advance_frame()
                fulfill_saves(r)
                py_reqs.append(r)
            bank_reqs = pool.advance_all()
            for r in bank_reqs:
                fulfill_saves(r)
            net_bank.tick()
            net_py.tick()
            for idx in range(n):
                assert (
                    py_builders[idx][1].sent == bank_builders[idx][1].sent
                ), f"tick {i} session {idx}: wire divergence"
                assert_requests_equal(
                    py_reqs[idx], bank_reqs[idx], f"tick {i} s{idx}"
                )
                pe = py_sessions[idx].events()
                assert pe == pool.events(idx), f"tick {i} s{idx} events"
                interrupted += sum(
                    isinstance(e, NetworkInterrupted) for e in pe
                )
                resumed += sum(
                    type(e).__name__ == "NetworkResumed" for e in pe
                )
        assert interrupted >= n, "blackout never tripped the interrupt timer"
        assert resumed >= n, "recovery never emitted NetworkResumed"
        assert all(pool.current_frame(i) >= 150 for i in range(n))


@needs_native
class TestOneCrossingPerTick:
    def test_crossing_count_is_exactly_ticks(self):
        """THE tentpole invariant: B sessions' whole protocol + sync
        mechanism steps in exactly ONE ctypes crossing per pool tick."""
        clock = [0]
        net = InMemoryNetwork(latency_ticks=1)
        pool = HostSessionPool()
        for b, s in two_peer_builders(net, clock, n_matches=4):
            pool.add_session(b, s)
        assert pool.native_active
        TICKS = 50
        for i in range(TICKS):
            clock[0] += 16
            for idx in range(len(pool)):
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            net.tick()
        assert pool.crossings == TICKS

    def test_scrapes_add_zero_tick_crossings(self):
        """The obs budget (DESIGN.md §12): a metrics scrape per pool tick
        costs exactly one SEPARATE ``ggrs_bank_stats`` crossing for the
        whole bank — the tick crossing count is untouched, repeat scrapes
        and ``network_stats`` reads within a tick hit the cache."""
        from ggrs_tpu.core.errors import StatsUnavailable
        from ggrs_tpu.obs import Registry

        clock = [0]
        net = InMemoryNetwork(latency_ticks=1)
        pool = HostSessionPool(metrics=Registry())
        for b, s in two_peer_builders(net, clock, n_matches=4):
            pool.add_session(b, s)
        assert pool.native_active
        TICKS = 50
        for i in range(TICKS):
            clock[0] += 16
            for idx in range(len(pool)):
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            pool.scrape()            # one stats crossing...
            pool.scrape()            # ...and the repeat is cached
            if i % 5 == 0:
                try:
                    pool.network_stats(0, 1)  # rides the same cache
                except StatsUnavailable:
                    pass  # under a second of elapsed clock (parity raise)
            net.tick()
        assert pool.crossings == TICKS, "scraping perturbed the tick path"
        assert pool.stat_crossings == TICKS
        assert pool.metrics.value("ggrs_pool_ticks_total") == TICKS
        assert pool.metrics.value(
            "ggrs_pool_crossings_total", kind="stats"
        ) == TICKS


class TestFallback:
    def test_fallback_behaves_like_plain_sessions(self, monkeypatch):
        """With the native bank unavailable the pool must drive ordinary
        P2PSessions — same wire bytes, frames, and requests as using
        P2PSession directly."""
        monkeypatch.setattr(_native, "bank_lib", lambda: None)
        clock = [0]
        faults = dict(seed=11, loss=0.05, duplicate=0.03, reorder=0.03,
                      latency_ticks=1)
        net_pool = InMemoryNetwork(**faults)
        net_ref = InMemoryNetwork(**faults)
        pool_builders = two_peer_builders(net_pool, clock, n_matches=2)
        ref_builders = two_peer_builders(net_ref, clock, n_matches=2)

        pool = HostSessionPool()
        for b, s in pool_builders:
            pool.add_session(b, s)
        refs = [b.start_p2p_session(s) for b, s in ref_builders]
        assert not pool.native_active
        assert pool.crossings == 0

        for i in range(150):
            clock[0] += 16
            for idx in range(len(refs)):
                refs[idx].add_local_input(idx % 2, (i + idx) % 16)
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            ref_reqs = []
            for s in refs:
                r = s.advance_frame()
                fulfill_saves(r)
                ref_reqs.append(r)
            pool_reqs = pool.advance_all()
            for r in pool_reqs:
                fulfill_saves(r)
            net_pool.tick()
            net_ref.tick()
            for idx in range(len(refs)):
                assert (
                    ref_builders[idx][1].sent == pool_builders[idx][1].sent
                ), f"tick {i} session {idx}: fallback wire divergence"
                assert_requests_equal(
                    ref_reqs[idx], pool_reqs[idx], f"tick {i} s{idx}"
                )
                assert refs[idx].events() == pool.events(idx)
                assert refs[idx].current_frame == pool.current_frame(idx)
        assert pool.crossings == 0  # no native crossings on the fallback

    def test_ineligible_shapes_fall_back(self):
        """Session shapes outside the bank's mechanism must use the Python
        sessions even when the native library is present; desync detection
        is inside it."""
        from ggrs_tpu.core.types import DesyncDetection

        def make(builder_tweak):
            clock = [0]
            net = InMemoryNetwork()
            pool = HostSessionPool()
            names = ("X", "Y")
            for me in (0, 1):
                b = (
                    SessionBuilder(Config.for_uint(16))
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                b = builder_tweak(b)
                pool.add_session(b, net.socket(names[me]))
            return pool

        assert not make(lambda b: b.with_sparse_saving_mode(True)).native_active
        assert not make(lambda b: b.with_max_prediction_window(0)).native_active
        # desync detection runs inside the bank since PR 35 (DESIGN.md §4):
        # a pool whose builders have it on is served by the native tier
        assert make(
            lambda b: b.with_desync_detection_mode(DesyncDetection.on(100))
        ).native_active
        assert not make(lambda b: b.with_sync_handshake(True)).native_active

    def test_empty_pool_is_a_noop(self):
        pool = HostSessionPool()
        assert not pool.native_active
        assert pool.advance_all() == []

    def test_observables_readable_before_first_tick(self, monkeypatch):
        """A P2PSession's state is readable right after construction; the
        pool's accessors must finalize lazily rather than crash (both
        paths)."""
        for native in (False, True):
            if not native:
                monkeypatch.setattr(_native, "bank_lib", lambda: None)
            net = InMemoryNetwork()
            pool = HostSessionPool()
            names = ("X", "Y")
            for me in (0, 1):
                b = (
                    SessionBuilder(Config.for_uint(16))
                    .with_clock(lambda: 0)
                    .with_rng(random.Random(me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                pool.add_session(b, net.socket(names[me]))
            assert pool.current_frame(0) == 0
            assert pool.last_confirmed_frame(1) == -1
            assert pool.frames_ahead(0) == 0
            assert pool.events(0) == []
            monkeypatch.undo()

    def test_mixed_timebases_fall_back(self):
        """A frozen test clock pooled with a real-time clock cannot share
        the bank's single per-tick clock read: per-session fallback."""
        from ggrs_tpu.net.protocol import monotonic_ms

        net = InMemoryNetwork()
        pool = HostSessionPool()
        names = ("X", "Y")
        clocks = (lambda: 0, monotonic_ms)
        for me in (0, 1):
            b = (
                SessionBuilder(Config.for_uint(16))
                .with_clock(clocks[me])
                .with_rng(random.Random(me))
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            pool.add_session(b, net.socket(names[me]))
        assert not pool.native_active

    def test_variable_size_inputs_fall_back(self):
        clock = [0]
        net = InMemoryNetwork()
        pool = HostSessionPool()
        names = ("X", "Y")
        for me in (0, 1):
            b = (
                SessionBuilder(Config.for_bytes())
                .with_clock(lambda: clock[0])
                .with_rng(random.Random(me))
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            pool.add_session(b, net.socket(names[me]))
        assert not pool.native_active
        # and it actually runs
        for i in range(20):
            clock[0] += 16
            pool.add_local_input(0, 0, bytes([i % 7]))
            pool.add_local_input(1, 1, bytes([i % 5, 1]))
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
        assert pool.current_frame(0) > 10


class TestHostedPool:
    def test_bank_feeds_batched_executor(self):
        """The full two-crossings-per-tick stack: HostSessionPool request
        lists straight into a BatchedRequestExecutor, states advancing and
        matching a per-session NumPy replay of the same inputs."""
        import numpy as np

        from ggrs_tpu.games import BoxGame, boxgame_config
        from ggrs_tpu.parallel import BatchedRequestExecutor, HostedPool

        game = BoxGame(2)
        clock = [0]
        net = InMemoryNetwork(latency_ticks=1)
        host = HostSessionPool()
        n_matches = 3
        for m in range(n_matches):
            names = (f"A{m}", f"B{m}")
            for me in (0, 1):
                b = (
                    SessionBuilder(boxgame_config())
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(7 * m + me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                host.add_session(b, net.socket(names[me]))

        executor = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
            batch_size=len(host), ring_length=10, max_burst=9,
            with_checksums=False,
        )
        executor.warmup(np.zeros((2,), np.uint8))
        hosted = HostedPool(host, executor)

        def sched(i, idx):
            return ((i + idx) // (2 + idx % 3)) % 16

        TICKS = 60
        for i in range(TICKS):
            clock[0] += 16
            hosted.tick([
                (idx, idx % 2, sched(i, idx)) for idx in range(len(host))
            ])
            net.tick()
        hosted.block_until_ready()
        for idx in range(len(host)):
            assert host.current_frame(idx) >= TICKS - 16
        # every session's live device state exists and has the right shape
        st = executor.live_state(0)
        assert set(st) == set(game.init_state_np())

    def test_size_mismatch_refused(self):
        from ggrs_tpu.games import BoxGame, boxgame_config
        from ggrs_tpu.parallel import BatchedRequestExecutor, HostedPool
        import numpy as np

        game = BoxGame(2)
        host = HostSessionPool()
        net = InMemoryNetwork()
        b = (
            SessionBuilder(boxgame_config())
            .with_rng(random.Random(0))
            .add_player(Local(), 0)
            .add_player(Remote("peer"), 1)
        )
        host.add_session(b, net.socket("me"))
        executor = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
            batch_size=4, ring_length=10, max_burst=9,
        )
        with pytest.raises(ValueError):
            HostedPool(host, executor)


@needs_native
class TestOutputBufferGrowth:
    def test_undersized_buffer_recovers_without_poisoning(self):
        """kErrBufferTooSmall is a grow-and-fetch, not a poisoned pool: the
        tick's output is retained natively (a stalled peer's whole-window
        retransmit volley must not kill all B matches)."""
        import ctypes

        clock = [0]
        net = InMemoryNetwork(latency_ticks=1)
        pool = HostSessionPool()
        for b, s in two_peer_builders(net, clock, n_matches=2):
            pool.add_session(b, s)
        assert pool.native_active

        def tick(i):
            clock[0] += 16
            for idx in range(len(pool)):
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            out = pool.advance_all()
            for reqs in out:
                fulfill_saves(reqs)
            net.tick()
            return out

        for i in range(10):
            tick(i)
        # sabotage: shrink the output buffer below any tick's record size
        pool._out_buf = ctypes.create_string_buffer(8)
        out = tick(10)  # grow-and-fetch path
        assert len(out) == len(pool)
        assert len(pool._out_buf) > 8
        for i in range(11, 30):
            tick(i)  # and the pool keeps running, not poisoned
        assert all(pool.current_frame(i) >= 20 for i in range(len(pool)))


@needs_native
class TestDisconnect:
    def test_silent_peer_disconnects_and_session_continues(self):
        """A peer that goes silent: NetworkInterrupted then Disconnected
        fire from the bank's timers, the disconnect rollback erases its
        predictions, and the session keeps advancing on dummy inputs.
        (Reactions apply one pool tick late on the native path — a
        documented divergence — so this asserts behavior, not bit parity.)
        """
        clock = [0]
        net = InMemoryNetwork(latency_ticks=1)
        pool = HostSessionPool()
        names = ("L", "R")
        b = (
            SessionBuilder(Config.for_uint(16))
            .with_clock(lambda: clock[0])
            .with_rng(random.Random(1))
            .with_disconnect_timeout(400)
            .with_disconnect_notify_delay(100)
            .add_player(Local(), 0)
            .add_player(Remote(names[1]), 1)
        )
        pool.add_session(b, net.socket(names[0]))
        assert pool.native_active

        peer_b = (
            SessionBuilder(Config.for_uint(16))
            .with_clock(lambda: clock[0])
            .with_rng(random.Random(2))
            .with_disconnect_timeout(400)
            .with_disconnect_notify_delay(100)
            .add_player(Local(), 1)
            .add_player(Remote(names[0]), 0)
        )
        peer = peer_b.start_p2p_session(net.socket(names[1]))

        events = []
        state = [0]

        def tick(i, drive_peer):
            clock[0] += 16
            if drive_peer:
                peer.add_local_input(1, i % 16)
                fulfill_saves(peer.advance_frame())
            pool.add_local_input(0, 0, i % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            events.extend(pool.events(0))
            net.tick()

        for i in range(40):
            tick(i, drive_peer=True)
        frame_at_silence = pool.current_frame(0)
        for i in range(40, 120):
            tick(i, drive_peer=False)

        kinds = [type(e).__name__ for e in events]
        assert "NetworkInterrupted" in kinds
        assert "Disconnected" in kinds
        # after the disconnect the session runs free on dummy inputs
        assert pool.current_frame(0) > frame_at_silence + 40
