"""Pins for the vectorized policy plane (DESIGN.md §19).

The host bank's tick output now leads with a packed per-slot header and
``HostSessionPool`` classifies all B slots from it, fast-pathing quiet
slots through pooled requests without a positional body parse.  Everything
here pins that path bit-identical to the legacy per-slot parser (the
reference decoder, forced via ``GGRS_TPU_NO_FASTPATH=1``): request values,
events, wire bytes, journal streams, frames — under seeded
loss/dup/reorder, on the event-heavy blackout path, and across the
eviction/export seams — and every slot's flight recorder (the wire taps
the fast leg takes from the tick's outbound table), on the per-datagram
and the batched send tiers, through send faults.  Plus: the crossing budget is untouched (still one
tick crossing + one stats crossing per pool tick), the fast path actually
engages, the B=256 scrape stays allocation-free (tracemalloc), and the
supervision transition feed drains incrementally.
"""

from __future__ import annotations

import os
import random
import tracemalloc

import pytest

from ggrs_tpu.core import Local, Remote
from ggrs_tpu.core.config import Config
from ggrs_tpu.net import InMemoryNetwork, _native
from ggrs_tpu.obs.registry import Registry
from ggrs_tpu.parallel.host_bank import HostSessionPool
from ggrs_tpu.sessions import SessionBuilder

from test_session_bank import (  # noqa: E402  (pytest rootdir path)
    RecordingSocket,
    assert_requests_equal,
    fulfill_saves,
    needs_native,
    two_peer_builders,
)


def _make_pool(builders, fastpath: bool, metrics=None):
    """Build + finalize one pool with the vectorized path on or off (the
    env flag is read at finalization)."""
    prev = os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
    if not fastpath:
        os.environ["GGRS_TPU_NO_FASTPATH"] = "1"
    try:
        pool = HostSessionPool(metrics=metrics)
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active, "native bank did not engage"
    finally:
        os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
        if prev is not None:
            os.environ["GGRS_TPU_NO_FASTPATH"] = prev
    assert pool._vectorized == fastpath
    return pool


class BatchRecordingSocket(RecordingSocket):
    """A RecordingSocket on the batched send tier: it offers
    ``send_datagram_batch`` (the fast leg's one call a slot a tick) and
    ``send_datagram`` (the reference leg's call a datagram), and records
    what both send in the same form."""

    def send_datagram(self, data, addr):
        self.sent.append((addr, bytes(data)))
        self.inner.send_datagram(data, addr)

    def send_datagram_batch(self, items):
        for data, addr in items:
            self.send_datagram(data, addr)


class RawBomb(RecordingSocket):
    """Per-datagram tier; once armed, the first datagram sent raises."""

    armed = False

    def send_to(self, msg, addr):
        if self.armed:
            self.armed = False
            raise OSError("boom")
        super().send_to(msg, addr)


class BatchBomb(BatchRecordingSocket):
    """Batched tier; once armed, the next input message raises before it
    is sent.  With one remote endpoint the input message is the last
    datagram of its slot's tick, so the fast leg, which taps the whole
    slot before its one batched call, taps what the reference leg taps
    before its failing call."""

    armed = False

    def send_datagram(self, data, addr):
        if self.armed and data[2] == 0:  # u16 magic, then the input tag
            self.armed = False
            raise OSError("boom")
        super().send_datagram(data, addr)


_SOCKETS = {"raw": (RecordingSocket, RawBomb),
            "batch": (BatchRecordingSocket, BatchBomb)}


def assert_recorders_equal(pool_a, pool_b, where):
    """Every slot's flight recorder reads the same on both legs: events,
    dump, the count recorded and the count retained."""
    for idx in range(len(pool_a)):
        ra, rb = pool_a.flight_recorder(idx), pool_b.flight_recorder(idx)
        assert ra.recorded == rb.recorded, f"{where} slot {idx}"
        assert len(ra) == len(rb), f"{where} slot {idx}"
        assert ra.events() == rb.events(), f"{where} slot {idx}"
        assert ra.dump(last=len(ra)) == rb.dump(last=len(rb))


def _drive_both(faults, ticks, n_matches=3, journals=None, blackout=None,
                scrape_every=0, sockets="raw", bomb=None, sparse=False):
    """Drive a vectorized and a legacy pool with identical seeded traffic;
    compare requests, events, frames, wire bytes and flight recorders
    every tick.  ``sockets`` picks the send tier (``_SOCKETS``); ``bomb``
    ``(tick, slot)`` arms that slot's socket to fail a send at that tick;
    ``sparse`` builds sessions that save sparsely.
    Returns (fast_pool, legacy_pool, events seen)."""
    clock = [0]
    net_a = InMemoryNetwork(**faults)
    net_b = InMemoryNetwork(**faults)
    builders_a = two_peer_builders(net_a, clock, n_matches, sparse=sparse)
    builders_b = two_peer_builders(net_b, clock, n_matches, sparse=sparse)
    plain, fused = _SOCKETS[sockets]
    for builders in (builders_a, builders_b):
        for k, (b, s) in enumerate(builders):
            kind = fused if bomb is not None and k == bomb[1] else plain
            builders[k] = (b, kind(s.inner))
    pool_a = _make_pool(builders_a, fastpath=True)
    pool_b = _make_pool(builders_b, fastpath=False)
    if journals is not None:
        from ggrs_tpu.broadcast.hub import SpectatorHub

        hub_a = SpectatorHub(pool_a)
        hub_b = SpectatorHub(pool_b)
        (ja, jb) = journals
        hub_a.attach_journal(0, ja)
        hub_b.attach_journal(0, jb)
    n = len(builders_a)
    saw_events = 0
    for i in range(ticks):
        dark = blackout is not None and i in blackout
        if dark:
            # starve the liveness timers: big clock steps with NO packet
            # delivery below — interrupt (then resume) events, retries,
            # the event-heavy slow path
            clock[0] += 300
        clock[0] += 16
        if bomb is not None and i == bomb[0]:
            builders_a[bomb[1]][1].armed = True
            builders_b[bomb[1]][1].armed = True
        for idx in range(n):
            v = (i + idx) % 16
            if pool_a.slot_state(idx) in ("dead", "migrated"):
                continue
            pool_a.add_local_input(idx, idx % 2, v)
            pool_b.add_local_input(idx, idx % 2, v)
        reqs_a = pool_a.advance_all()
        reqs_b = pool_b.advance_all()
        if scrape_every and i % scrape_every == 0:
            pool_a.scrape()
            pool_b.scrape()
        for idx in range(n):
            assert_requests_equal(
                reqs_b[idx], reqs_a[idx], f"tick {i} slot {idx}"
            )
            fulfill_saves(reqs_a[idx])
            fulfill_saves(reqs_b[idx])
        if not dark:
            net_a.tick()
            net_b.tick()
        for idx in range(n):
            ev_a = pool_a.events(idx)
            saw_events += len(ev_a)
            assert ev_a == pool_b.events(idx), (
                f"tick {i} slot {idx}: events diverged"
            )
            assert pool_a.current_frame(idx) == pool_b.current_frame(idx)
            assert (
                pool_a.last_confirmed_frame(idx)
                == pool_b.last_confirmed_frame(idx)
            )
            sa = builders_a[idx][1].sent
            sb = builders_b[idx][1].sent
            assert sa == sb, (
                f"tick {i} slot {idx}: wire bytes diverged "
                f"({len(sa)} vs {len(sb)} datagrams)"
            )
        assert_recorders_equal(pool_a, pool_b, f"tick {i}")
    return pool_a, pool_b, saw_events


@needs_native
class TestVectorizedParity:
    @pytest.mark.parametrize("seed,sockets,bomb", [
        (11, "raw", None), (23, "raw", None),
        (11, "batch", None), (23, "batch", None),
        (11, "raw", (70, 1)), (23, "batch", (70, 2)),
    ], ids=["11", "23", "11-batch", "23-batch", "11-raw-fault",
            "23-batch-fault"])
    def test_fuzzed_traffic_bit_identical(self, seed, sockets, bomb):
        """Seeded loss/dup/reorder: the vectorized decode is bit-identical
        to the legacy per-slot parser — flight recorders included, past
        the point where each ring drops its oldest, with slow slots among
        the fast ones, on both send tiers and through a send fault — and
        the fast path actually served slots (the quiet majority)."""
        rng = random.Random(seed)
        faults = dict(
            loss=0.08, duplicate=0.04, reorder=0.15,
            seed=rng.randrange(1 << 30),
        )
        pool_a, pool_b, _ = _drive_both(faults, ticks=180, sockets=sockets,
                                        bomb=bomb)
        assert pool_a.fast_slot_ticks > 0, "fast path never engaged"
        assert pool_b.fast_slot_ticks == 0, "legacy leg took the fast path"
        assert pool_a.desc_slow_slots > 0, "no slow slot among the fast"
        assert max(pool_a.flight_recorder(i).recorded
                   for i in range(len(pool_a))) > 256, "no ring dropped"
        if bomb is not None:
            assert pool_a.slot_state(bomb[1]) != "native"
            assert "boom" in pool_a.fault_log(bomb[1])[0].detail

    @pytest.mark.parametrize("seed,sockets,bomb", [
        (11, "raw", None), (23, "batch", (70, 2)),
    ], ids=["11", "23-batch-fault"])
    def test_sparse_saving_bit_identical(self, seed, sockets, bomb):
        """Sessions that save sparsely: the fast plane's advance-only and
        sparse resim rows decode to what the legacy parser reads, through
        a send fault on a slot whose staged inputs it re-feeds."""
        faults = dict(loss=0.08, duplicate=0.04, reorder=0.15,
                      seed=random.Random(seed).randrange(1 << 30))
        pool_a, pool_b, _ = _drive_both(faults, ticks=180, sockets=sockets,
                                        bomb=bomb, sparse=True)
        assert pool_a.fast_slot_ticks > 0, "fast path never engaged"
        assert pool_b.fast_slot_ticks == 0, "legacy leg took the fast path"
        if bomb is not None:
            assert pool_a.slot_state(bomb[1]) != "native"

    def test_event_heavy_blackout_path(self):
        """Clock-jump blackouts force interrupt/resume events and retry
        storms: the event (slow) path of the vectorized decoder, pinned
        against the reference under the same schedule."""
        pool_a, _, saw_events = _drive_both(
            dict(), ticks=120, blackout={40, 41, 42, 80}
        )
        # the blackout actually produced protocol events (the slow path),
        # and the rest of the run stayed on the fast path
        assert saw_events > 0, "blackout produced no events"
        assert 0 < pool_a.fast_slot_ticks < 120 * len(pool_a._slot_state)

    def test_journal_streams_bit_identical(self, tmp_path):
        """The journal tap rides the fast path (kHdrConf): both pools'
        journal files must be byte-identical."""
        from ggrs_tpu.broadcast.journal import MatchJournal

        cfg_players, isize = 2, Config.for_uint(16).native_input_size
        ja = MatchJournal(tmp_path / "a.journal", cfg_players, isize)
        jb = MatchJournal(tmp_path / "b.journal", cfg_players, isize)
        pool_a, _, _ = _drive_both(dict(loss=0.05, seed=7), ticks=100,
                                   journals=(ja, jb))
        assert pool_a.fast_slot_ticks > 0
        ja.close()
        jb.close()
        a = (tmp_path / "a.journal").read_bytes()
        b = (tmp_path / "b.journal").read_bytes()
        assert a == b and len(a) > 0, "journal streams diverged"

    def test_export_bundle_identical_after_quiet_run(self):
        """Migration continuity: after a long quiet run (stale Python
        mirrors on the fast leg), the export bundle — which now reads the
        harvest's peer mirrors — matches the legacy pool's exactly."""
        pool_a, pool_b, _ = _drive_both(dict(), ticks=90, n_matches=2)
        for slot in range(2):
            ba = pool_a.export_resume_state(slot)
            bb = pool_b.export_resume_state(slot)
            assert ba == bb, f"slot {slot}: export bundles diverged"
            assert ba["endpoints"][0]["peer_last"] == (
                bb["endpoints"][0]["peer_last"]
            )

    def test_export_bundle_materializes_pending_events(self):
        """A bundle exported while lazily-staged events sit undrained must
        carry real GgrsEvent objects — the destination session's queue is
        extended verbatim and its consumer does isinstance checks."""
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, 1)
        pool = _make_pool(builders, fastpath=True)
        n = len(builders)
        for i in range(40):
            dark = 20 <= i < 24
            if dark:
                clock[0] += 300  # starved liveness: interrupt events
            clock[0] += 16
            for idx in range(n):
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            if not dark:
                net.tick()
        # deliberately NOT drained via events(): export with a live queue
        assert any(pool._mirrors[i].event_queue for i in range(n)), (
            "blackout produced no staged events — test setup broken"
        )
        for i in range(n):
            for ev in pool.export_resume_state(i)["pending_events"]:
                assert not isinstance(ev, tuple), (
                    f"raw lazy tuple leaked into the export bundle: {ev!r}"
                )

    def test_crossing_budget_unchanged(self):
        """Still exactly one tick crossing per pool tick and one stats
        crossing per scraped tick on the vectorized path."""
        pool_a, _, _ = _drive_both(dict(), ticks=60, scrape_every=1)
        assert pool_a.crossings == 60
        assert pool_a.stat_crossings == 60
        assert pool_a.harvests == 0


@needs_native
class TestIncrementalSupervision:
    def _pool(self, n_matches=2, **kw):
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, n_matches)
        pool = HostSessionPool(metrics=Registry(), **kw)
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        return pool, builders, net, clock

    def _tick(self, pool, net, clock, i, n):
        clock[0] += 16
        for idx in range(n):
            pool.add_local_input(idx, idx % 2, (i + idx) % 16)
        for reqs in pool.advance_all():
            fulfill_saves(reqs)
        net.tick()

    def test_transition_feed_drains_incrementally(self):
        pool, builders, net, clock = self._pool()
        n = len(builders)
        for i in range(10):
            self._tick(pool, net, clock, i, n)
        assert pool.drain_state_transitions() == []
        pool.inject_slot_error(1)
        for i in range(10, 30):
            self._tick(pool, net, clock, i, n)
        feed = pool.drain_state_transitions()
        assert feed and feed[0][0] == 1
        assert [t[2] for t in feed][:2] == ["quarantined", "evicted"]
        assert pool.drain_state_transitions() == []
        # and the attention set holds exactly the evicted slot
        assert pool._attention == {1}

    def test_evicted_session_is_pooled_and_ticks(self):
        pool, builders, net, clock = self._pool()
        n = len(builders)
        for i in range(8):
            self._tick(pool, net, clock, i, n)
        pool.inject_slot_error(0)
        for i in range(8, 40):
            self._tick(pool, net, clock, i, n)
        assert pool.slot_state(0) == "evicted"
        session = pool._evicted[0]
        assert session._pooled_list is not None, (
            "evicted session did not take the pooled-request path"
        )
        assert pool.current_frame(0) > 8  # it resumed and advances


@needs_native
class TestPooledSessionParity:
    def test_pooled_requests_value_identical(self):
        """P2PSession.enable_request_pooling changes object lifetimes, not
        values: two identically-seeded matches, one pooled, compare every
        tick's requests/events/frames."""
        def build(pool_requests):
            clock = [0]
            net = InMemoryNetwork(loss=0.05, reorder=0.1, seed=99)
            sessions = []
            for me in (0, 1):
                names = ("A", "B")
                b = (
                    SessionBuilder(Config.for_uint(16))
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(5 + me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                s = b.start_p2p_session(
                    RecordingSocket(net.socket(names[me]))
                )
                if pool_requests:
                    s.enable_request_pooling()
                sessions.append(s)
            return net, clock, sessions

        net_a, clock_a, plain = build(False)
        net_b, clock_b, pooled = build(True)
        for i in range(150):
            clock_a[0] += 16
            clock_b[0] += 16
            for me in (0, 1):
                plain[me].add_local_input(me, (i + me) % 16)
                pooled[me].add_local_input(me, (i + me) % 16)
            for me in (0, 1):
                ra = plain[me].advance_frame()
                rb = pooled[me].advance_frame()
                assert_requests_equal(ra, rb, f"tick {i} session {me}")
                fulfill_saves(ra)
                fulfill_saves(rb)
            net_a.tick()
            net_b.tick()
            for me in (0, 1):
                assert plain[me].events() == pooled[me].events()
                assert plain[me].current_frame == pooled[me].current_frame
                assert (
                    plain[me]._socket.sent == pooled[me]._socket.sent
                )


@needs_native
class TestScrapeAllocationB256:
    def test_b256_steady_state_is_allocation_free(self):
        """ISSUE 10 satellite: at B=256 the tick+scrape steady state must
        not grow the heap — the record dicts refill in place, the gauge
        setters are prebound, and the fast path reuses its pooled
        requests.  Measured with tracemalloc, filtered to this package."""
        clock = [0]
        net = InMemoryNetwork()
        # plain (non-recording) sockets: a RecordingSocket's unbounded
        # .sent list would dominate the measurement
        builders = []
        for m in range(128):  # 256 sessions
            names = (f"A{m}", f"B{m}")
            for me in (0, 1):
                b = (
                    SessionBuilder(Config.for_uint(16))
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(3 + 5 * m + me))
                    .add_player(Local(), me)
                    .add_player(Remote(names[1 - me]), 1 - me)
                )
                builders.append((b, net.socket(names[me])))
        # small flight-recorder rings so they FILL during warmup — the
        # measurement targets the scrape/decode steady state, not the
        # bounded one-time fill of 256 rings
        pool = HostSessionPool(metrics=Registry(), flight_recorder_size=8)
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        n = len(builders)

        def tick(i):
            clock[0] += 16
            for idx in range(n):
                pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            pool.scrape()
            net.tick()

        for i in range(12):  # warm: caches, prebinds, recorder rings
            tick(i)
        assert pool.fast_slot_ticks > 0
        tracemalloc.start()
        try:
            for i in range(12, 24):  # churn the bounded rings with
                tick(i)             # TRACKED objects before baselining
            snap1 = tracemalloc.take_snapshot()
            for i in range(24, 44):
                tick(i)
            snap2 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        # the flight recorder's ring is BOUNDED but churns (newest N
        # events replace oldest): tracemalloc attributes the live tail to
        # whichever window allocated it, which reads as spurious growth —
        # out of scope for this pin (the scrape/decode steady state)
        flt = [
            tracemalloc.Filter(True, "*ggrs_tpu*"),
            tracemalloc.Filter(False, "*obs/recorder.py"),
        ]
        growth = sum(
            s.size_diff
            for s in snap2.filter_traces(flt).compare_to(
                snap1.filter_traces(flt), "filename"
            )
        )
        # 20 ticks × 256 slots with per-tick scrapes: the steady state
        # must retain (almost) nothing — the bound is deliberately tight
        # relative to the ~500 dicts/tick the naive version allocated.
        # (The descriptor plane retains ONE RequestPlan — bounded, O(B),
        # replaced each tick — whose resim-row list varies with the
        # tick's rollback count; the slack above 64 KiB covers that
        # variance, nothing per-tick.)
        assert growth < 96 * 1024, (
            f"steady-state heap grew {growth} bytes over 20 scraped ticks"
        )
