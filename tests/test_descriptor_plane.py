"""Pins for the descriptor-plane quiet path (DESIGN.md §21).

Three tentpole surfaces, each pinned against the reference decoder
(``GGRS_TPU_NO_FASTPATH=1`` / per-call staging — the unchanged
semantics):

* **batched input staging** — ``HostSessionPool.stage_inputs`` routes all
  B local inputs through ONE ``ggrs_bank_stage_inputs`` crossing; wire
  bytes, requests, events and frames must be bit-identical to per-call
  ``add_local_input`` staging, the crossing budget must stay one tick +
  one stats crossing, and unconsumed staged inputs must survive into the
  harvest (eviction/export re-feed them);
* **request descriptor tables** — ``advance_all`` returns a lazy
  ``RequestPlan`` whose fast slots materialize pooled requests only on
  demand, while ``BatchedRequestExecutor`` consumes the flat descriptor
  columns directly (zero request objects): the device state must stay
  bit-identical to the materialized path under seeded loss/dup/reorder
  (which forces rollback-resim descriptors through ``_fill_resim``);
* **batched outbound** — non-attached fd-backed sockets flush the whole
  tick through one ``ggrs_net_send_table`` crossing; the peer-observed
  byte stream must match the per-datagram reference leg over real
  loopback UDP.

Plus the §21 satellite: the per-slot staging router (``_stagers``) is
precomputed at finalize and rebuilt on supervision transitions instead of
re-validating handle→slot mappings per call.
"""

from __future__ import annotations

import ctypes
import random
import socket as pysocket
import struct
import zlib

import numpy as np
import pytest

from ggrs_tpu.core import Local, Remote
from ggrs_tpu.core.config import Config
from ggrs_tpu.core.errors import InvalidRequest
from ggrs_tpu.net import InMemoryNetwork, _native
from ggrs_tpu.parallel.host_bank import HostSessionPool, RequestPlan
from ggrs_tpu.sessions import SessionBuilder

from test_session_bank import (  # noqa: E402  (pytest rootdir path)
    assert_requests_equal,
    fulfill_saves,
    needs_native,
    two_peer_builders,
)

needs_io = pytest.mark.skipif(
    _native.net_lib() is None,
    reason="kernel-batched socket datapath unavailable",
)


def _drive_pair(faults, ticks, n_matches=3, fault_at=None,
                scrape_every=0):
    """Two identically-seeded pools: pool A stages through the batched
    ``stage_inputs`` API, pool B through per-call ``add_local_input``.
    Compares requests, events, frames and wire bytes every tick; returns
    both pools."""
    clock = [0]
    net_a = InMemoryNetwork(**faults)
    net_b = InMemoryNetwork(**faults)
    builders_a = two_peer_builders(net_a, clock, n_matches)
    builders_b = two_peer_builders(net_b, clock, n_matches)
    pool_a, pool_b = HostSessionPool(), HostSessionPool()
    for b, s in builders_a:
        pool_a.add_session(b, s)
    for b, s in builders_b:
        pool_b.add_session(b, s)
    assert pool_a.native_active and pool_b.native_active
    n = len(builders_a)
    for i in range(ticks):
        clock[0] += 16
        pool_a.stage_inputs(
            [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
        )
        for idx in range(n):
            pool_b.add_local_input(idx, idx % 2, (i + idx) % 16)
        if fault_at is not None and i == fault_at:
            pool_a.inject_slot_error(0)
            pool_b.inject_slot_error(0)
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
        net_a.tick()
        net_b.tick()
        for idx in range(n):
            assert pool_a.events(idx) == pool_b.events(idx)
            assert pool_a.current_frame(idx) == pool_b.current_frame(idx)
            sa = builders_a[idx][1].sent
            sb = builders_b[idx][1].sent
            assert sa == sb, f"tick {i} slot {idx}: wire bytes diverged"
    return pool_a, pool_b


@needs_native
class TestBatchedStagingParity:
    @pytest.mark.parametrize("seed", [5, 31])
    def test_fuzzed_traffic_bit_identical(self, seed):
        """Batched native staging vs per-call staging: bit-identical wire
        bytes / requests / events / frames under seeded loss/dup/reorder,
        and the staged path actually went native (no inline dicts)."""
        rng = random.Random(seed)
        faults = dict(
            loss=0.08, duplicate=0.04, reorder=0.15,
            seed=rng.randrange(1 << 30),
        )
        pool_a, _ = _drive_pair(faults, ticks=180)
        assert pool_a.fast_slot_ticks > 0
        assert all(not m.staged_inputs for m in pool_a._mirrors), (
            "batched staging leaked into the inline dicts"
        )

    def test_crossing_budget_with_batched_staging(self):
        """stage_inputs is its OWN crossing (like the harvest): the tick
        budget stays exactly one tick + one stats crossing per pool
        tick."""
        pool_a, _ = _drive_pair(dict(), ticks=60, scrape_every=1)
        assert pool_a.crossings == 60
        assert pool_a.stat_crossings == 60
        assert pool_a.harvests == 0

    def test_eviction_with_native_staged_inputs(self):
        """A slot faulted while its inputs sit in the NATIVE staging
        buffer: the harvest's staged tail re-feeds them to the evicted
        session — bit-identical to the inline-staged reference leg."""
        pool_a, pool_b = _drive_pair(
            dict(latency_ticks=1), ticks=80, n_matches=2, fault_at=30
        )
        assert pool_a.slot_state(0) == "evicted"
        assert pool_b.slot_state(0) == "evicted"
        assert pool_a.current_frame(0) > 31, "evicted slot never resumed"

    def test_missing_input_raises_before_crossing(self):
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, 2)
        pool = HostSessionPool()
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        # stage only the first slot's input
        pool.stage_inputs([(0, 0, 3)])
        with pytest.raises(InvalidRequest, match="Missing local input"):
            pool.advance_all()

    def test_inline_staging_wins_over_stale_native(self):
        """Both mechanisms used for one slot in one tick: the inline dict
        wins and the native copy is dropped ON BOTH SIDES — the next
        all-native tick must not resurrect stale bytes."""
        clock = [0]
        net_a, net_b = InMemoryNetwork(), InMemoryNetwork()
        builders_a = two_peer_builders(net_a, clock, 1)
        builders_b = two_peer_builders(net_b, clock, 1)
        pool_a, pool_b = HostSessionPool(), HostSessionPool()
        for b, s in builders_a:
            pool_a.add_session(b, s)
        for b, s in builders_b:
            pool_b.add_session(b, s)
        # finalize BOTH pools at the same clock: endpoint timer seeds are
        # drawn at finalize time, and a one-tick skew shifts the quality
        # report schedule between the legs
        assert pool_a.native_active and pool_b.native_active
        n = len(builders_a)
        for i in range(30):
            clock[0] += 16
            if i == 5:
                # stage a WRONG value natively, then override inline with
                # the reference value: inline must win
                pool_a.stage_inputs(
                    [(idx, idx % 2, 15) for idx in range(n)]
                )
                for idx in range(n):
                    pool_a.add_local_input(idx, idx % 2, (i + idx) % 16)
            else:
                pool_a.stage_inputs(
                    [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
                )
            for idx in range(n):
                pool_b.add_local_input(idx, idx % 2, (i + idx) % 16)
            for idx, (ra, rb) in enumerate(
                zip(pool_a.advance_all(), pool_b.advance_all())
            ):
                assert_requests_equal(rb, ra, f"tick {i} slot {idx}")
                fulfill_saves(ra)
                fulfill_saves(rb)
            net_a.tick()
            net_b.tick()
            for idx in range(n):
                assert builders_a[idx][1].sent == builders_b[idx][1].sent

    def test_export_bundle_carries_native_staged_inputs(self):
        """Inputs staged natively but not yet consumed (no advance_all)
        ride the harvest's staged tail into the export bundle."""
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, 1)
        pool = HostSessionPool()
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        n = len(builders)
        for i in range(10):
            clock[0] += 16
            pool.stage_inputs(
                [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
            )
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            net.tick()
        # stage for the NEXT tick, then export before advancing
        pool.stage_inputs([(idx, idx % 2, 7) for idx in range(n)])
        cfg = builders[0][0]._config
        for idx in range(n):
            bundle = pool.export_resume_state(idx)
            staged = bundle["staged_inputs"]
            assert staged == {idx % 2: cfg.input_encode(7)}, (
                f"slot {idx}: staged tail missing from the bundle"
            )


@needs_native
class TestRequestPlan:
    def _pool(self, n_matches=2):
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, n_matches)
        pool = HostSessionPool()
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        return pool, builders, net, clock

    def _tick(self, pool, net, clock, i, fulfill=True):
        clock[0] += 16
        n = len(pool)
        pool.stage_inputs(
            [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
        )
        plan = pool.advance_all()
        if fulfill:
            for reqs in plan:
                fulfill_saves(reqs)
        net.tick()
        return plan

    def test_fast_slots_materialize_lazily(self):
        pool, builders, net, clock = self._pool()
        plan = None
        for i in range(20):
            plan = self._tick(pool, net, clock, i)
        assert isinstance(plan, RequestPlan)
        # a steady-state tick: every live slot deferred
        plan = self._tick(pool, net, clock, 20, fulfill=False)
        assert all(lst is None for lst in plan.lists), (
            "quiet slots were materialized at plan build"
        )
        # indexing materializes exactly that slot; requests_for is the
        # same surface
        reqs = plan[0]
        assert plan.lists[0] is reqs and plan.lists[1] is None
        assert pool.requests_for(0) is reqs
        for reqs in plan:
            fulfill_saves(reqs)

    def test_stale_plan_raises(self):
        pool, builders, net, clock = self._pool()
        n = len(pool)

        def quiet_tick(fulfill=True):
            # constant inputs: repeat-last predictions are always right,
            # so skipping one tick's save fulfillment cannot be loaded
            # back by a later rollback
            clock[0] += 16
            pool.stage_inputs([(idx, idx % 2, 7) for idx in range(n)])
            plan = pool.advance_all()
            if fulfill:
                for reqs in plan:
                    fulfill_saves(reqs)
            net.tick()
            return plan

        for _ in range(10):
            quiet_tick()
        plan = quiet_tick(fulfill=False)
        assert plan.lists[0] is None  # still deferred
        quiet_tick()
        with pytest.raises(InvalidRequest, match="stale"):
            plan[0]

    def test_plan_counters(self):
        pool, builders, net, clock = self._pool()
        for i in range(30):
            self._tick(pool, net, clock, i)
        assert pool.plan_ticks == 30
        assert pool.fast_slot_ticks > 0
        # tick 0 (frame-0 double save) is kReqOther → eager for all slots
        assert pool.desc_slow_slots >= len(pool)


@needs_native
class TestExecutorDescriptorParity:
    @pytest.mark.parametrize("faults", [
        dict(),
        dict(loss=0.08, duplicate=0.04, reorder=0.15, seed=77),
    ])
    def test_device_state_bit_identical(self, faults):
        """HostedPool with the bulk raw-input converter (descriptor
        consumption, zero request objects) vs the materialized reference:
        live device state and ring frame tags bit-identical after a
        faulted-traffic run (rollback resims included)."""
        import jax

        from ggrs_tpu.games import BoxGame
        from ggrs_tpu.parallel import BatchedRequestExecutor, HostedPool

        game = BoxGame(2)

        def to_arr(pairs):
            return np.asarray([p[0] for p in pairs], np.uint8)

        def raw_to_arr(blobs, statuses):
            # Config.for_uint(16): u16le blobs; values are 0..15 → byte 0
            return blobs[:, :, 0]

        def build(vector):
            clock = [0]
            net = InMemoryNetwork(**faults)
            builders = two_peer_builders(net, clock, 4)
            host = HostSessionPool()
            for b, s in builders:
                host.add_session(b, s)
            ex = BatchedRequestExecutor(
                game.advance, game.init_state(), to_arr,
                batch_size=len(builders), ring_length=10, max_burst=9,
                with_checksums=False,
                raw_inputs_to_array=raw_to_arr if vector else None,
            )
            ex.warmup(np.zeros((2,), np.uint8))
            return clock, net, host, ex, HostedPool(host, ex)

        ca, na, ha, ea, pa = build(True)
        cb, nb, hb, eb, pb = build(False)
        assert ha.native_active and hb.native_active
        n = len(ha)
        for i in range(150):
            ca[0] += 16
            cb[0] += 16
            items = [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
            pa.tick(items)
            pb.tick(items)
            na.tick()
            nb.tick()
        for la, lb in zip(
            jax.tree_util.tree_leaves(jax.device_get(ea.live_states)),
            jax.tree_util.tree_leaves(jax.device_get(eb.live_states)),
        ):
            np.testing.assert_array_equal(la, lb)
        np.testing.assert_array_equal(ea._host_frames, eb._host_frames)
        assert ha.fast_slot_ticks > 0
        # descriptor consumption means the plan's fast slots were never
        # materialized by the executor
        plan = ha._plan
        deferred = [
            i for i in range(n)
            if plan.live_l[i] and plan.lists[i] is None
        ]
        assert deferred, "executor materialized every fast slot"


@needs_native
class TestFlushFaultSuppression:
    def test_faulted_fast_slot_never_reaches_the_device(self):
        """A fast slot whose batched outbound flush fails fatally must be
        suppressed on the DEVICE too: pruned from the plan's quiet
        columns and routed through the eager rows, so the executor sees
        its (empty or supervise-replaced) list instead of dispatching the
        stale quiet program for a slot the pool just quarantined."""
        import jax
        import numpy as np

        from ggrs_tpu.games import BoxGame
        from ggrs_tpu.parallel import BatchedRequestExecutor, HostedPool

        class BombSocket:
            """FakeSocket wrapper whose batched flush explodes on cue.
            Exposes send_datagram_batch (so the slot takes the batched
            tier) but no fileno (so it never takes the native table)."""

            def __init__(self, inner):
                self.inner = inner
                self.explode = False

            def send_to(self, msg, addr):
                self.inner.send_to(msg, addr)

            def send_datagram(self, data, addr):
                if self.explode:
                    raise OSError("boom")
                self.inner.send_datagram(data, addr)

            def send_datagram_batch(self, items):
                if self.explode:
                    raise OSError("boom")
                self.inner.send_datagram_batch(items)

            def receive_all_datagrams(self):
                return self.inner.receive_all_datagrams()

            def receive_all_messages(self):
                return self.inner.receive_all_messages()

        game = BoxGame(2)

        def build(vector):
            clock = [0]
            net = InMemoryNetwork()
            builders = two_peer_builders(net, clock, 2)
            host = HostSessionPool()
            bombs = []
            for b, s in builders:
                sock = BombSocket(s.inner)  # unwrap the RecordingSocket
                bombs.append(sock)
                host.add_session(b, sock)
            ex = BatchedRequestExecutor(
                game.advance, game.init_state(),
                lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
                batch_size=len(builders), ring_length=10, max_burst=9,
                with_checksums=False,
                raw_inputs_to_array=(
                    (lambda blobs, statuses: blobs[:, :, 0])
                    if vector else None
                ),
            )
            ex.warmup(np.zeros((2,), np.uint8))
            return clock, net, host, ex, HostedPool(host, ex), bombs

        legs = [build(True), build(False)]
        assert all(leg[2].native_active for leg in legs)
        n = len(legs[0][2])
        for i in range(60):
            for clock, net, host, ex, hosted, bombs in legs:
                clock[0] += 16
                if i == 30:
                    bombs[0].explode = True  # fatal mid-run flush fault
                hosted.tick(
                    [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
                )
                net.tick()
        (ca, na, ha, ea, pa, _), (cb, nb, hb, eb, pb, _) = legs
        assert ha.fast_slot_ticks > 0
        assert ha.slot_state(0) != "native"  # the fault took slot 0 out
        assert ha.slot_state(0) == hb.slot_state(0)
        # the faulted slot's device history — suppression tick included —
        # must match the materialized reference leg bit-for-bit
        for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(ea.live_states)),
            jax.tree_util.tree_leaves(jax.device_get(eb.live_states)),
        ):
            np.testing.assert_array_equal(x, y)

    def test_reference_leg_send_fault_keeps_staged_inputs(self):
        """The reference decoder branch (GGRS_TPU_NO_FASTPATH) with
        NATIVE staging: a send fault on an advanced tick must rebuild
        the inline staged dict from the decoded advance (the bank's
        copy was consumed by the trailing advance), so eviction stays
        fed instead of raising Missing-local-input."""
        import os

        class Bomb:
            """Single-shot: the FIRST send after arming fails, so the
            native slot faults but the evicted session's own resume
            sends succeed."""

            def __init__(self, inner):
                self.inner = inner
                self.explode = False

            def send_to(self, msg, addr):
                if self.explode:
                    self.explode = False
                    raise OSError("boom")
                self.inner.send_to(msg, addr)

            def receive_all_datagrams(self):
                return self.inner.receive_all_datagrams()

            def receive_all_messages(self):
                return self.inner.receive_all_messages()

        prev = os.environ.get("GGRS_TPU_NO_FASTPATH")
        os.environ["GGRS_TPU_NO_FASTPATH"] = "1"
        try:
            clock = [0]
            net = InMemoryNetwork()
            builders = two_peer_builders(net, clock, 1)
            pool = HostSessionPool()
            bombs = []
            for b, s in builders:
                sock = Bomb(s.inner)
                bombs.append(sock)
                pool.add_session(b, sock)
            assert pool.native_active and not pool._vectorized
            n = len(pool)
            for i in range(40):
                clock[0] += 16
                pool.stage_inputs(
                    [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
                )
                if i == 20:
                    bombs[0].explode = True
                for reqs in pool.advance_all():
                    fulfill_saves(reqs)
                net.tick()
            # pre-fix, the reconstructed dict was missing and the
            # same-tick eviction's session raised Missing-local-input
            # out of advance_all; post-fix the eviction consumed the
            # rebuilt inputs and the fallback session keeps advancing
            assert pool.slot_state(0) == "evicted"
            assert pool.current_frame(0) > 21
        finally:
            if prev is None:
                os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
            else:
                os.environ["GGRS_TPU_NO_FASTPATH"] = prev


@needs_native
class TestStagerRouter:
    def test_foreign_handle_raises(self):
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, 1)
        pool = HostSessionPool()
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        with pytest.raises(InvalidRequest, match="local player"):
            pool.add_local_input(0, 1, 3)  # handle 1 is slot 0's REMOTE

    def test_router_rebuilt_on_transitions(self):
        """The per-slot stager is precomputed and swapped on supervision
        transitions: after eviction the dispatch goes to the evicted
        session; after death it drops."""
        clock = [0]
        net = InMemoryNetwork()
        builders = two_peer_builders(net, clock, 2)
        pool = HostSessionPool()
        for b, s in builders:
            pool.add_session(b, s)
        assert pool.native_active
        n = len(pool)

        def tick(i):
            clock[0] += 16
            for idx in range(n):
                if pool.slot_state(idx) not in ("dead", "migrated"):
                    pool.add_local_input(idx, idx % 2, (i + idx) % 16)
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            net.tick()

        for i in range(8):
            tick(i)
        native_stager = pool._stagers[0]
        pool.inject_slot_error(0)
        for i in range(8, 30):
            tick(i)
        assert pool.slot_state(0) == "evicted"
        assert pool._stagers[0] is not native_stager
        assert (
            pool._stagers[0].__self__ is pool._evicted[0]
        ), "evicted slot's stager is not the session's add_local_input"
        # a released slot drops inputs silently (nothing ticks for it)
        pool.release_slot(1)
        pool.add_local_input(1, 1, 9)  # must not raise


@needs_io
class TestSendTable:
    def test_order_content_and_fatal_isolation(self):
        """ggrs_net_send_table direct unit: datagrams arrive in record
        order per fd; a fatal record (bogus fd) reports its index+errno
        while OTHER fds' runs still flush."""
        lib = _native.net_lib()
        tx_a = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_DGRAM)
        tx_b = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_DGRAM)
        rx = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(2.0)
        port = rx.getsockname()[1]
        ip = int.from_bytes(pysocket.inet_aton("127.0.0.1"), "little")
        payload = b"".join(
            bytes([i]) * (10 + i) for i in range(6)
        )
        offs = np.cumsum([0] + [10 + i for i in range(5)])
        bogus_fd = 10_000  # EBADF: deterministic fatal
        rows = [
            # fd A: two datagrams, then fd BOGUS, then fd B: three
            (tx_a.fileno(), 0, 10),
            (tx_a.fileno(), 1, 11),
            (bogus_fd, 2, 12),
            (tx_b.fileno(), 3, 13),
            (tx_b.fileno(), 4, 14),
        ]
        desc = np.empty(len(rows), np.dtype(list(_native.NET_SEND_FIELDS)))
        for k, (fd, idx, _ln) in enumerate(rows):
            desc[k] = (fd, ip, port, 0, offs[idx], 10 + idx)
        stats3 = (ctypes.c_uint64 * _native.NET_SEND_STATS)()
        fatal = (ctypes.c_int32 * 32)()
        rc = lib.ggrs_net_send_table(
            desc.ctypes.data, len(rows), payload, len(payload),
            stats3, fatal, 16,
        )
        assert rc == 1, f"expected exactly one fatal record, got {rc}"
        assert fatal[0] == 2  # the bogus-fd record's index
        assert fatal[1] != 0  # its errno (EBADF)
        got = [rx.recv(2048) for _ in range(4)]
        want = [
            payload[offs[i] : offs[i] + 10 + i] for i in (0, 1, 3, 4)
        ]
        assert sorted(got) == sorted(want)
        # per-fd order is preserved (different fds may interleave)
        a_got = [g for g in got if g in want[:2]]
        b_got = [g for g in got if g in want[2:]]
        assert a_got == want[:2] and b_got == want[2:]
        assert int(stats3[0]) == 4
        for s in (tx_a, tx_b, rx):
            s.close()

    def test_pool_outbound_rides_send_table_bit_identical(self):
        """A non-attached UDP pool's outbound goes through the one-
        crossing send table (descriptor leg) — the peer-observed byte
        stream, and the pool's flight recorder past the point where its
        ring drops the oldest, must equal the per-datagram reference leg
        (GGRS_TPU_NO_FASTPATH), through a transient send failure (the
        datagram counts as lost on both legs)."""
        import errno
        import os

        from ggrs_tpu.net.sockets import UdpNonBlockingSocket

        cfg = Config.for_uint(16)
        lib = _native.net_lib()
        drop_at = 60

        class DroppingSocket(UdpNonBlockingSocket):
            """The reference leg's twin of the table's injected transient
            errno: once armed, the next datagram is lost as a transient
            send error would lose it."""

            armed = False

            def send_datagram(self, data, addr):
                if self.armed:
                    self.armed = False
                    self.stats.send_errors += 1
                    return
                super().send_datagram(data, addr)

        class TeeSocket:
            """Records every datagram the peer RECEIVES (the pool's
            outbound as observed on the wire) without stealing them."""

            def __init__(self, inner):
                self.inner = inner
                self.tape = []

            def send_to(self, msg, addr):
                self.inner.send_to(msg, addr)

            def send_datagram(self, data, addr):
                self.inner.send_datagram(data, addr)

            def receive_all_datagrams(self):
                got = self.inner.receive_all_datagrams()
                self.tape.extend(data for _, data in got)
                return got

            def receive_all_messages(self):
                return self.inner.receive_all_messages()

        def leg(fastpath: bool):
            prev = os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
            if not fastpath:
                os.environ["GGRS_TPU_NO_FASTPATH"] = "1"
            try:
                clock = [0]
                pool = HostSessionPool()
                host_sock = DroppingSocket(0)
                peer_inner = UdpNonBlockingSocket(0)
                peer_sock = TeeSocket(peer_inner)
                peer_addr = ("127.0.0.1", peer_inner.local_port())
                host_addr = ("127.0.0.1", host_sock.local_port())
                b = (
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(11))
                    .add_player(Local(), 0)
                    .add_player(Remote(peer_addr), 1)
                )
                pool.add_session(b, host_sock)
                peer = (
                    SessionBuilder(cfg)
                    .with_clock(lambda: clock[0])
                    .with_rng(random.Random(12))
                    .add_player(Local(), 1)
                    .add_player(Remote(host_addr), 0)
                ).start_p2p_session(peer_sock)
                assert pool.native_active
                if fastpath:
                    assert pool._send_fds[0] is not None, (
                        "send table did not engage for a plain UDP socket"
                    )
                for i in range(200):
                    clock[0] += 16
                    # the peer polls first (loopback delivery of last
                    # tick's pool sends is already complete), then the
                    # pool ticks — the same lockstep both legs
                    peer.add_local_input(1, i % 16)
                    fulfill_saves(peer.advance_frame())
                    pool.stage_inputs([(0, 0, i % 16)])
                    if i == drop_at:
                        if fastpath:
                            # the tick's first send-table row fails
                            lib.ggrs_net_inject_table_errno(
                                errno.ENOBUFS, 0, 1)
                        else:
                            host_sock.armed = True
                    for reqs in pool.advance_all():
                        fulfill_saves(reqs)
                rec = pool.flight_recorder(0)
                return (list(peer_sock.tape), pool.current_frame(0),
                        (rec.events(), rec.dump(last=len(rec)),
                         rec.recorded, len(rec)))
            finally:
                lib.ggrs_net_inject_table_errno(0, 0, 0)
                os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
                if prev is not None:
                    os.environ["GGRS_TPU_NO_FASTPATH"] = prev

        ref_stream, ref_frame, ref_rec = leg(False)
        fast_stream, fast_frame, fast_rec = leg(True)
        assert fast_stream == ref_stream, (
            f"peer-observed streams diverged ({len(fast_stream)} vs "
            f"{len(ref_stream)} datagrams)"
        )
        assert fast_frame == ref_frame >= 180
        assert fast_rec == ref_rec
        assert ref_rec[2] > 256, "the recorder's ring never dropped"


def _walk_sections(out, n, players, isize):
    """The reference walk the outbound table replaces: every body record's
    two outbound sections, positionally, as table rows.  Returns the rows
    and where the body records end."""
    hdr_dtype = np.dtype(list(_native.BANK_HDR_FIELDS))
    hdr = np.frombuffer(out, hdr_dtype, count=n)
    pos = n * (hdr_dtype.itemsize + _native.BANK_REQ_STRIDE)
    rows = []
    for slot in range(n):
        start = pos
        (n_ops,) = struct.unpack_from("<H", out, pos + 33)
        p = pos + 35
        for _ in range(n_ops):
            p += 1 + (players * (1 + isize) if out[p] == 2 else 8)
        for section in (0, 1):
            (n_out,) = struct.unpack_from("<H", out, p)
            p += 2
            for _ in range(n_out):
                ep, dlen = struct.unpack_from("<HI", out, p)
                p += 6
                rows.append((slot, ep, section, p, dlen,
                             zlib.crc32(out[p : p + dlen])))
                p += dlen
        pos = start + int(hdr["rec_len"][slot])
    return rows, pos


@needs_native
class TestOutboundTable:
    @pytest.mark.parametrize("detect", [False, True],
                             ids=["plain", "detecting"])
    def test_rows_are_the_sections_walked(self, detect):
        """Over fuzzed ticks, traced so the timing tail rides too: the
        outbound table's rows are what a walk of every record's sections
        finds, their crc32 is zlib's, and the wanted tail (where the bank
        detects) and the timing tail still parse around the table."""
        from ggrs_tpu.core.types import DesyncDetection
        from ggrs_tpu.obs import default_tracer

        clock = [0]
        net = InMemoryNetwork(loss=0.08, duplicate=0.04, reorder=0.15,
                              seed=77)
        builders = two_peer_builders(net, clock, 3)
        pool = HostSessionPool()
        for b, s in builders:
            if detect:
                b = b.with_desync_detection_mode(DesyncDetection.on(5))
            pool.add_session(b, s)
        assert pool.native_active and pool._vectorized
        n = len(pool)
        m0 = pool._mirrors[0]
        wire_dtype = np.dtype(list(_native.BANK_WIRE_FIELDS))
        tracer = default_tracer()
        tracer.switch(False)
        tracer.clear()
        tracer.switch(True)
        seen = asked = 0
        try:
            for i in range(120):
                clock[0] += 16
                pool.stage_inputs(
                    [(idx, idx % 2, (i + idx) % 16) for idx in range(n)]
                )
                for reqs in pool.advance_all():
                    fulfill_saves(reqs)
                net.tick()
                out = bytes(pool._out_buf.raw[: pool._out_len.value])
                walked, pos = _walk_sections(
                    out, n, m0.num_players, m0.input_size
                )
                if detect:
                    (wanted,) = struct.unpack_from("<I", out, pos)
                    pos += 16 + 16 * wanted
                    asked += wanted
                (count,) = struct.unpack_from("<I", out, pos)
                table = np.frombuffer(out, wire_dtype, count=count,
                                      offset=pos + 4)
                assert table.tolist() == walked, f"tick {i}"
                end = n * (48 + 24) + int(np.frombuffer(
                    out, np.dtype(list(_native.BANK_HDR_FIELDS)), count=n
                )["rec_len"].sum())
                assert pool._wire_table(end).tolist() == walked
                phases, _ = pool._parse_timing_tail()
                assert [p for p, _ in phases] == list(_native.BANK_PHASES)
                assert pos + 4 + 19 * count + 8 * len(phases) + 9 == len(out)
                seen += count
        finally:
            tracer.switch(False)
            tracer.clear()
        assert seen > 2 * 120
        assert pool.desc_slow_slots > 0 and pool.fast_slot_ticks > 0
        assert (asked > 0) == detect  # wanted rows rode ahead of the table

    @needs_io
    def test_attached_slots_add_no_rows(self):
        """Slots whose sockets the bank drives itself send inside the
        crossing: their sections are empty and the table holds no row of
        theirs, while their datagrams still reach the peers."""
        from ggrs_tpu.net.sockets import UdpNonBlockingSocket

        cfg = Config.for_uint(16)
        clock = [0]
        pool = HostSessionPool(native_io=True)
        peers = []
        for m in range(2):
            host = UdpNonBlockingSocket(0)
            peer_sock = UdpNonBlockingSocket(0)
            peer_addr = ("127.0.0.1", peer_sock.local_port())
            pool.add_session(
                SessionBuilder(cfg).with_clock(lambda: clock[0])
                .with_rng(random.Random(3 + m))
                .add_player(Local(), 0).add_player(Remote(peer_addr), 1),
                host,
            )
            peers.append(
                SessionBuilder(cfg).with_clock(lambda: clock[0])
                .with_rng(random.Random(30 + m))
                .add_player(Local(), 1)
                .add_player(Remote(("127.0.0.1", host.local_port())), 0)
                .start_p2p_session(peer_sock)
            )
        assert pool.native_io_active
        for i in range(60):
            clock[0] += 16
            for peer in peers:
                peer.add_local_input(1, i % 16)
                fulfill_saves(peer.advance_frame())
            pool.stage_inputs([(m, 0, i % 16) for m in range(2)])
            for reqs in pool.advance_all():
                fulfill_saves(reqs)
            assert pool._wire_tick == (0, 0), f"tick {i}"
        assert pool.fast_slot_ticks > 0
        assert all(peer.current_frame > 50 for peer in peers)
