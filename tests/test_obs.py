"""Tests for the observability subsystem (ggrs_tpu.obs + the pool's
one-crossing stat harvest; DESIGN.md §12).

Four layers of pins:

1. the registry/recorder/exporter primitives (no native code needed);
2. metrics stay correct across the supervision state machine
   (quarantine -> eviction -> dead), driven through the real chaos
   harness;
3. the scrape budget: a scrape per tick adds zero tick crossings and
   exactly one ``ggrs_bank_stats`` crossing;
4. metrics are observational only: survivors' wire bytes are
   bit-identical with metrics enabled vs disabled; and
   ``HostSessionPool.network_stats`` returns the exact per-session
   ``NetworkStats`` for native, quarantined, and evicted slots.
"""

from __future__ import annotations

import random

import pytest

from ggrs_tpu.chaos import drive_chaos
from ggrs_tpu.core import Local, Remote
from ggrs_tpu.core.config import Config
from ggrs_tpu.core.errors import BadPlayerHandle, StatsUnavailable
from ggrs_tpu.net import InMemoryNetwork, _native
from ggrs_tpu.obs import (
    FlightRecorder,
    Registry,
    json_snapshot,
    prometheus_text,
)
from ggrs_tpu.parallel.host_bank import (
    EVICT_MAX_ATTEMPTS,
    HostSessionPool,
    SLOT_DEAD,
    SLOT_EVICTED,
    SLOT_NATIVE,
    SLOT_QUARANTINED,
)
from ggrs_tpu.sessions import SessionBuilder

needs_native = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)


# ---------------------------------------------------------------------------
# 1. registry / recorder / exporter primitives
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = Registry()
        c = reg.counter("c_total", "a counter")
        c.inc()
        c.inc(2)
        assert c.value == 3
        g = reg.gauge("g", "a gauge")
        g.set(5)
        g.dec()
        assert g.value == 4
        h = reg.histogram("h", "a histogram", buckets=(1, 4))
        for v in (0.5, 2, 3, 100):
            h.observe(v)
        assert h.count == 4 and h.sum == 105.5
        assert h.cumulative() == [(1, 1), (4, 3), (float("inf"), 4)]

    def test_labels(self):
        reg = Registry()
        fam = reg.counter("req_total", "requests", labels=("kind",))
        fam.labels(kind="save").inc(3)
        fam.labels(kind="load").inc()
        assert reg.value("req_total", kind="save") == 3
        assert reg.value("req_total", kind="load") == 1
        assert reg.value("req_total", kind="advance") is None
        with pytest.raises(ValueError):
            fam.labels(wrong="x")

    def test_idempotent_and_conflicting_registration(self):
        reg = Registry()
        a = reg.counter("x_total", "x")
        b = reg.counter("x_total", "x")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total", "now a gauge?")
        with pytest.raises(ValueError):
            reg.counter("x_total", "same kind, new labels", labels=("k",))

    def test_disabled_registry_is_null(self):
        reg = Registry(enabled=False)
        c = reg.counter("c_total")
        c.inc(100)
        g = reg.gauge("g", labels=("k",))
        g.labels(k="v").set(1)
        h = reg.histogram("h")
        h.observe(5)
        assert reg.families() == []
        assert prometheus_text(reg) == "\n"
        assert json_snapshot(reg) == {}


class TestExporters:
    def _reg(self):
        reg = Registry()
        reg.counter("ticks_total", "pool ticks").inc(7)
        fam = reg.gauge("state", "slots per state", labels=("state",))
        fam.labels(state="native").set(3)
        h = reg.histogram("depth", "rollback depth", buckets=(1, 2))
        h.observe(1)
        h.observe(5)
        return reg

    def test_prometheus_text(self):
        text = prometheus_text(self._reg())
        assert "# TYPE ticks_total counter" in text
        assert "ticks_total 7" in text
        assert 'state{state="native"} 3' in text
        assert 'depth_bucket{le="1"} 1' in text
        assert 'depth_bucket{le="+Inf"} 2' in text
        assert "depth_sum 6" in text
        assert "depth_count 2" in text

    def test_json_snapshot(self):
        snap = json_snapshot(self._reg())
        assert snap["ticks_total"]["samples"][0]["value"] == 7
        assert snap["state"]["samples"][0]["labels"] == {"state": "native"}
        hist = snap["depth"]["samples"][0]
        assert hist["count"] == 2 and hist["sum"] == 6

    def test_http_server_round_trip(self):
        import urllib.request

        from ggrs_tpu.obs import start_http_server

        try:
            server = start_http_server(self._reg(), port=0)
        except OSError:
            pytest.skip("cannot bind a loopback socket in this sandbox")
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            body = urllib.request.urlopen(url, timeout=5).read().decode()
            assert "ticks_total 7" in body
            url_json = f"http://127.0.0.1:{server.port}/metrics.json"
            body = urllib.request.urlopen(url_json, timeout=5).read().decode()
            assert '"ticks_total"' in body
        finally:
            server.close()


class TestFlightRecorder:
    def test_ring_bounds_and_dump(self):
        rec = FlightRecorder(capacity=8)
        for i in range(20):
            rec.record(i, "state", f"event {i}")
        assert len(rec) == 8
        assert rec.recorded == 20
        events = rec.events()
        assert events[0][0] == 12 and events[-1][0] == 19
        dump = rec.dump(4)
        assert "event 19" in dump and "event 15" not in dump

    def test_wire_tuples_format_lazily(self):
        rec = FlightRecorder()
        rec.record(3, "wire", (1, 53, 0xAB12CD34))
        assert "ep=1 len=53B crc=ab12cd34" in rec.dump()

    @pytest.mark.parametrize("capacity", [8, 1, 0])
    def test_columnar_taps_read_as_one_ring(self, capacity):
        """A recorder whose wire digests live in a pool's WireTaps store —
        one at a time or scattered a tick at once, more than the ring
        holds in one tick included — reads exactly what a recorder that
        keeps them in its own deque reads: events, dump, recorded, len."""
        import numpy as np

        from ggrs_tpu.obs.recorder import WireTaps

        taps = WireTaps(3, capacity)
        cols = FlightRecorder(capacity, taps=taps, slot=1)
        other = FlightRecorder(capacity, taps=taps, slot=2)
        plain = FlightRecorder(capacity)
        rng = random.Random(capacity)
        for tick in range(60):
            if rng.random() < 0.3:
                cols.record(tick, "rollback", f"load frame {tick}")
                plain.record(tick, "rollback", f"load frame {tick}")
            for _ in range(rng.randrange(3)):
                ep = rng.choice([0, 1, "spec0", "spec3"])
                n, crc = rng.randrange(1, 99), rng.getrandbits(32)
                cols.record_wire(tick, ep, n, crc)
                plain.record(tick, "wire", (ep, n, crc))
            k = rng.choice([0, 1, 2, 11])
            eps = [rng.randrange(3) for _ in range(k)]
            lens = [rng.randrange(1, 99) for _ in range(k)]
            crcs = [rng.getrandbits(32) for _ in range(k)]
            other.record(tick, "state", "a slot of its own")
            taps.scatter(
                np.array([1] * k + [2] * 2, np.intp), tick,
                np.array(eps + [0, 0]), np.array(lens + [5, 6], np.uint32),
                np.array(crcs + [7, 8], np.uint32),
            )
            for row in zip(eps, lens, crcs):
                plain.record(tick, "wire", row)
            if rng.random() < 0.2:
                cols.record(tick, "state", "native -> quarantined")
                plain.record(tick, "state", "native -> quarantined")
            assert cols.events() == plain.events(), f"tick {tick}"
            assert cols.events(last=5) == plain.events(last=5)
            assert cols.dump(last=capacity) == plain.dump(last=capacity)
            assert cols.recorded == plain.recorded
            assert len(cols) == len(plain)
        assert plain.recorded > capacity + 60
        assert other.recorded == 60 * 3 and len(other) == min(capacity, 180)


# ---------------------------------------------------------------------------
# 2. metrics across the supervision state machine
# ---------------------------------------------------------------------------


@needs_native
class TestSupervisionMetrics:
    def test_quarantine_then_eviction_counters(self):
        """A native fault: faults / transitions / evictions / latency all
        land, the slot-state gauge tracks occupancy, and the flight
        recorder holds the fault and both transitions."""
        reg = Registry()
        run = drive_chaos(
            120, n_matches=2, seed=3, metrics=reg,
            inject=lambda i, ctx: (
                ctx["pool"].inject_slot_error(ctx["target"])
                if i == 60 else None
            ),
        )
        pool, target = run["pool"], run["target"]
        assert run["states"][target] == SLOT_EVICTED
        code = str(_native.BANK_ERR_INJECTED)
        assert reg.value("ggrs_pool_slot_faults_total", code=code) == 1
        assert reg.value(
            "ggrs_pool_slot_transitions_total",
            src=SLOT_NATIVE, dst=SLOT_QUARANTINED,
        ) == 1
        assert reg.value(
            "ggrs_pool_slot_transitions_total",
            src=SLOT_QUARANTINED, dst=SLOT_EVICTED,
        ) == 1
        assert reg.value("ggrs_pool_evictions_total") == 1
        assert reg.value("ggrs_pool_eviction_failures_total") == 0
        # one eviction-latency observation (count; the immediate-evict
        # path lands in the first bucket)
        assert reg.value("ggrs_pool_eviction_latency_ticks") == 1
        # gauge occupancy: every slot accounted for, exactly one evicted
        assert reg.value("ggrs_pool_slot_state", state=SLOT_EVICTED) == 1
        assert reg.value("ggrs_pool_slot_state", state=SLOT_NATIVE) == (
            len(run["states"]) - 1
        )
        assert reg.value("ggrs_pool_slot_state", state=SLOT_QUARANTINED) == 0
        # crossing accounting: ticks + one harvest for the eviction, plus
        # drive_chaos's final scrape
        assert reg.value("ggrs_pool_crossings_total", kind="tick") == 120
        assert reg.value("ggrs_pool_crossings_total", kind="harvest") == 1
        assert reg.value("ggrs_pool_crossings_total", kind="stats") == 1
        # flight recorder: fault + both transitions are in the ring
        kinds = [k for _, k, _ in pool.flight_recorder(target).events()]
        assert "fault" in kinds and "state" in kinds and "evict" in kinds
        dump = pool.flight_dump(target, last=32)
        assert "native -> quarantined" in dump
        assert "quarantined -> evicted" in dump

    def test_eviction_failure_to_dead_counters(self):
        """Every eviction attempt fails (sabotaged harvest): the slot
        walks quarantined -> dead after EVICT_MAX_ATTEMPTS, with failures
        counted and the gauge ending on dead=1."""
        reg = Registry()

        def sabotage(i, ctx):
            if i == 20:
                pool = ctx["pool"]
                pool._evict = _raise  # every attempt now fails
                pool.inject_slot_error(ctx["target"])

        def _raise(index):
            raise RuntimeError("sabotaged eviction")

        run = drive_chaos(150, n_matches=2, seed=5, metrics=reg,
                          inject=sabotage)
        target = run["target"]
        assert run["states"][target] == SLOT_DEAD
        assert reg.value(
            "ggrs_pool_eviction_failures_total"
        ) == EVICT_MAX_ATTEMPTS
        assert reg.value("ggrs_pool_evictions_total") == 0
        assert reg.value(
            "ggrs_pool_slot_transitions_total",
            src=SLOT_QUARANTINED, dst=SLOT_DEAD,
        ) == 1
        assert reg.value("ggrs_pool_slot_state", state=SLOT_DEAD) == 1
        assert reg.value("ggrs_pool_slot_state", state=SLOT_QUARANTINED) == 0
        # dead slot that never evicted: nothing live to measure
        with pytest.raises(StatsUnavailable):
            run["pool"].network_stats(target, 0)


# ---------------------------------------------------------------------------
# 3. + 4. scrape budget, bit-identical wire, NetworkStats parity
# ---------------------------------------------------------------------------


@needs_native
class TestObservationalOnly:
    def test_wire_bit_identical_metrics_on_vs_off(self):
        """The whole obs layer — registry, per-slot flight recorders, wire
        digests, the final scrape — must not move a single wire byte:
        identical fault-injected runs with metrics on vs off."""
        inject = lambda i, ctx: (  # noqa: E731
            ctx["pool"].inject_slot_error(ctx["target"])
            if i == 60 else None
        )
        on = drive_chaos(160, n_matches=2, seed=9, metrics=Registry(),
                         inject=inject)
        off = drive_chaos(160, n_matches=2, seed=9,
                          metrics=Registry(enabled=False), inject=inject)
        assert on["states"] == off["states"]
        assert on["frames"] == off["frames"]
        for idx in range(len(on["states"])):
            assert on["wire"][idx] == off["wire"][idx], (
                f"slot {idx}: wire bytes diverged with metrics enabled"
            )
            assert on["reqs"][idx] == off["reqs"][idx]
            assert on["events"][idx] == off["events"][idx]
        # metrics-off pool really ran dark
        assert off["pool"].flight_recorder(0) is None
        assert off["registry"].families() == []

    def test_scrape_returns_native_counters(self):
        run = drive_chaos(100, n_matches=2, seed=2, metrics=Registry())
        for s in run["scrape"]:
            if s["state"] != SLOT_NATIVE:
                continue
            assert s["ticks"] == 100
            for es in s["endpoints"]:
                assert es["core"]["emits"] > 0
                assert es["packets_sent"] > 0
                assert es["bytes_sent"] > 0


@needs_native
class TestNetworkStatsParity:
    def _builders(self, net, clock):
        out = []
        names = ("X", "Y")
        for me in (0, 1):
            b = (
                SessionBuilder(Config.for_uint(16))
                .with_clock(lambda: clock[0])
                .with_rng(random.Random(3 + me))
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            out.append((b, net.socket(names[me])))
        return out

    @staticmethod
    def _fulfill(reqs):
        for r in reqs:
            if type(r).__name__ == "SaveGameState":
                r.cell.save(r.frame, None, None)

    def test_native_slot_matches_python_session(self):
        """The API-parity pin: the pooled ``network_stats`` equals the
        per-session one field-for-field under identical seeded traffic
        (ping, send queue, kbps, frame advantage both ways)."""
        clock = [0]
        faults = dict(seed=7, loss=0.05, duplicate=0.03, reorder=0.03,
                      latency_ticks=1)
        net_bank = InMemoryNetwork(**faults)
        net_py = InMemoryNetwork(**faults)
        pool = HostSessionPool(metrics=Registry())
        for b, s in self._builders(net_bank, clock):
            pool.add_session(b, s)
        pys = [
            b.start_p2p_session(s) for b, s in self._builders(net_py, clock)
        ]
        assert pool.native_active
        for i in range(200):
            clock[0] += 16
            for idx in range(2):
                pys[idx].add_local_input(idx, (i + idx) % 16)
                pool.add_local_input(idx, idx, (i + idx) % 16)
            for s in pys:
                self._fulfill(s.advance_frame())
            for reqs in pool.advance_all():
                self._fulfill(reqs)
            net_bank.tick()
            net_py.tick()
        for idx in range(2):
            assert (
                pool.network_stats(idx, 1 - idx)
                == pys[idx].network_stats(1 - idx)
            )
        with pytest.raises(BadPlayerHandle):
            pool.network_stats(0, 0)  # local handle
        with pytest.raises(BadPlayerHandle):
            pool.network_stats(0, 7)  # unknown handle

    def test_stats_unavailable_before_time_elapses(self):
        clock = [0]
        net = InMemoryNetwork()
        pool = HostSessionPool(metrics=Registry())
        for b, s in self._builders(net, clock):
            pool.add_session(b, s)
        assert pool.native_active
        with pytest.raises(StatsUnavailable):
            pool.network_stats(0, 1)

    def test_evicted_slot_serves_stats(self):
        """After an injected fault and eviction, ``network_stats`` keeps
        working, now backed by the live fallback session."""
        run = drive_chaos(
            200, n_matches=2, seed=4, metrics=Registry(),
            inject=lambda i, ctx: (
                ctx["pool"].inject_slot_error(ctx["target"])
                if i == 60 else None
            ),
        )
        pool, target = run["pool"], run["target"]
        assert run["states"][target] == SLOT_EVICTED
        stats = pool.network_stats(target, 1)
        assert stats.ping >= 0 and stats.send_queue_len >= 0
        # quarantined-or-native survivors answer from the bank harvest
        survivor = 0 if target != 0 else 1
        stats = pool.network_stats(survivor, 1 - (survivor % 2))
        assert stats.kbps_sent >= 0

    def test_fallback_pool_delegates(self, monkeypatch):
        monkeypatch.setattr(_native, "bank_lib", lambda: None)
        clock = [0]
        net = InMemoryNetwork()
        pool = HostSessionPool(metrics=Registry())
        for b, s in self._builders(net, clock):
            pool.add_session(b, s)
        assert not pool.native_active
        for i in range(80):
            clock[0] += 16
            for idx in range(2):
                pool.add_local_input(idx, idx, i % 16)
            for reqs in pool.advance_all():
                self._fulfill(reqs)
            net.tick()
        stats = pool.network_stats(0, 1)
        assert stats.ping >= 0
        # fallback scrape: no native crossing, but the same record shape
        scrape = pool.scrape()
        assert pool.stat_crossings == 0
        assert scrape[0]["endpoints"][0]["send_queue_len"] >= 0
        assert scrape[0]["ticks"] == 80
