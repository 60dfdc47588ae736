"""Tests for tick tracing & desync forensics (DESIGN.md §14).

Pin layers:

1. the Tracer primitive (ring bounds, nesting, disabled no-op, Chrome
   trace-event export) and the forensics primitives (bisection, checksum
   history) — no native code needed;
2. tracing is observational only: a fault-injected chaos run's wire
   bytes / requests / events are bit-identical with the tracer on vs off,
   and tracing adds ZERO tick crossings (the native timing tail rides the
   existing tick output);
3. the native phase spans: they nest inside the measured crossing span
   and sum to no more than its duration, the Perfetto export is valid
   JSON with the required keys, and the cumulative totals ride the stats
   crossing;
4. the HTTP endpoints (/healthz, /trace) and DesyncReport artifacts.
"""

from __future__ import annotations

import json

import pytest

from ggrs_tpu.chaos import drive_chaos, drive_desync_forensics
from ggrs_tpu.net import _native
from ggrs_tpu.obs import (
    ChecksumHistory,
    Registry,
    Tracer,
    first_divergent_frame,
    start_http_server,
)

needs_native = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)


# ---------------------------------------------------------------------------
# 1. tracer + forensics primitives
# ---------------------------------------------------------------------------


class TestTracer:
    def test_ring_bounds_and_drop_count(self):
        t = Tracer(capacity=4)
        for i in range(10):
            with t.span(f"s{i}"):
                pass
        assert len(t) == 4
        assert t.recorded == 10
        assert t.dropped == 6
        assert [e[1] for e in t.events()] == ["s6", "s7", "s8", "s9"]

    def test_nesting_containment(self):
        """Chrome infers the span tree from time containment: a child's
        [start, start+dur) must sit inside its parent's."""
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                pass
        events = {e[1]: e for e in t.events()}
        _, _, _, o_start, o_dur, _, _ = events["outer"]
        _, _, _, i_start, i_dur, _, _ = events["inner"]
        assert o_start <= i_start
        assert i_start + i_dur <= o_start + o_dur

    def test_disabled_is_noop(self):
        t = Tracer(enabled=False)
        cm = t.span("x")
        assert cm is t.span("y")  # shared singleton: zero allocation
        with cm:
            pass
        t.add_instant("i")
        t.add_complete("c", 0, 5)
        assert len(t) == 0 and t.recorded == 0
        assert t.chrome_trace()["traceEvents"] == []

    def test_chrome_export_shape(self):
        t = Tracer()
        with t.span("a", cat="py", slot=3):
            pass
        t.add_instant("fault", cat="py", code=-71)
        doc = t.chrome_trace()
        json.dumps(doc)  # serializable end to end
        events = doc["traceEvents"]
        assert len(events) == 2
        complete = next(e for e in events if e["ph"] == "X")
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(
            complete
        )
        assert complete["args"] == {"slot": 3}
        instant = next(e for e in events if e["ph"] == "i")
        assert instant["args"] == {"code": -71}
        # time base is shifted: the oldest event sits at ts 0
        assert min(e["ts"] for e in events) == 0

    def test_summary_totals(self):
        t = Tracer()
        for _ in range(3):
            with t.span("tick"):
                pass
        s = t.summary()
        assert s["tick"]["count"] == 3
        assert s["tick"]["total_us"] >= s["tick"]["max_us"] > 0


class TestForensicsPrimitives:
    def test_bisection_finds_first_divergence(self):
        local = {f: f * 7 for f in range(1, 200)}
        for div in (1, 2, 57, 199):
            remote = {
                f: (f * 7 if f < div else f * 7 + 1) for f in range(1, 200)
            }
            assert first_divergent_frame(local, remote) == div

    def test_bisection_sparse_and_disjoint_windows(self):
        local = {f: f for f in range(0, 100, 3)}
        remote = {f: (f if f < 50 else f + 1) for f in range(0, 100, 5)}
        # shared frames are multiples of 15; first divergent shared is 60
        assert first_divergent_frame(local, remote) == 60
        assert first_divergent_frame(local, {}) == -1
        assert first_divergent_frame({}, {}) == -1

    def test_bisection_no_divergence(self):
        h = {f: f for f in range(50)}
        assert first_divergent_frame(h, dict(h)) == -1

    def test_checksum_history_bounds(self):
        h = ChecksumHistory(capacity=8)
        for f in range(20):
            h.record(f, f * 3)
        assert len(h) == 8
        assert h.frames() == list(range(12, 20))
        assert h.get(19) == 57 and h.get(3) is None
        h.record(19, 1)  # update in place, no eviction
        assert len(h) == 8 and h.get(19) == 1


# ---------------------------------------------------------------------------
# 2. + 3. observational-only pins and native phase spans
# ---------------------------------------------------------------------------


def _inject_at_60(i, ctx):
    if i == 60:
        ctx["pool"].inject_slot_error(ctx["target"])


@needs_native
class TestTracingObservational:
    def test_wire_bit_identical_and_zero_extra_crossings(self):
        """The whole tracing layer — Python spans, the armed native phase
        timers, the timing tail — must not move a wire byte or add a tick
        crossing: identical fault-injected runs with the tracer on vs
        off — and it must not choose the path: a traced pool decodes through
        the descriptor plane (``plan_ticks``) like the untraced one."""
        on = drive_chaos(160, n_matches=2, seed=11, metrics=Registry(),
                         tracer=Tracer(), inject=_inject_at_60)
        off = drive_chaos(160, n_matches=2, seed=11, metrics=Registry(),
                          tracer=None, inject=_inject_at_60)
        assert on["pool"]._trace_native  # the timers really were armed
        assert on["states"] == off["states"]
        assert on["frames"] == off["frames"]
        for idx in range(len(on["states"])):
            assert on["wire"][idx] == off["wire"][idx], (
                f"slot {idx}: wire bytes diverged with tracing enabled"
            )
            assert on["reqs"][idx] == off["reqs"][idx]
            assert on["events"][idx] == off["events"][idx]
        # zero extra crossings: one tick crossing per pool tick, and the
        # scrape budget untouched (one stats crossing from the final
        # scrape, one harvest for the eviction — same as the off leg)
        assert on["pool"].crossings == off["pool"].crossings == 160
        assert on["pool"].plan_ticks == off["pool"].plan_ticks == 160
        assert on["pool"].fast_slot_ticks == off["pool"].fast_slot_ticks > 0
        assert on["pool"].harvests == off["pool"].harvests
        assert on["pool"].stat_crossings == off["pool"].stat_crossings

    def test_native_phase_spans_nest_and_sum(self):
        """Per-phase native spans: laid end-to-end inside the measured
        crossing span, summing to the in-crossing time (<= the ctypes
        window; the remainder is crossing overhead)."""
        tracer = Tracer(capacity=1 << 14)
        run = drive_chaos(60, n_matches=2, seed=12, metrics=Registry(),
                          tracer=tracer)
        pool = run["pool"]
        events = tracer.events()
        crossings = [e for e in events if e[1] == "bank.crossing"]
        assert crossings, "no crossing spans recorded"
        phase_names = {f"bank.{n}" for n in _native.BANK_PHASES}
        seen = {e[1] for e in events}
        assert "pool.tick" in seen and "pool.decode" in seen
        # per-slot spans belong to the legacy decoder, which a traced pool
        # no longer takes: the plan decode carries the counts instead
        assert "pool.slot" not in seen
        decode = [e for e in events if e[1] == "pool.decode"][-1]
        assert decode[6]["slots"] == len(run["states"])
        assert decode[6]["parent"] == "pool.tick"
        assert 0 < decode[6]["fast"] <= decode[6]["slots"]
        assert seen & phase_names, "no native phase spans recorded"
        # last tick: phases nest inside the last crossing and sum <= dur
        _, _, _, c_start, c_dur, _, _ = crossings[-1]
        tail = [e for e in events if e[1] in phase_names
                and e[3] >= c_start]
        assert tail, "no phase spans for the last crossing"
        for _, name, _, start, dur, _, _ in tail:
            assert start >= c_start
            assert start + dur <= c_start + c_dur
        phases = pool.last_tick_phases()
        assert phases is not None and set(phases) == set(
            _native.BANK_PHASES
        )
        assert 0 < sum(phases.values()) <= c_dur
        # the Perfetto export round-trips
        doc = json.loads(json.dumps(tracer.chrome_trace()))
        assert doc["traceEvents"]

    def test_64_slot_pool_perfetto_export(self):
        """The acceptance-shaped pin: a 64+-slot pool run exports a valid
        Perfetto document whose per-phase native spans sum to within 10%
        of the measured tick crossing time (the `other` phase closes the
        books natively; the residual gap is ctypes call overhead, which
        amortizes to noise at this scale)."""
        tracer = Tracer(capacity=1 << 15)
        run = drive_chaos(30, n_matches=32, seed=17, metrics=Registry(),
                          tracer=tracer)  # 2*32+1 = 65 bank slots
        assert len(run["states"]) == 65
        events = tracer.events()
        phase_names = {f"bank.{n}" for n in _native.BANK_PHASES}
        crossings = [e for e in events if e[1] == "bank.crossing"]
        assert crossings
        ratios = []
        for _, _, _, c_start, c_dur, _, _ in crossings:
            span_sum = sum(
                e[4] for e in events
                if e[1] in phase_names and c_start <= e[3] < c_start + c_dur
            )
            if span_sum:
                ratios.append(span_sum / c_dur)
        assert ratios
        ratios.sort()
        median = ratios[len(ratios) // 2]
        assert 0.9 <= median <= 1.0, (
            f"native phase spans cover {median:.1%} of the median "
            f"crossing; expected within 10%"
        )
        # the export loads: valid JSON, complete events carry ts+dur
        doc = json.loads(json.dumps(tracer.chrome_trace()))
        assert len(doc["traceEvents"]) == len(events)
        for ev in doc["traceEvents"]:
            assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_phase_totals_ride_the_stats_crossing(self):
        tracer = Tracer()
        run = drive_chaos(50, n_matches=1, seed=13, metrics=Registry(),
                          tracer=tracer)
        pool = run["pool"]  # drive_chaos ends with a scrape
        totals = pool.native_phase_totals()
        assert totals is not None
        timed_ticks, by_phase = totals
        assert timed_ticks == 50
        assert set(by_phase) == set(_native.BANK_PHASES)
        assert sum(by_phase.values()) > 0
        # the scrape that refreshed them was the run's single stats
        # crossing: the cumulative view costs nothing extra
        assert pool.stat_crossings == 1


# ---------------------------------------------------------------------------
# 4. HTTP endpoints + DesyncReport artifacts
# ---------------------------------------------------------------------------


class TestHttpEndpoints:
    def test_healthz_and_trace(self):
        import time as _time
        import urllib.error
        import urllib.request

        reg = Registry()
        reg.counter("x_total").inc()
        tracer = Tracer()
        with tracer.span("tick"):
            pass
        stamp = [_time.monotonic()]
        try:
            server = start_http_server(
                reg, port=0, tracer=tracer, health=lambda: stamp[0],
                stale_after=60.0,
            )
        except OSError:
            pytest.skip("cannot bind a loopback socket in this sandbox")
        try:
            base = f"http://127.0.0.1:{server.port}"
            body = json.loads(
                urllib.request.urlopen(base + "/healthz", timeout=5).read()
            )
            assert body["ok"] is True
            assert body["last_tick_age_s"] >= 0
            doc = json.loads(
                urllib.request.urlopen(base + "/trace", timeout=5).read()
            )
            assert doc["traceEvents"][0]["name"] == "tick"
            # stale loop: 503 with ok false
            stamp[0] = _time.monotonic() - 3600
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/healthz", timeout=5)
            assert exc.value.code == 503
            assert json.loads(exc.value.read())["ok"] is False
        finally:
            server.close()

    def test_trace_404_without_tracer(self):
        import urllib.error
        import urllib.request

        try:
            server = start_http_server(Registry(), port=0)
        except OSError:
            pytest.skip("cannot bind a loopback socket in this sandbox")
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/trace", timeout=5
                )
            assert exc.value.code == 404
        finally:
            server.close()


class TestDesyncReports:
    def test_checksum_compare_report_round_trips(self, tmp_path):
        """The reference-path report: first-divergent-frame bisection
        lands on the seeded fault frame and the artifact round-trips
        through JSON with every forensic section present."""
        run = drive_desync_forensics(160, fault_frame=30, seed=14,
                                     interval=1, tracer=Tracer())
        assert run["reports_a"] and run["reports_b"]
        report = run["reports_b"][0]
        assert report.kind == "checksum-compare"
        assert report.first_divergent_frame == 30
        assert report.detected_frame == 30
        assert report.local_checksum != report.remote_checksum
        # the checksum window straddles the divergence on both sides
        assert "29" in report.to_dict()["checksum_window"]["local"]
        assert report.recorder_dump
        assert report.trace_events
        path = report.write(tmp_path / "report.json")
        loaded = json.load(open(path))
        assert loaded["first_divergent_frame"] == 30
        assert loaded["kind"] == "checksum-compare"
        assert loaded["trace_events"]

    def test_report_list_is_bounded(self):
        """A persistent desync re-fires every interval; the report list
        must not grow without bound."""
        from ggrs_tpu.obs.forensics import MAX_REPORTS

        run = drive_desync_forensics(400, fault_frame=30, seed=15,
                                     interval=1)
        assert len(run["desyncs"][0]) > MAX_REPORTS
        assert len(run["reports_a"]) == MAX_REPORTS

    @needs_native
    def test_native_fault_report_on_quarantine(self):
        """A desync-class bank fault (BANK_ERR_SYNC) leaves a forensic
        artifact on the pool, with the recorder dump and trace window
        attached."""
        tracer = Tracer()
        run = drive_chaos(
            120, n_matches=2, seed=16, metrics=Registry(), tracer=tracer,
            inject=lambda i, ctx: (
                ctx["pool"].inject_slot_error(
                    ctx["target"], _native.BANK_ERR_SYNC
                )
                if i == 60 else None
            ),
        )
        pool, target = run["pool"], run["target"]
        report = pool.desync_report(target)
        assert report is not None
        assert report.kind == "native-fault"
        assert report.recorder_dump
        assert report.trace_events
        json.dumps(report.to_dict())
        # non-desync slots carry no report
        assert pool.desync_report(0) is None
        # the injected non-desync fault class leaves no report either
        other = drive_chaos(80, n_matches=1, seed=16, metrics=Registry(),
                            inject=_inject_at_60)
        assert other["pool"].desync_report(other["target"]) is None
