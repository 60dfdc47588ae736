"""Match-lifecycle timeline & SLO plane tests (DESIGN.md §28).

The acceptance pins, mirrored by ``scripts/chaos.py --fault net`` and
``--fault lockstep`` artifacts:

* the stable event schema + 16-byte trace context round-trip, and
  ``fold_trace_aliases`` lands an ingress-observed (match-id-blind)
  ROUTE_FLIP inside the real match's causal chain;
* a merged timeline re-emits as a Perfetto trace that passes
  ``validate_chrome_trace`` — ONE export shows the cross-host life;
* burn rates are computed on the FLEET clock with the multi-window
  guard (both windows must burn hot before a page), and a critical
  verdict flips ``healthz()["ok"]`` — the 503 path;
* the plane is strictly piggyback — ZERO extra ctypes crossings per
  tick (the pool crossing budget is unchanged with the timeline sink
  installed and firing) and ZERO extra RPC round trips (the op set of
  the RPC latency histogram is exactly the serving path's).
"""

from __future__ import annotations

import importlib.util
import json
import sys
import urllib.request
from pathlib import Path

import pytest

from ggrs_tpu.chaos import drive_chaos, drive_fleet_chaos, drive_proc_fleet
from ggrs_tpu.fleet import FleetTuning
from ggrs_tpu.net import _native
from ggrs_tpu.obs import (
    Registry,
    Tracer,
    json_snapshot,
    start_http_server,
    validate_chrome_trace,
)
from ggrs_tpu.obs.slo import (
    LEVEL_CRITICAL,
    LEVEL_OK,
    LEVEL_WARN,
    TIER_LOCKSTEP,
    TIER_ROLLBACK,
    BurnRateEngine,
    ShardSloMeter,
    SloPolicy,
)
from ggrs_tpu.obs.timeline import (
    EV_ADMIT,
    EV_DEMOTE_LOCKSTEP,
    EV_MIGRATE_BEGIN,
    EV_MIGRATE_COMMIT,
    EV_ROUTE_FLIP,
    TIMELINE_VERSION,
    TRACE_CTX_BYTES,
    ZERO_TRACE_CTX,
    MatchTimeline,
    TimelineStore,
    first_occurrence_order,
    fold_trace_aliases,
    format_timeline,
    match_trace_id,
    merge_timelines,
    pack_trace_ctx,
    timeline_event,
    timeline_ring_events,
    unpack_trace_ctx,
)

needs_native = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)

REPO = Path(__file__).resolve().parents[1]


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# the trace context + event schema
# ----------------------------------------------------------------------


class TestTraceContext:
    def test_pack_unpack_round_trip(self):
        ctx = pack_trace_ctx("m3", 7, 42)
        assert len(ctx) == TRACE_CTX_BYTES == 16
        trace, epoch, span = unpack_trace_ctx(ctx)
        assert trace == match_trace_id("m3")
        assert (epoch, span) == (7, 42)

    def test_zero_ctx_is_no_context(self):
        assert unpack_trace_ctx(ZERO_TRACE_CTX) == (0, 0, 0)

    def test_trace_id_is_stable_and_distinct(self):
        # every process derives the SAME id with no coordination — the
        # property that joins a match's events across hosts
        assert match_trace_id("m0") == match_trace_id("m0")
        ids = {match_trace_id(f"m{i}") for i in range(256)}
        assert len(ids) == 256

    def test_event_schema_is_pinned(self):
        ev = timeline_event(EV_ADMIT, "m1", origin="h0", tick=3,
                            epoch=2, span=5, detail={"shard": "a0"},
                            ts_ns=1000)
        assert ev == {
            "v": TIMELINE_VERSION, "ev": EV_ADMIT, "mid": "m1",
            "ts_ns": 1000, "origin": "h0", "tick": 3,
            "trace": match_trace_id("m1"), "epoch": 2, "span": 5,
            "detail": {"shard": "a0"},
        }
        json.dumps(ev)  # JSON-safe by construction


# ----------------------------------------------------------------------
# bounded logs + the store
# ----------------------------------------------------------------------


class TestMatchTimeline:
    def test_time_sorted_with_arrival_tiebreak(self):
        tl = MatchTimeline("m0")
        tl.add(timeline_event("B", "m0", ts_ns=200))
        tl.add(timeline_event("A", "m0", ts_ns=100))
        tl.add(timeline_event("C", "m0", ts_ns=200))
        assert [e["ev"] for e in tl.events()] == ["A", "B", "C"]

    def test_capacity_evicts_oldest_by_time(self):
        # a late-ferried EARLY event must not push out the live tail
        tl = MatchTimeline("m0", capacity=4)
        for ts in (400, 300, 500, 600):
            tl.add(timeline_event("X", "m0", ts_ns=ts))
        tl.add(timeline_event("LATE_EARLY", "m0", ts_ns=100))
        assert tl.dropped == 1
        kept = [e["ts_ns"] for e in tl.events()]
        assert kept == [300, 400, 500, 600]  # the oldest (100) went


class TestTimelineStore:
    def test_record_and_read_back(self):
        store = TimelineStore(clock=lambda: 123)
        ev = store.record(EV_ADMIT, "m0", origin="fleet", tick=1)
        assert ev["ts_ns"] == 123
        assert store.timeline("m0") == [ev]
        assert store.match_ids() == ["m0"]
        assert store.counts() == {"m0": 1}

    def test_ingest_applies_clock_offset(self):
        # remote ts_ns shifts into the local clock domain (§18 offsets)
        store = TimelineStore()
        store.ingest([timeline_event("X", "m0", ts_ns=5000)],
                     offset_ns=2000)
        assert store.timeline("m0")[0]["ts_ns"] == 3000

    def test_malformed_remote_events_counted_not_raised(self):
        store = TimelineStore()
        n = store.ingest([
            {"no_mid": 1},
            {"mid": "m0", "ts_ns": "not-a-number"},
            timeline_event("OK", "m0", ts_ns=1),
        ])
        assert n == 1
        assert store.malformed == 2
        assert len(store.timeline("m0")) == 1

    def test_lru_match_eviction(self):
        store = TimelineStore(capacity_matches=2)
        store.record("A", "m0", ts_ns=1)
        store.record("A", "m1", ts_ns=2)
        store.record("A", "m0", ts_ns=3)  # touch m0: m1 becomes LRU
        store.record("A", "m2", ts_ns=4)
        assert sorted(store.match_ids()) == ["m0", "m2"]


# ----------------------------------------------------------------------
# merging, trace-alias folding, re-emission
# ----------------------------------------------------------------------


class TestMergeAndFold:
    def test_merge_stores_and_dicts_time_sorted(self):
        a = TimelineStore()
        a.record("B", "m0", ts_ns=200, origin="h0")
        b = {"m0": [timeline_event("A", "m0", ts_ns=100, origin="h1")]}
        merged = merge_timelines(a, b, None)
        assert [e["ev"] for e in merged["m0"]] == ["A", "B"]

    def test_fold_lands_ingress_flip_in_the_match_chain(self):
        # the ingress never learns match ids — it keys ROUTE_FLIP on the
        # wire trace context; the fold joins on match_trace_id
        trace = match_trace_id("m5")
        merged = {
            "m5": [timeline_event(EV_MIGRATE_BEGIN, "m5", ts_ns=100)],
            f"trace:{trace:016x}": [
                timeline_event(EV_ROUTE_FLIP, f"trace:{trace:016x}",
                               ts_ns=150, origin="ingress")],
        }
        folded = fold_trace_aliases(merged)
        assert list(folded) == ["m5"]
        assert [e["ev"] for e in folded["m5"]] == [
            EV_MIGRATE_BEGIN, EV_ROUTE_FLIP]

    def test_unresolvable_alias_stays_keyed_as_is(self):
        merged = {"trace:00000000deadbeef": [
            timeline_event(EV_ROUTE_FLIP, "trace:00000000deadbeef",
                           ts_ns=1)]}
        assert list(fold_trace_aliases(merged)) == [
            "trace:00000000deadbeef"]

    def test_first_occurrence_order(self):
        evs = [timeline_event(e, "m0", ts_ns=i * 10) for i, e in
               enumerate([EV_ADMIT, EV_MIGRATE_BEGIN, EV_ROUTE_FLIP,
                          EV_MIGRATE_COMMIT, EV_ROUTE_FLIP])]
        assert first_occurrence_order(
            evs, EV_ADMIT, EV_MIGRATE_BEGIN, EV_ROUTE_FLIP,
            EV_MIGRATE_COMMIT)
        assert not first_occurrence_order(
            evs, EV_MIGRATE_COMMIT, EV_ADMIT)      # out of order
        assert not first_occurrence_order(
            evs, EV_ADMIT, EV_DEMOTE_LOCKSTEP)     # absent event

    def test_ring_reemission_validates_as_chrome_trace(self):
        # the §28 acceptance: a merged timeline exports as ONE
        # schema-valid Perfetto trace
        evs = [timeline_event(e, "m0", ts_ns=1000 + i * 500,
                              origin="h0", detail={"k": i})
               for i, e in enumerate([EV_ADMIT, EV_MIGRATE_BEGIN,
                                      EV_ROUTE_FLIP, EV_MIGRATE_COMMIT])]
        tracer = Tracer(capacity=64)
        tracer.import_spans(timeline_ring_events(evs))
        trace = tracer.chrome_trace()
        assert validate_chrome_trace(trace) == []
        names = [e["name"] for e in trace["traceEvents"]
                 if e["name"].startswith("timeline.")]
        assert names == [f"timeline.{e}" for e in
                         (EV_ADMIT, EV_MIGRATE_BEGIN, EV_ROUTE_FLIP,
                          EV_MIGRATE_COMMIT)]

    def test_format_timeline_relative_offsets(self):
        evs = [timeline_event(EV_ADMIT, "m0", ts_ns=1_000_000,
                              origin="h0", tick=0),
               timeline_event(EV_ROUTE_FLIP, "m0", ts_ns=3_500_000)]
        lines = format_timeline(evs)
        assert len(lines) == 2
        assert "ADMIT" in lines[0] and "origin=h0" in lines[0]
        assert "+     2.500ms" in lines[1]
        assert format_timeline([]) == []


# ----------------------------------------------------------------------
# the SLO plane
# ----------------------------------------------------------------------


class TestShardSloMeter:
    def test_compliance_counters_by_tier(self):
        reg = Registry()
        meter = ShardSloMeter(reg)
        assert meter.observe_rollback(10.0)       # inside 16.7 ms
        assert not meter.observe_rollback(20.0)   # breach
        assert meter.observe_lockstep(2)
        assert not meter.observe_lockstep(9)      # beyond 4 frames
        assert reg.value("ggrs_slo_ticks_total", tier=TIER_ROLLBACK) == 2
        assert reg.value("ggrs_slo_breaches_total",
                         tier=TIER_ROLLBACK) == 1
        assert reg.value("ggrs_slo_ticks_total", tier=TIER_LOCKSTEP) == 2
        assert reg.value("ggrs_slo_breaches_total",
                         tier=TIER_LOCKSTEP) == 1


def _policy(**kw):
    kw.setdefault("target", 0.9)                 # budget = 0.1
    kw.setdefault("windows", (("w4", 4), ("w16", 16)))
    kw.setdefault("warn_burn", 2.0)
    kw.setdefault("critical_burn", 5.0)
    return SloPolicy(**kw)


class TestBurnRateEngine:
    def test_burn_is_error_rate_over_budget(self):
        reg = Registry()
        policy = _policy()
        meter = ShardSloMeter(reg, policy=policy)
        burn = BurnRateEngine(policy=policy)
        for tick in range(8):
            meter.observe_rollback(20.0)         # every tick breaches
            v = burn.update(tick, reg)
        # error rate 1.0 over budget 0.1 = burn 10 in both windows
        tiers = v["tiers"][TIER_ROLLBACK]
        assert tiers["burn"]["w4"] == pytest.approx(10.0)
        assert tiers["burn"]["w16"] == pytest.approx(10.0)
        assert tiers["level"] == LEVEL_CRITICAL
        assert v["level"] == LEVEL_CRITICAL and v["ok"] is False

    def test_multi_window_guard_no_page_on_a_blip(self):
        # a hot SHORT window with a cold LONG window must not page:
        # the verdict floor is min() across windows
        reg = Registry()
        policy = _policy(windows=(("w4", 4), ("w40", 40)))
        meter = ShardSloMeter(reg, policy=policy)
        burn = BurnRateEngine(policy=policy)
        for tick in range(40):
            meter.observe_rollback(20.0 if tick >= 37 else 1.0)
            v = burn.update(tick, reg)
        tiers = v["tiers"][TIER_ROLLBACK]
        assert tiers["burn"]["w4"] > policy.critical_burn
        assert tiers["burn"]["w40"] < policy.warn_burn
        assert tiers["level"] == LEVEL_OK and v["ok"] is True

    def test_escalation_counted_once_per_transition(self):
        reg = Registry()
        mreg = Registry()
        policy = _policy()
        meter = ShardSloMeter(reg, policy=policy)
        burn = BurnRateEngine(metrics=mreg, policy=policy)
        for tick in range(6):
            meter.observe_rollback(20.0)
            burn.update(tick, reg)
        assert mreg.value("ggrs_slo_escalations_total") == 1
        assert mreg.value("ggrs_slo_level") == 2
        assert mreg.value("ggrs_slo_burn_rate", tier=TIER_ROLLBACK,
                          window="w4") == pytest.approx(10.0)

    def test_warn_between_thresholds(self):
        reg = Registry()
        policy = _policy(warn_burn=2.0, critical_burn=50.0)
        meter = ShardSloMeter(reg, policy=policy)
        burn = BurnRateEngine(policy=policy)
        for tick in range(8):
            meter.observe_rollback(20.0 if tick % 2 else 1.0)
            v = burn.update(tick, reg)
        assert v["level"] == LEVEL_WARN and v["ok"] is True

    def test_policy_dict_round_trips_the_knobs(self):
        p = SloPolicy()
        d = p.as_dict()
        assert d["rollback_budget_ms"] == pytest.approx(16.7)
        assert d["lockstep_lag_frames"] == 4
        assert d["windows"] == {"5m": 18000, "1h": 216000}
        assert p.error_budget == pytest.approx(0.001)


# ----------------------------------------------------------------------
# the piggyback pins: zero extra crossings, zero extra RPC round trips
# ----------------------------------------------------------------------


@needs_native
class TestPiggybackBudgets:
    def test_timeline_sink_adds_zero_crossings(self):
        """The crossing budget with the timeline sink installed AND
        firing (a mid-run lockstep demotion) is exactly one tick
        crossing per advance_all — identical to a sink-less run."""
        TICKS = 32
        store = TimelineStore()

        def inject(i, ctx):
            if i == 0:
                ctx["pool"].timeline_sink = (
                    lambda etype, slot, detail:
                    store.record(etype, f"slot{slot}", origin="pool",
                                 detail=detail))
            if i == 16:
                ctx["pool"].demote_to_lockstep(ctx["target"])

        chaos = drive_chaos(TICKS, n_matches=2, seed=3, inject=inject)
        pool = chaos["pool"]
        control = drive_chaos(TICKS, n_matches=2, seed=3)
        # the demotion reached the store through the sink...
        demoted = [e for evs in store.to_dict().values() for e in evs
                   if e["ev"] == EV_DEMOTE_LOCKSTEP]
        assert len(demoted) == 1
        # ...and the tick crossing budget did not move
        assert pool.crossings == TICKS == control["pool"].crossings
        # the stats/harvest cadence is unchanged too (scrape-driven,
        # never timeline-driven)
        assert pool.stat_crossings <= control["pool"].stat_crossings + 1

    def test_fleet_run_rpc_ops_and_supervisor_timelines(self):
        """Proc fleet: the RPC op histogram carries ONLY the serving
        path's ops (timelines ride existing replies — §28's zero extra
        round trips), while the supervisor's store has every match's
        ADMIT."""
        tuning = FleetTuning(
            heartbeat_interval_s=0.05, heartbeat_deadline_s=1.0,
            rpc_timeout_s=5.0, spawn_timeout_s=120.0,
            drain_deadline_s=0.5, restart_max=0,
        )
        ctx = drive_proc_fleet(16, matches_per_shard=1, seed=13,
                               backend="proc", tuning=tuning,
                               desync_interval=0)
        sup = ctx["sup"]
        try:
            ops = {
                labels["op"]
                for fam in sup.metrics.families()
                if fam.name == "ggrs_fleet_proc_rpc_seconds"
                for labels, _child in fam.samples()
            }
            timelines = sup.fleet_obs.timelines.to_dict()
        finally:
            sup.close()
        assert ops <= {"hello", "tick", "admit", "adopt", "evict",
                       "drop", "identity", "healthz", "retire",
                       "shutdown"}
        for mid in ctx["match_ids"]:
            assert first_occurrence_order(timelines.get(mid, []),
                                          EV_ADMIT), mid


# ----------------------------------------------------------------------
# supervisor healthz + the /timeline endpoint
# ----------------------------------------------------------------------


@needs_native
class TestHealthAndEndpoint:
    def test_fleet_healthz_carries_the_slo_verdict(self):
        ctx = drive_fleet_chaos(16, matches_per_shard=1, seed=5)
        sup = ctx["sup"]
        try:
            hz = sup.healthz()
        finally:
            sup.close()
        slo = hz["slo"]
        assert slo["level"] in (LEVEL_OK, LEVEL_WARN, LEVEL_CRITICAL)
        assert set(slo["tiers"]) <= {TIER_ROLLBACK, TIER_LOCKSTEP}
        assert slo["policy"]["target"] == pytest.approx(0.999)

    def test_critical_burn_flips_healthz_to_503(self):
        # the SLO plane pages through the door the fleet already
        # watches: ok=False on the health dict -> MetricsServer 503
        reg = Registry()
        policy = _policy()
        meter = ShardSloMeter(reg, policy=policy)
        burn = BurnRateEngine(policy=policy)
        for tick in range(8):
            meter.observe_rollback(100.0)
            burn.update(tick, reg)
        health = {"ok": burn.verdict()["ok"], "slo": burn.verdict()}
        server = start_http_server(reg, port=0, health=lambda: health)
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/healthz")
            assert exc.value.code == 503
            body = json.loads(exc.value.read().decode())
            assert body["slo"]["level"] == LEVEL_CRITICAL
        finally:
            server.close()

    def test_timeline_endpoint_serves_merged_store(self):
        store = TimelineStore()
        store.record(EV_ADMIT, "m0", origin="fleet", tick=0, ts_ns=10)
        store.record(EV_ROUTE_FLIP, "m0", origin="ingress", ts_ns=20)
        server = start_http_server(Registry(), port=0,
                                   timelines=store.to_dict)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/timeline"
            ) as r:
                doc = json.loads(r.read().decode())
        finally:
            server.close()
        assert [e["ev"] for e in doc["m0"]] == [EV_ADMIT, EV_ROUTE_FLIP]


# ----------------------------------------------------------------------
# DesyncReport embeds its timeline
# ----------------------------------------------------------------------


class TestDesyncReportTimeline:
    def test_report_carries_the_life_up_to_the_desync(self):
        from ggrs_tpu.obs.forensics import DesyncReport

        tl = [timeline_event(EV_ADMIT, "m0", ts_ns=1, origin="h0")]
        rep = DesyncReport(
            "checksum", 12, 10, local_checksum=1, remote_checksum=2,
            timeline=tl,
        )
        d = rep.to_dict()
        assert d["timeline"] == tl
        json.dumps(d)


# ----------------------------------------------------------------------
# scripts: match_timeline extraction, fleet_top render
# ----------------------------------------------------------------------


class TestMatchTimelineScript:
    def setup_method(self):
        self.mod = _load_script("match_timeline")

    def test_extracts_and_folds_chaos_artifact(self, tmp_path):
        trace = match_trace_id("m2")
        artifact = {
            "scenario": "x",
            "timeline": {
                "m2": [timeline_event(EV_MIGRATE_BEGIN, "m2", ts_ns=10)],
                f"trace:{trace:016x}": [
                    timeline_event(EV_ROUTE_FLIP, f"trace:{trace:016x}",
                                   ts_ns=20)],
            },
        }
        p = tmp_path / "art.json"
        p.write_text(json.dumps(artifact))
        merged = self.mod.load_sources([], [str(p)])
        assert [e["ev"] for e in merged["m2"]] == [
            EV_MIGRATE_BEGIN, EV_ROUTE_FLIP]

    def test_desync_report_list_form(self, tmp_path):
        doc = {"match_id": "m9",
               "timeline": [timeline_event(EV_ADMIT, "m9", ts_ns=1)]}
        p = tmp_path / "rep.json"
        p.write_text(json.dumps(doc))
        merged = self.mod.load_sources([], [str(p)])
        assert [e["ev"] for e in merged["m9"]] == [EV_ADMIT]

    def test_perfetto_export_validates(self, tmp_path):
        evs = [timeline_event(EV_ADMIT, "m0", ts_ns=100),
               timeline_event(EV_ROUTE_FLIP, "m0", ts_ns=200)]
        out = tmp_path / "m0.trace.json"
        assert self.mod.export_perfetto(evs, str(out)) == []
        trace = json.loads(out.read_text())
        assert len(trace["traceEvents"]) >= 2


@needs_native
class TestFleetTopSlo:
    def test_render_shows_slo_column_and_timeline_footer(self):
        fleet_top = _load_script("fleet_top")
        ctx = drive_fleet_chaos(16, matches_per_shard=1, seed=5)
        sup = ctx["sup"]
        try:
            healthz = sup.healthz()
            metrics = json_snapshot(sup.merged_registry())
            timelines = sup.fleet_obs.timelines.to_dict()
        finally:
            sup.close()
        frame = fleet_top.render(healthz, metrics, timelines=timelines)
        assert "SLO" in frame                     # the new column
        assert "slo:" in frame                    # the verdict header
        assert "timeline" in frame                # the footer block
