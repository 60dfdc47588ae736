#!/usr/bin/env python
"""Pool-scale chaos CLI for the supervised session bank (DESIGN.md §9).

Thin front-end over ``ggrs_tpu.chaos`` — the SAME driver the test suite
uses (tests/test_bank_faults.py), so the script and the tests exercise one
code path.  For each selected fault class it runs a fault-free CONTROL leg
and a CHAOS leg, then verifies the blast radius: every non-targeted slot's
wire bytes, request lists, and events must be bit-identical between the two
legs, and the crossing count must stay one native crossing per pool tick.
Each scenario ends with a metrics + flight-recorder summary (faults by
code, evictions, survivor counters, the target slot's last 32 recorded
events) instead of discarding that state — DESIGN.md §12.

With ``--artifact-dir``, every scenario additionally writes a
machine-readable JSON artifact (digest + verdict + any DesyncReport
path) for CI consumption — DESIGN.md §14.

Fault classes (all driven through the pool's real tick path):
  native-error  simulated native slot fault (ctrl-op channel)
  desync        desync-class invariant fault (BANK_ERR_SYNC) on the bank —
                the quarantine now yields a DesyncReport artifact — plus a
                forensic leg on the reference detection path: a state
                fault seeded at a known frame must bisect to EXACTLY that
                first divergent frame in both peers' reports
  blackout      the target's peer goes permanently silent
  malformed     burst of truncated/corrupted datagrams into the target
  fuzz          seeded random junk datagrams into the target
  lockstep      lockstep-demotion leg (DESIGN.md §27): a live native slot
                is demoted to the lockstep tier mid-run — survivors must
                stay bit-identical to control, the demoted slot must land
                evicted+lockstep with exactly one adoption load, zero
                saves, and CONFIRMED-only advances
  spectator     broadcast leg: a hub-fanned match with live viewers and a
                journal is chaos-killed with its native harvest DEAD; the
                slot must recover from the journal tail, the viewers must
                keep following, and the in-bank side matches must stay
                bit-identical to control (ends with the hub's metrics
                digest — DESIGN.md §13)
  socket        batched-datapath leg (real loopback UDP, native_io=True —
                DESIGN.md §15): an ENOBUFS/EAGAIN storm on the target's
                sendmmsg path must count as loss without faulting the
                slot, a fatal EPERM must fault exactly that slot
                (BANK_ERR_IO) and evict it onto the Python socket path —
                survivors' wire bytes bit-identical to control either way
  proc          out-of-process leg (DESIGN.md §17): s1 is a REAL
                subprocess (scripts/shard_runner.py) behind the
                supervisor RPC — SIGKILL mid-traffic must be detected
                within the heartbeat deadline with every match
                journal-recovered and zero orphans, SIGSTOP must
                escalate SIGTERM -> drain deadline -> SIGKILL before the
                same recovery, and a 5x kill storm must exhaust the
                restart budget instead of crash-looping; every artifact
                records its FleetTuning knobs
  net           multi-host fleet link leg (DESIGN.md §25): the proc
                topology with the supervisor<->runner control plane on
                the authenticated TCP link — a severed or half-open
                link must RESUME inside the reconnect window with zero
                failovers, hostile dribble against the listener
                (garbage / slowloris / truncated auth) is refused and
                counted without touching the served link, a SIGKILLed
                runner journal-fails-over bit-identically to control,
                and a runner resurrected after its window expired is
                fenced at handshake by the bumped epoch and exits;
                ends with a cross-host placement leg (DESIGN.md §26):
                killing a whole host fails every match over to the
                survivor host behind UNCHANGED virtual endpoints
  shard         fleet leg (DESIGN.md §16): a two-shard ShardSupervisor
                (B = --fleet-matches journaled matches per shard, default
                32) runs three scenarios — kill-a-shard (every affected
                match journal-recovers onto the survivor within bounded
                lag; the surviving shard's matches bit-identical to a
                fault-free control), drain-under-load (admission closes,
                every match migrates off, the shard retires), and
                migrate-under-loss (a live migration under seeded
                loss/dup/reorder keeps the peer connected and
                desync-free, spectators resume from their ack window)
  all           every class, sequentially

Usage:
  JAX_PLATFORMS=cpu python scripts/chaos.py --matches 4 --ticks 400
  python scripts/chaos.py --fault blackout --ticks 600 --seed 7

Exit code 0 = blast radius contained in every leg; 1 = violation.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from ggrs_tpu.chaos import (  # noqa: E402
    MALFORMED_BURST,
    blast_radius_violations,
    drive_broadcast,
    drive_chaos,
    drive_desync_forensics,
    drive_dispatch_chaos,
    drive_socket_chaos,
)
from ggrs_tpu.net import _native  # noqa: E402
from ggrs_tpu.obs import (  # noqa: E402
    Tracer,
    fleet_metrics_digest,
    json_snapshot,
    validate_chrome_trace,
)
from ggrs_tpu.obs.slo import (  # noqa: E402
    BurnRateEngine,
    ShardSloMeter,
    SloPolicy,
)
from ggrs_tpu.obs.timeline import (  # noqa: E402
    EV_ADMIT,
    EV_DEMOTE_LOCKSTEP,
    EV_FAILOVER,
    EV_MIGRATE_BEGIN,
    EV_MIGRATE_COMMIT,
    EV_ROUTE_FLIP,
    TimelineStore,
    first_occurrence_order,
    fold_trace_aliases,
    merge_timelines,
    timeline_ring_events,
)


def _fleet_trace_artifact(artifact_dir, name: str, tracer):
    """Write one scenario's Perfetto export beside its JSON artifact and
    return ``{"trace_path":..., "trace_events":..., "trace_problems":...}``
    for embedding (DESIGN.md §18).  The export is schema-validated here
    (eps widened for imported cross-process spans) so a torn trace shows
    up in CI, not in a ui.perfetto.dev tab weeks later."""
    if artifact_dir is None or tracer is None:
        return {}
    out = Path(artifact_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = tracer.write(out / f"{name}.trace.json")
    trace = tracer.chrome_trace()
    problems = validate_chrome_trace(trace, eps_us=50.0)
    if problems:
        print(f"  trace validation ({name}): {len(problems)} problems, "
              f"e.g. {problems[0]}")
    else:
        print(f"  trace: {path} ({len(trace['traceEvents'])} events, "
              "schema-valid)")
    return {
        "trace_path": str(path),
        "trace_events": len(trace["traceEvents"]),
        "trace_problems": problems[:8],
    }


def _placement_timelines(ctx) -> dict:
    """The cross-host merged match timelines of a placement-fleet run
    (DESIGN.md §28): the placement plane's own store, each host
    supervisor's harvested store (origin prefixed with the host id so
    the merged view shows WHICH machine saw each event), and the
    ingress node's trace-keyed ROUTE_FLIP events folded onto their
    matches via the wire trace context."""
    sources = [ctx["placement"].timelines.to_dict()]
    for hid, sup in ctx["hosts"].items():
        exported = sup.fleet_obs.timelines.to_dict()
        sources.append({
            mid: [dict(e, origin=f"{hid}/{e.get('origin') or '?'}")
                  for e in evs]
            for mid, evs in exported.items()
        })
    ing: dict = {}
    for ev in ctx["ingress"].drain_timeline():
        ing.setdefault(ev["mid"], []).append(ev)
    sources.append(ing)
    return fold_trace_aliases(merge_timelines(*sources))


def _timeline_trace_artifact(artifact_dir, name: str, timelines: dict):
    """ONE Perfetto export for a merged timeline view — every match's
    lifecycle events re-emitted as instants through the §18 Tracer path
    — schema-validated in CI like the span exports.  Returns the
    embedding dict (empty without --artifact-dir)."""
    if artifact_dir is None or not timelines:
        return {}
    events = [ev for evs in timelines.values() for ev in evs]
    tracer = Tracer(capacity=max(256, len(events) + 16))
    tracer.import_spans(timeline_ring_events(events))
    out = Path(artifact_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = tracer.write(out / f"{name}.timeline.trace.json")
    trace = tracer.chrome_trace()
    problems = validate_chrome_trace(trace, eps_us=50.0)
    if problems:
        print(f"  timeline trace validation ({name}): "
              f"{len(problems)} problems, e.g. {problems[0]}")
    else:
        print(f"  timeline trace: {path} "
              f"({len(trace['traceEvents'])} events, schema-valid)")
    return {
        "timeline_trace_path": str(path),
        "timeline_trace_events": len(trace["traceEvents"]),
        "timeline_trace_problems": problems[:8],
    }


def _write_artifact(artifact_dir, name: str, payload: dict):
    """One machine-readable JSON artifact per scenario (CI consumption):
    digest + verdict + any DesyncReport path, alongside the stdout
    digest.  Returns the path, or None when no --artifact-dir was given."""
    if artifact_dir is None:
        return None
    out = Path(artifact_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{name}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    print(f"  artifact: {path}")
    return path


def _metrics_summary(chaos) -> str:
    """Per-scenario metrics digest (DESIGN.md §12): faults by code,
    supervision flow, crossing budget, and survivor counters — the state
    a plain pass/fail verdict used to discard."""
    reg = chaos["registry"]
    lines = []
    fam = {f.name: f for f in reg.families()}
    faults = fam.get("ggrs_pool_slot_faults_total")
    if faults is not None and faults.children:
        by_code = ", ".join(
            f"code {labels['code']}: {int(child.value)}"
            for labels, child in faults.samples()
        )
        lines.append(f"  metrics: faults by code: {by_code or 'none'}")
    else:
        lines.append("  metrics: faults by code: none")
    lines.append(
        "  metrics: evictions={} eviction_failures={} ticks={} "
        "crossings(tick/harvest/stats)={}/{}/{}".format(
            int(reg.value("ggrs_pool_evictions_total") or 0),
            int(reg.value("ggrs_pool_eviction_failures_total") or 0),
            int(reg.value("ggrs_pool_ticks_total") or 0),
            int(reg.value("ggrs_pool_crossings_total", kind="tick") or 0),
            int(reg.value("ggrs_pool_crossings_total", kind="harvest") or 0),
            int(reg.value("ggrs_pool_crossings_total", kind="stats") or 0),
        )
    )
    lines.append(
        "  metrics: survivor counters: requests save/load/advance = "
        "{}/{}/{}, rollbacks={}".format(
            int(reg.value("ggrs_pool_requests_total", kind="save") or 0),
            int(reg.value("ggrs_pool_requests_total", kind="load") or 0),
            int(reg.value("ggrs_pool_requests_total", kind="advance") or 0),
            int(reg.value("ggrs_pool_rollbacks_total") or 0),
        )
    )
    states = fam.get("ggrs_pool_slot_state")
    if states is not None:
        occupancy = ", ".join(
            f"{labels['state']}={int(child.value)}"
            for labels, child in states.samples()
            if child.value
        )
        lines.append(f"  metrics: slot states: {occupancy}")
    return "\n".join(lines)


def _fuzz_bytes(seed: int, i: int, k: int) -> bytes:
    rng = random.Random(seed * 7919 + i * 31 + k)
    return bytes(rng.randrange(256) for _ in range(rng.randrange(0, 40)))


FAULTS = {
    "native-error": dict(
        inject=lambda i, ctx: (
            ctx["pool"].inject_slot_error(ctx["target"]) if i == 60 else None
        ),
    ),
    "desync": dict(
        inject=lambda i, ctx: (
            ctx["pool"].inject_slot_error(
                ctx["target"], _native.BANK_ERR_SYNC
            )
            if i == 60
            else None
        ),
    ),
    "blackout": dict(ext_alive=lambda i: i < 80, retire=True),
    "malformed": dict(
        inject=lambda i, ctx: (
            [
                ctx["pool"].inject_datagram(ctx["target"], "X", junk)
                for junk in MALFORMED_BURST
            ]
            if 50 <= i < 60
            else None
        ),
    ),
    "fuzz": dict(
        inject=lambda i, ctx: (
            [
                ctx["pool"].inject_datagram(
                    ctx["target"], "X", _fuzz_bytes(ctx["seed"], i, k)
                )
                for k in range(3)
            ]
            if 40 <= i < 140
            else None
        ),
    ),
}


def verify_leg(name: str, matches: int, ticks: int, seed: int,
               artifact_dir=None) -> bool:
    spec = FAULTS[name]
    retire = spec.get("retire", False)
    control = drive_chaos(ticks, n_matches=matches, seed=seed, retire=retire)
    chaos = drive_chaos(
        ticks, n_matches=matches, seed=seed,
        inject=spec.get("inject"),
        ext_alive=spec.get("ext_alive"),
        retire=retire,
    )
    target = chaos["target"]
    violations = blast_radius_violations(chaos, control)
    pool = chaos["pool"]
    print(f"--- {name} ---")
    print(f"  target slot {target}: state={chaos['states'][target]}, "
          f"frame={chaos['frames'][target]}, ext peer frame="
          f"{chaos['ext'].current_frame}")
    for f in pool.fault_log(target):
        print(f"    fault@tick {f.tick}: code={f.code} {f.detail}")
    print(f"  crossings={pool.crossings} harvests={pool.harvests} "
          f"stat_crossings={pool.stat_crossings} "
          f"fastpath_slot_ticks={pool.fast_slot_ticks}")
    print(_metrics_summary(chaos))
    dump = pool.flight_dump(target, last=32)
    print(f"  flight recorder (target slot {target}, last 32 events):")
    print("\n".join(f"  {line}" for line in dump.splitlines()))
    report = pool.desync_report(target)
    report_path = None
    if report is not None:
        # the desync-class fault left a forensic artifact, not a bare event
        print("  " + report.summary().replace("\n", "\n  "))
        if artifact_dir is not None:
            out = Path(artifact_dir)
            out.mkdir(parents=True, exist_ok=True)
            report_path = report.write(out / f"{name}.desync_report.json")
            print(f"  desync report: {report_path}")
    if name == "desync":
        violations += _verify_desync_forensics(ticks, seed, artifact_dir)
    verdict = not violations
    _write_artifact(artifact_dir, name, {
        "scenario": name,
        "verdict": "PASS" if verdict else "FAIL",
        "violations": violations,
        "target_slot": target,
        "target_state": chaos["states"][target],
        "target_frame": chaos["frames"][target],
        "fault_log": [
            {"tick": f.tick, "code": f.code, "detail": f.detail}
            for f in pool.fault_log(target)
        ],
        "crossings": {"tick": pool.crossings, "harvest": pool.harvests,
                      "stats": pool.stat_crossings},
        # vectorized policy plane (DESIGN.md §19) + descriptor plane
        # (§21): how much of the run the quiet fast path served — fault
        # ticks and their neighbors must take the slow reference decoder,
        # survivors stay fast — and how many plan-tick slots needed the
        # eager per-slot decoder
        "fastpath": {"slot_ticks": pool.fast_slot_ticks,
                     "all_fast_ticks": pool.fast_ticks,
                     "plan_ticks": getattr(pool, "plan_ticks", 0),
                     "desc_slow_slots": getattr(
                         pool, "desc_slow_slots", 0)},
        "desync_report": str(report_path) if report_path else None,
        "metrics": json_snapshot(chaos["registry"]),
    })
    if violations:
        print("  BLAST RADIUS VIOLATED:")
        for v in violations:
            print(f"    {v}")
        return False
    print(f"  OK: {len(chaos['states']) - 1} surviving slots bit-identical "
          "to control")
    return True


def _verify_desync_forensics(ticks: int, seed: int, artifact_dir=None):
    """The forensic leg of the desync scenario: the REFERENCE detection
    path (two Python sessions, interval-1 checksum exchange) with a state
    fault seeded at a known frame — the resulting DesyncReport's
    first-divergent-frame bisection must land exactly on it."""
    from ggrs_tpu.obs import Tracer

    fault_frame = max(20, min(60, ticks // 3))
    run = drive_desync_forensics(
        max(ticks, fault_frame + 60), fault_frame=fault_frame, seed=seed,
        interval=1, tracer=Tracer(),
    )
    violations = []
    print(f"  forensic leg: state fault seeded at frame {fault_frame} "
          f"(checksum interval 1)")
    for side, reports in (("A", run["reports_a"]), ("B", run["reports_b"])):
        if not reports:
            violations.append(f"peer {side} produced no DesyncReport")
            continue
        r = reports[0]
        print(f"  peer {side}: " + r.summary().replace("\n", "\n  "))
        if r.first_divergent_frame != fault_frame:
            violations.append(
                f"peer {side}: first divergent frame "
                f"{r.first_divergent_frame} != fault frame {fault_frame}"
            )
    if run["reports_a"] and run["reports_b"]:
        # both ends' recorder dumps ride one artifact
        report = run["reports_a"][0]
        report.remote_recorder_dump = run["recorders"][1].dump(32)
        if artifact_dir is not None:
            out = Path(artifact_dir)
            out.mkdir(parents=True, exist_ok=True)
            path = report.write(out / "desync.forensic_report.json")
            print(f"  forensic report: {path}")
    return violations


def verify_lockstep_leg(matches: int, ticks: int, seed: int,
                        artifact_dir=None) -> bool:
    """The lockstep-demotion scenario (DESIGN.md §27): a live native slot
    is demoted to the lockstep tier mid-run — the pool's load-shed path.
    The demoted slot must land evicted with ``max_prediction=0``, replay
    its confirmed prefix through EXACTLY ONE adoption load, never save or
    load again, advance only CONFIRMED inputs, and keep making frames;
    every OTHER slot must stay bit-identical to a fault-free control leg."""
    from ggrs_tpu.core import InputStatus
    from ggrs_tpu.parallel.host_bank import SLOT_EVICTED

    demote_at = max(20, min(60, ticks // 3))

    # §28 riders on the chaos leg: the pool's timeline sink (slot-keyed
    # lifecycle events) and a self-contained SLO pipeline — meter fed
    # from real inter-tick wall time + the demoted slot's confirmed
    # lag, burn engine over windows sized to the run
    import time

    from ggrs_tpu.obs.registry import Registry

    timelines = TimelineStore()
    slo_reg = Registry()
    policy = SloPolicy(windows=(("16t", 16), ("64t", max(64, ticks // 2))))
    meter = ShardSloMeter(slo_reg, policy=policy)
    burn = BurnRateEngine(policy=policy)
    last_ns = [0]
    tick_box = [0]

    def inject(i, ctx):
        pool = ctx["pool"]
        tick_box[0] = i
        if i == 0:
            pool.timeline_sink = lambda etype, slot, detail: (
                timelines.record(etype, f"slot{slot}", origin="pool",
                                 tick=tick_box[0], detail=detail))
        now = time.perf_counter_ns()
        if last_ns[0]:
            meter.observe_rollback((now - last_ns[0]) / 1e6)
        last_ns[0] = now
        if pool.lockstep_slots():
            lag = max(0, ctx["ext"].current_frame
                      - pool.current_frame(ctx["target"]))
            meter.observe_lockstep(lag)
        burn.update(i, slo_reg)
        if i == demote_at:
            ctx["resume_frame"] = pool.demote_to_lockstep(ctx["target"])

    control = drive_chaos(ticks, n_matches=matches, seed=seed)
    chaos = drive_chaos(ticks, n_matches=matches, seed=seed, inject=inject)
    target = chaos["target"]
    pool = chaos["pool"]
    resume = chaos.get("resume_frame")
    violations = list(blast_radius_violations(chaos, control))

    print("--- lockstep ---")
    print(f"  target slot {target}: demoted at tick {demote_at}, resume "
          f"frame {resume}, state={chaos['states'][target]}, "
          f"frame={chaos['frames'][target]}, ext peer frame="
          f"{chaos['ext'].current_frame}")

    if chaos["states"][target] != SLOT_EVICTED:
        violations.append(
            f"demoted slot state {chaos['states'][target]!r}, expected "
            f"evicted-to-python ({SLOT_EVICTED!r})"
        )
    if not pool.in_lockstep(target):
        violations.append("pool does not report the target in lockstep")
    if pool.lockstep_slots() != {target: demote_at}:
        violations.append(
            f"lockstep_slots() = {pool.lockstep_slots()!r}, expected "
            f"{{{target}: {demote_at}}}"
        )
    if not resume or resume <= 0:
        violations.append(f"demotion returned resume frame {resume!r}")
    elif chaos["frames"][target] <= resume:
        violations.append(
            f"demoted slot stuck: frame {chaos['frames'][target]} <= "
            f"resume frame {resume}"
        )

    # post-demotion request discipline: one adoption load, zero saves,
    # real progress, and every advance carries CONFIRMED inputs only
    post = [r for tick_reqs in chaos["reqs"][target][demote_at:]
            for r in tick_reqs]
    loads = sum(1 for r in post if r[0] == "LoadGameState")
    saves = sum(1 for r in post if r[0] == "SaveGameState")
    advs = [r for r in post if r[0] == "adv"]
    predicted = sum(
        1 for r in advs
        for _, status in r[1] if status != InputStatus.CONFIRMED
    )
    print(f"  post-demotion requests: {loads} loads (adoption), {saves} "
          f"saves, {len(advs)} advances ({predicted} non-CONFIRMED inputs)")
    if loads != 1:
        violations.append(f"{loads} post-demotion loads, expected exactly "
                          "the 1 adoption load")
    if saves:
        violations.append(f"{saves} post-demotion saves, expected 0 "
                          "(lockstep never snapshots)")
    if not advs:
        violations.append("demoted slot produced no post-demotion advances")
    if predicted:
        violations.append(
            f"{predicted} post-demotion inputs advanced non-CONFIRMED "
            "(lockstep must never run predicted inputs)"
        )
    print(f"  crossings={pool.crossings} harvests={pool.harvests} "
          f"stat_crossings={pool.stat_crossings} "
          f"fastpath_slot_ticks={pool.fast_slot_ticks}")
    print(_metrics_summary(chaos))

    # §28: the pool's timeline seam must have emitted the demotion
    demote_events = [
        e for e in timelines.timeline(f"slot{target}")
        if e["ev"] == EV_DEMOTE_LOCKSTEP
    ]
    if not demote_events:
        violations.append(
            "timeline sink recorded no DEMOTE_LOCKSTEP for the target"
        )
    slo_verdict = burn.verdict()
    tier_levels = ", ".join(
        f"{t}={v['level']}" for t, v in slo_verdict["tiers"].items())
    print(f"  slo: level={slo_verdict['level']} tiers=[{tier_levels}]")

    verdict = not violations
    _write_artifact(artifact_dir, "lockstep", {
        "scenario": "lockstep",
        "verdict": "PASS" if verdict else "FAIL",
        "violations": violations,
        "target_slot": target,
        "demoted_at_tick": demote_at,
        "resume_frame": resume,
        "target_state": chaos["states"][target],
        "target_frame": chaos["frames"][target],
        "post_demotion": {"loads": loads, "saves": saves,
                          "advances": len(advs),
                          "non_confirmed_inputs": predicted},
        "crossings": {"tick": pool.crossings, "harvest": pool.harvests,
                      "stats": pool.stat_crossings},
        # §28 riders: the pool-seam timeline and the run's SLO verdict
        "timeline": timelines.to_dict(),
        "slo": slo_verdict,
        "metrics": json_snapshot(chaos["registry"]),
    })
    if violations:
        print("  BLAST RADIUS VIOLATED:")
        for v in violations:
            print(f"    {v}")
        return False
    print(f"  OK: {len(chaos['states']) - 1} surviving slots bit-identical "
          "to control; demoted slot lockstep-clean")
    return True


def verify_broadcast_leg(matches: int, ticks: int, seed: int,
                         artifact_dir=None) -> bool:
    """The broadcast scenario: chaos-kill a hub-fanned, journaled match
    whose native harvest is dead; verify journal recovery, viewer
    continuity, and survivor bit-identity — then print the hub's metrics
    digest (DESIGN.md §13) instead of discarding it."""
    import tempfile

    from ggrs_tpu.parallel.host_bank import SLOT_EVICTED, SLOT_NATIVE

    # clamp inside the run: the kill must actually fire and leave room to
    # observe the recovery, whatever --ticks was passed
    kill_at = min(max(40, ticks // 3), max(1, ticks - 20))

    def inject(i, ctx):
        if i == kill_at:
            ctx["pool"].inject_slot_error(ctx["target"])

    with tempfile.TemporaryDirectory() as tmp:
        control = drive_broadcast(
            ticks, use_hub=True, seed=seed, n_spectators=2,
            n_side_matches=matches,
            journal_path=f"{tmp}/control.ggjl",
        )
        chaos = drive_broadcast(
            ticks, use_hub=True, seed=seed, n_spectators=2,
            n_side_matches=matches,
            journal_path=f"{tmp}/chaos.ggjl",
            inject=inject, sabotage_harvest=True, scrape_every=8,
        )
    pool = chaos["pool"]
    print("--- spectator ---")
    print(f"  target slot 0: state={chaos['states'][0]}, "
          f"frame={chaos['frames'][0]}, ext peer frame="
          f"{chaos['peer_frame']}, viewers at "
          f"{[f[-1] for f in chaos['viewer_frames']]}")
    for f in pool.fault_log(0):
        print(f"    fault@tick {f.tick}: code={f.code} {f.detail}")
    violations = []
    if chaos["states"][0] != SLOT_EVICTED:
        violations.append(
            f"target never recovered: state {chaos['states'][0]}"
        )
    if not any("journal tail" in f.detail for f in pool.fault_log(0)):
        violations.append("recovery did not come from the journal")
    for vf in chaos["viewer_frames"]:
        if vf[-1] < vf[kill_at] + (ticks - kill_at) // 2:
            violations.append("a viewer stalled after the kill")
    for idx in range(1, 1 + 2 * matches):
        if chaos["states"][idx] != SLOT_NATIVE:
            violations.append(f"slot {idx} left native")
        for field in ("reqs", "events"):
            if chaos[field][idx] != control[field][idx]:
                violations.append(f"slot {idx}: {field} diverged")
    for k in range(2 * matches):
        if chaos["side_wire"][k] != control["side_wire"][k]:
            violations.append(f"side socket {k}: wire diverged")
    print("  hub metrics digest:")
    print(chaos["hub"].metrics_digest())
    _write_artifact(artifact_dir, "spectator", {
        "scenario": "spectator",
        "verdict": "PASS" if not violations else "FAIL",
        "violations": violations,
        "target_state": chaos["states"][0],
        "target_frame": chaos["frames"][0],
        "fault_log": [
            {"tick": f.tick, "code": f.code, "detail": f.detail}
            for f in pool.fault_log(0)
        ],
        "metrics": json_snapshot(chaos["registry"]),
        "desync_report": None,
    })
    if violations:
        print("  BROADCAST SCENARIO VIOLATED:")
        for v in violations:
            print(f"    {v}")
        return False
    print(f"  OK: journal recovery + {2 * matches} surviving slots "
          "bit-identical to control")
    return True


def verify_socket_leg(matches: int, ticks: int, seed: int,
                      artifact_dir=None) -> bool:
    """The batched-datapath scenario (DESIGN.md §15): errno storms on the
    target slot's sendmmsg path, a fault-free control leg, and per-leg
    verification that the blast radius stayed ≤ 1 slot with survivors'
    wire bytes (captured at the NetBatch tee, exact send order)
    bit-identical to control."""
    import errno as _errno

    from ggrs_tpu.net import _native as _nat

    ticks = max(ticks, 160)
    print("--- socket ---")
    try:
        control = drive_socket_chaos(ticks, n_matches=matches, seed=seed)
    except RuntimeError as e:
        # no recvmmsg/sendmmsg on this platform / library: the fallback
        # matrix says the Python shuttle serves — nothing to storm
        print(f"  skip: {e}")
        return True

    def storm_transient(i, ctx):
        if 40 <= i < 60:
            ctx["pool"].inject_socket_errno(
                ctx["target"], _errno.ENOBUFS, 4
            )
        elif 60 <= i < 70:
            ctx["pool"].inject_socket_errno(
                ctx["target"], _errno.EAGAIN, 4
            )

    def storm_fatal(i, ctx):
        if i == 50:
            ctx["pool"].inject_socket_errno(ctx["target"], _errno.EPERM, 1)

    violations = []
    legs = {}
    for name, storm in (("transient", storm_transient),
                        ("fatal", storm_fatal)):
        chaos = drive_socket_chaos(
            ticks, n_matches=matches, seed=seed, inject=storm
        )
        legs[name] = chaos
        target = chaos["target"]
        pool = chaos["pool"]
        for f in pool.fault_log(target):
            print(f"    [{name}] fault@tick {f.tick}: code={f.code} "
                  f"{f.detail}")
        if name == "transient":
            if chaos["states"][target] != "native":
                violations.append(
                    f"transient storm faulted the slot: "
                    f"{chaos['states'][target]}"
                )
            if chaos["io"]["send_errors"] < 20:
                violations.append(
                    "transient storm left no send_errors trace "
                    f"({chaos['io']['send_errors']})"
                )
        else:
            if chaos["states"][target] != "evicted":
                violations.append(
                    f"fatal errno did not evict: {chaos['states'][target]}"
                )
            if not any(f.code == _nat.BANK_ERR_IO
                       for f in pool.fault_log(target)):
                violations.append("fault log missing BANK_ERR_IO")
        if chaos["frames"][target] < ticks - 80:
            violations.append(
                f"{name}: target stalled at frame {chaos['frames'][target]}"
            )
        for idx in range(target):
            if chaos["states"][idx] != "native":
                violations.append(f"{name}: survivor slot {idx} left native")
            if chaos["wire"][idx] != control["wire"][idx]:
                violations.append(
                    f"{name}: survivor slot {idx} wire diverged "
                    f"({len(chaos['wire'][idx])} vs "
                    f"{len(control['wire'][idx])} datagrams)"
                )
            if chaos["reqs"][idx] != control["reqs"][idx]:
                violations.append(f"{name}: survivor slot {idx} reqs diverged")
        print(f"  [{name}] target state={chaos['states'][target]} "
              f"frame={chaos['frames'][target]} "
              f"io={{recv_calls: {chaos['io']['recv_calls']}, "
              f"send_calls: {chaos['io']['send_calls']}, "
              f"send_errors: {chaos['io']['send_errors']}}}")

    # --- shared dispatch socket leg (DESIGN.md §23): a fatal errno on
    # the SHARED fd must fault exactly the owning slot — the record's,
    # not the fd's — while every co-tenant stays native and bit-identical
    # (peer-observed bytes) to a fault-free dispatch control
    try:
        d_control = drive_dispatch_chaos(ticks, n_matches=matches,
                                         seed=seed)
    except RuntimeError as e:
        print(f"  [dispatch_fatal] skip: {e}")
        d_control = None
    if d_control is not None:
        def dispatch_storm(i, ctx):
            # record 0 of tick 50's send table = the target slot's (the
            # table is packed in slot order; slot 0 sends every tick)
            if i == 50:
                ctx["lib"].ggrs_net_inject_table_errno(_errno.EPERM, 0, 1)

        d_chaos = drive_dispatch_chaos(
            ticks, n_matches=matches, seed=seed, inject=dispatch_storm
        )
        legs["dispatch_fatal"] = d_chaos
        target = d_chaos["target"]
        pool = d_chaos["pool"]
        for f in pool.fault_log(target):
            print(f"    [dispatch_fatal] fault@tick {f.tick}: "
                  f"code={f.code} {f.detail}")
        if d_chaos["states"][target] != "evicted":
            violations.append(
                "dispatch_fatal: shared-fd fatal did not evict the "
                f"owner: {d_chaos['states'][target]}"
            )
        if not any(f.code == _nat.BANK_ERR_IO
                   for f in pool.fault_log(target)):
            violations.append("dispatch_fatal: fault log missing "
                              "BANK_ERR_IO")
        if d_chaos["frames"][target] < ticks - 80:
            violations.append(
                "dispatch_fatal: target stalled at frame "
                f"{d_chaos['frames'][target]}"
            )
        for idx in range(1, matches + 1):
            if d_chaos["states"][idx] != "native":
                violations.append(
                    f"dispatch_fatal: co-tenant slot {idx} left native: "
                    f"{d_chaos['states'][idx]}"
                )
            if d_chaos["wire"][idx] != d_control["wire"][idx]:
                violations.append(
                    f"dispatch_fatal: co-tenant slot {idx} wire diverged "
                    f"({len(d_chaos['wire'][idx])} vs "
                    f"{len(d_control['wire'][idx])} datagrams)"
                )
            if d_chaos["reqs"][idx] != d_control["reqs"][idx]:
                violations.append(
                    f"dispatch_fatal: co-tenant slot {idx} reqs diverged"
                )
        if d_chaos["pool"].crossings != ticks:
            violations.append(
                f"dispatch_fatal: crossing count "
                f"{d_chaos['pool'].crossings} != {ticks} pool ticks"
            )
        drain = d_chaos["io"]["drain"]
        dec = d_chaos["io"]["decode"]
        print(f"  [dispatch_fatal] target state="
              f"{d_chaos['states'][target]} "
              f"frame={d_chaos['frames'][target]} fds={d_chaos['hub_fds']} "
              f"drain={{datagrams: {drain['datagrams']}, "
              f"unroutable: {drain['unroutable']}, "
              f"crossings: {drain['crossings']}}} "
              f"gso={d_chaos['io']['gso']} "
              f"decode={{backend: {dec['backend']}, "
              f"parallel_ticks: {dec['parallel_ticks']}, "
              f"jobs: {dec['jobs']}}}")
    verdict = not violations
    _write_artifact(artifact_dir, "socket", {
        "scenario": "socket",
        "verdict": "PASS" if verdict else "FAIL",
        "violations": violations,
        "target_slot": control["target"],
        "legs": {
            name: {
                "target_state": leg["states"][leg["target"]],
                "target_frame": leg["frames"][leg["target"]],
                "io": leg["io"],
                "fault_log": [
                    {"tick": f.tick, "code": f.code, "detail": f.detail}
                    for f in leg["pool"].fault_log(leg["target"])
                ],
            }
            for name, leg in legs.items()
        },
        # §24 decode-plane posture under fault load (each leg's full
        # counters also ride along in legs[*].io.decode)
        "decode_plane": legs["fatal"]["io"]["decode"],
        "metrics": json_snapshot(legs["fatal"]["registry"]),
        "desync_report": None,
    })
    if violations:
        print("  SOCKET SCENARIO VIOLATED:")
        for v in violations:
            print(f"    {v}")
        return False
    print(f"  OK: storms contained; {control['target']} surviving slots "
          "bit-identical to control")
    return True


def verify_fleet_leg(matches_per_shard: int, ticks: int, seed: int,
                     artifact_dir=None) -> bool:
    """The fleet scenarios (DESIGN.md §16), over ``drive_fleet_chaos`` —
    the SAME driver tests/test_fleet.py pins.  Three sub-scenarios, each a
    control/chaos pair with its own JSON verdict:

    - ``shard_kill``: one of two shards dies mid-tick; every affected
      match must journal-recover onto the survivor within bounded lag,
      with the surviving shard's matches bit-identical to control.
    - ``shard_drain``: graceful drain under load; every match migrates
      off a bounded few per tick and the shard retires.
    - ``shard_migrate``: a live migration under seeded loss/dup/reorder;
      the migrated match's peer stays connected and desync-free, the
      untouched matches stay bit-identical to their lossy control, and
      the spectator resumes from its ack window (stream never resets).
    """
    from ggrs_tpu.chaos import (
        drive_fleet_chaos,
        fleet_recovery_violations,
        fleet_survivor_violations,
    )

    p = matches_per_shard
    ticks = max(96, min(ticks, 240))  # bounded: B is the scale knob here
    survivors = [f"m{k}" for k in range(p)]           # pinned to s0
    affected = [f"m{k}" for k in range(p, 2 * p)]     # pinned to s1
    ok = True

    def fleet_digest(ctx) -> dict:
        reg = ctx["registry"]
        return {
            "locations": ctx["locations"],
            "lost": ctx["lost"],
            "healthz": {
                k: v for k, v in ctx["healthz"].items() if k != "shards"
            },
            "migrations": {
                labels["reason"]: int(child.value)
                for f in reg.families()
                if f.name == "ggrs_fleet_migrations_total"
                for labels, child in f.samples()
            },
            "failovers": int(
                reg.value("ggrs_fleet_failovers_total") or 0
            ),
        }

    def report(name: str, violations, ctx, extra=None,
               tracer=None) -> bool:
        digest = fleet_digest(ctx)
        print(f"  [{name}] locations: "
              f"{sum(1 for s in ctx['locations'].values() if s == 's0')} "
              f"on s0, lost={len(ctx['lost'])}, "
              f"migrations={digest['migrations']}")
        _write_artifact(artifact_dir, name, {
            "scenario": name,
            "verdict": "PASS" if not violations else "FAIL",
            "violations": violations,
            "matches_per_shard": p,
            "ticks": ticks,
            **digest,
            **(extra or {}),
            "fleet_obs": fleet_metrics_digest(ctx["sup"]),
            **_fleet_trace_artifact(artifact_dir, name, tracer),
            "metrics": json_snapshot(ctx["sup"].merged_registry()),
        })
        if violations:
            print(f"  {name.upper()} VIOLATED:")
            for v in violations:
                print(f"    {v}")
            return False
        return True

    print("--- shard ---")
    print(f"  two shards x {p} journaled matches, {ticks} ticks")
    control = drive_fleet_chaos(ticks, matches_per_shard=p, seed=seed)

    # 1. kill-a-shard: crash failover from the durable journals alone
    def kill(i, ctx):
        if i == ticks // 2:
            ctx["sup"].kill("s1")

    tr = Tracer(capacity=16384) if artifact_dir is not None else None
    chaos = drive_fleet_chaos(
        ticks, matches_per_shard=p, seed=seed, inject=kill, tracer=tr
    )
    violations = fleet_survivor_violations(chaos, control, survivors)
    violations += fleet_recovery_violations(
        chaos, affected, dead_shards=["s1"]
    )
    recovered = sum(
        1 for m in affected if chaos["locations"][m] not in (None, "s1")
    )
    lag = max(
        (chaos["peer_frames"][m] - (chaos["frames"][m] or 0)
         for m in affected), default=0,
    )
    print(f"  [shard_kill] s1 killed @tick {ticks // 2}: {recovered}/{p} "
          f"matches journal-recovered onto s0, max lag {lag} frames")
    ok &= report("shard_kill", violations, chaos,
                 extra={"recovered": recovered, "max_lag_frames": lag},
                 tracer=tr)

    # 2. drain-under-load: admission off, migrate all, retire
    def drain(i, ctx):
        if i == ticks // 3:
            ctx["sup"].drain("s1")

    tr = Tracer(capacity=16384) if artifact_dir is not None else None
    chaos = drive_fleet_chaos(
        ticks, matches_per_shard=p, seed=seed, inject=drain, tracer=tr
    )
    violations = fleet_survivor_violations(chaos, control, survivors)
    violations += fleet_recovery_violations(chaos, affected)
    state = chaos["sup"].shards["s1"].state
    if state != "retired":
        violations.append(f"drained shard is {state}, not retired")
    print(f"  [shard_drain] s1 drained @tick {ticks // 3}: shard {state}, "
          f"{sum(1 for m in affected if chaos['locations'][m] == 's0')}/{p} "
          "matches migrated to s0")
    ok &= report("shard_drain", violations, chaos,
                 extra={"drained_shard_state": state}, tracer=tr)

    # 3. migrate-under-loss: live migration on a lossy wire + spectators
    lossy = dict(latency_ticks=1, loss=0.05, duplicate=0.02, reorder=0.05)
    lossy_control = drive_fleet_chaos(
        ticks, matches_per_shard=p, seed=seed, fault_cfg=dict(lossy),
        n_spectators=2,
    )

    def migrate(i, ctx):
        if i == ticks // 3:
            ctx["sup"].migrate("m0")

    tr = Tracer(capacity=16384) if artifact_dir is not None else None
    chaos = drive_fleet_chaos(
        ticks, matches_per_shard=p, seed=seed, inject=migrate,
        fault_cfg=dict(lossy), n_spectators=2, tracer=tr,
    )
    untouched = [m for m in chaos["match_ids"] if m != "m0"]
    violations = fleet_survivor_violations(chaos, lossy_control, untouched)
    violations += fleet_recovery_violations(chaos, ["m0"])
    if chaos["locations"]["m0"] == lossy_control["locations"]["m0"]:
        violations.append("m0 never moved")
    # spectator continuity: the stream resumes from the ack window — it
    # never resets/regresses and advances well past the migration tick
    viewer_tips = []
    for v, stream in enumerate(chaos["viewer_streams"]):
        frames = [f for f, _ in stream]
        if frames != sorted(set(frames)):
            violations.append(f"viewer {v} stream reset/regressed")
        if not frames or frames[-1] < ticks // 3 + 8:
            violations.append(
                f"viewer {v} stalled at {frames[-1] if frames else None}"
            )
        viewer_tips.append(frames[-1] if frames else None)
    print(f"  [shard_migrate] m0 -> {chaos['locations']['m0']} under "
          f"loss/dup/reorder; viewers at {viewer_tips}")
    ok &= report("shard_migrate", violations, chaos,
                 extra={"migrated_to": chaos["locations"]["m0"],
                        "viewer_tips": viewer_tips}, tracer=tr)
    if ok:
        print(f"  OK: {p}-per-shard fleet survived kill, drain, and "
              "lossy migration")
    return ok


def verify_proc_leg(matches_per_shard: int, ticks: int, seed: int,
                    artifact_dir=None) -> bool:
    """The out-of-process scenarios (DESIGN.md §17), over
    ``drive_proc_fleet`` — the SAME driver tests/test_fleet_proc.py
    pins.  Shard ``s0`` serves in-process, ``s1`` is a real subprocess
    (scripts/shard_runner.py); every scenario is verified against a
    fault-free proc-backend control and every artifact records the
    ``FleetTuning`` knobs it ran with (round-trippable JSON):

    - ``proc_sigkill``: SIGKILL the shard subprocess mid-traffic; death
      must be detected within the heartbeat deadline, every match must
      re-adopt from its durable journal onto the survivor, the
      surviving shard's peer-observed wire must be bit-identical to
      control, and zero orphan processes/fds may remain.
    - ``proc_sigstop``: SIGSTOP (a hang, not a death) until the
      watchdog escalates SIGTERM → drain deadline → SIGKILL, then the
      same recovery contract — wedged ≠ dead, and failover only after
      confirmed death.
    - ``proc_restart_storm``: kill the same shard 5× fast; the
      jittered-backoff restart policy must respawn it at most
      ``restart_max`` times inside the storm window and then leave it
      dead, with every match still recovered and nothing leaked.
    """
    import os
    import signal
    import time

    from ggrs_tpu.chaos import (
        drive_proc_fleet,
        fleet_recovery_violations,
        fleet_survivor_violations,
    )
    from ggrs_tpu.fleet import FleetTuning, SHARD_DEAD

    p = matches_per_shard
    ticks = max(120, min(ticks, 240))
    tuning = FleetTuning(
        heartbeat_interval_s=0.05, heartbeat_deadline_s=0.5,
        rpc_timeout_s=0.75, drain_deadline_s=0.4,
        spawn_timeout_s=120.0, restart_max=0,
    )
    survivors = [f"m{k}" for k in range(p)]           # pinned to s0
    affected = [f"m{k}" for k in range(p, 2 * p)]     # pinned to s1
    ok = True

    def report(name, violations, ctx, extra=None, tracer=None) -> bool:
        reg = ctx["registry"]
        _write_artifact(artifact_dir, name, {
            "scenario": name,
            "verdict": "PASS" if not violations else "FAIL",
            "violations": violations,
            "matches_per_shard": p,
            "ticks": ticks,
            "tuning": tuning.as_dict(),
            "locations": ctx["locations"],
            "lost": ctx["lost"],
            "healthz": {
                k: v for k, v in ctx["healthz"].items() if k != "shards"
            },
            "s1": ctx["healthz"]["shards"]["s1"],
            "watchdog": {
                stage: int(reg.value(
                    "ggrs_fleet_proc_watchdog_total",
                    shard="s1", stage=stage) or 0)
                for stage in ("sigterm", "sigkill")
            },
            "restarts": int(reg.value(
                "ggrs_fleet_proc_restarts_total", shard="s1") or 0),
            **(extra or {}),
            "fleet_obs": fleet_metrics_digest(ctx["sup"]),
            **_fleet_trace_artifact(artifact_dir, name, tracer),
            "metrics": json_snapshot(ctx["sup"].merged_registry()),
        })
        if violations:
            print(f"  {name.upper()} VIOLATED:")
            for v in violations:
                print(f"    {v}")
            return False
        return True

    print("--- proc ---")
    print(f"  s0 in-process + s1 subprocess x {p} journaled matches, "
          f"{ticks} ticks")
    control = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="proc",
        tuning=tuning,
    )
    control["sup"].close()

    # 1. SIGKILL mid-traffic: crash detection + journal failover
    timing = {}

    def sigkill(i, ctx):
        sup = ctx["sup"]
        if i == ticks // 2:
            timing["pid"] = sup.shards["s1"].pid
            timing["killed_at"] = time.monotonic()
            os.kill(timing["pid"], signal.SIGKILL)
        elif "killed_at" in timing and "detected_at" not in timing:
            if sup.shards["s1"].state == SHARD_DEAD:
                timing["detected_at"] = time.monotonic()

    tr = Tracer(capacity=16384) if artifact_dir is not None else None
    chaos = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="proc",
        tuning=tuning, inject=sigkill, tracer=tr,
    )
    chaos["sup"].close()
    violations = fleet_survivor_violations(chaos, control, survivors)
    violations += fleet_recovery_violations(
        chaos, affected, dead_shards=["s1"]
    )
    detect_s = (
        timing.get("detected_at", float("inf")) - timing["killed_at"]
    )
    if detect_s > tuning.heartbeat_deadline_s:
        violations.append(
            f"death detected in {detect_s:.2f}s > heartbeat deadline "
            f"{tuning.heartbeat_deadline_s}s"
        )
    orphans = chaos["sup"].shards["s1"].orphan_count()
    if orphans:
        violations.append(f"{orphans} orphan runner processes")
    if os.path.exists(f"/proc/{timing['pid']}"):
        violations.append(f"killed runner pid {timing['pid']} not reaped")
    recovered = sum(
        1 for m in affected if chaos["locations"][m] not in (None, "s1")
    )
    print(f"  [proc_sigkill] pid {timing['pid']} SIGKILLed @tick "
          f"{ticks // 2}: detected in {detect_s * 1000:.0f} ms, "
          f"{recovered}/{p} matches journal-recovered, {orphans} orphans")
    ok &= report("proc_sigkill", violations, chaos, extra={
        "recovered": recovered,
        "detect_seconds": detect_s,
        "orphans": orphans,
    }, tracer=tr)

    # 2. SIGSTOP: a hang — watchdog escalation, then the same recovery.
    # tick_sleep stretches real time so the (wall-clock) escalation
    # deadlines can pass without the logical clock outrunning the
    # peers' disconnect timeout.
    def sigstop(i, ctx):
        if i == ticks // 3:
            os.kill(ctx["sup"].shards["s1"].pid, signal.SIGSTOP)

    chaos = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="proc",
        tuning=tuning, inject=sigstop, tick_sleep_s=0.02,
    )
    chaos["sup"].close()
    reg = chaos["registry"]
    violations = fleet_survivor_violations(chaos, control, survivors)
    violations += fleet_recovery_violations(
        chaos, affected, dead_shards=["s1"]
    )
    sigterms = int(reg.value("ggrs_fleet_proc_watchdog_total",
                             shard="s1", stage="sigterm") or 0)
    sigkills = int(reg.value("ggrs_fleet_proc_watchdog_total",
                             shard="s1", stage="sigkill") or 0)
    if not sigterms:
        violations.append("watchdog never escalated to SIGTERM")
    if not sigkills:
        violations.append("watchdog never escalated to SIGKILL")
    orphans = chaos["sup"].shards["s1"].orphan_count()
    if orphans:
        violations.append(f"{orphans} orphan runner processes")
    print(f"  [proc_sigstop] hang @tick {ticks // 3}: escalation "
          f"sigterm={sigterms} sigkill={sigkills}, "
          f"{sum(1 for m in affected if chaos['locations'][m] == 's0')}"
          f"/{p} matches recovered")
    ok &= report("proc_sigstop", violations, chaos, extra={
        "sigterms": sigterms, "sigkills": sigkills, "orphans": orphans,
    })

    # 2b. harvest overhead: the SAME topology with the runner-side obs
    # harvest compiled out (obs_harvest=0) — the runner tick p99 delta
    # prices the piggyback (<5% target, informational: recorded in the
    # artifact, not asserted, because CI boxes jitter)
    from ggrs_tpu.fleet import FleetTuning as _FT
    off = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="proc",
        tuning=_FT.from_dict({**tuning.as_dict(), "obs_harvest": 0}),
    )
    off["sup"].close()
    on_p99 = control["healthz"]["shards"]["s1"].get("tick_p99_ms") or 0.0
    off_p99 = off["healthz"]["shards"]["s1"].get("tick_p99_ms") or 0.0
    pct = (100.0 * (on_p99 - off_p99) / off_p99) if off_p99 else None
    print(f"  [proc_harvest] s1 tick p99: harvest-on {on_p99:.2f} ms vs "
          f"harvest-off {off_p99:.2f} ms "
          f"({'n/a' if pct is None else f'{pct:+.1f}%'}, target <5%)")
    _write_artifact(artifact_dir, "proc_harvest_overhead", {
        "scenario": "proc_harvest_overhead",
        "verdict": "INFO",
        "tick_p99_ms_harvest_on": on_p99,
        "tick_p99_ms_harvest_off": off_p99,
        "overhead_pct": pct,
        "fleet_obs": fleet_metrics_digest(control["sup"]),
    })

    # 3. restart storm: kill the same shard 5x fast; the backoff
    # restart policy must respawn at most restart_max times, then stay
    # dead — a crash loop must not melt the host
    storm_tuning = FleetTuning(
        heartbeat_interval_s=0.05, heartbeat_deadline_s=0.5,
        rpc_timeout_s=0.75, drain_deadline_s=0.3,
        spawn_timeout_s=120.0,
        restart_backoff_s=0.05, restart_max=2, restart_window_s=60.0,
    )
    kills = {"n": 0}

    def storm(i, ctx):
        s1 = ctx["sup"].shards["s1"]
        if i >= ticks // 3 and kills["n"] < 5 and s1.pid and s1._alive():
            kills["n"] += 1
            os.kill(s1.pid, signal.SIGKILL)

    chaos = drive_proc_fleet(
        max(ticks, 240), matches_per_shard=min(p, 4), seed=seed,
        backend="proc", tuning=storm_tuning, inject=storm,
        tick_sleep_s=0.01,
    )
    chaos["sup"].close()
    s1 = chaos["sup"].shards["s1"]
    storm_affected = [
        m for m in chaos["match_ids"]
        if m not in [f"m{k}" for k in range(min(p, 4))]
    ]
    violations = fleet_recovery_violations(
        chaos, storm_affected, dead_shards=["s1"]
    )
    if s1.restarts != storm_tuning.restart_max:
        violations.append(
            f"{s1.restarts} restarts != storm budget "
            f"{storm_tuning.restart_max}"
        )
    if s1.state != SHARD_DEAD:
        violations.append(f"stormed shard is {s1.state}, not dead")
    orphans = s1.orphan_count()
    if orphans:
        violations.append(f"{orphans} orphan runner processes")
    print(f"  [proc_restart_storm] {kills['n']} kills: {s1.restarts} "
          f"restarts (budget {storm_tuning.restart_max}), final state "
          f"{s1.state}, {orphans} orphans")
    ok &= report("proc_restart_storm", violations, chaos, extra={
        "kills": kills["n"], "tuning": storm_tuning.as_dict(),
        "orphans": orphans,
    })
    if ok:
        print(f"  OK: {p}-per-shard subprocess fleet survived SIGKILL, "
              "SIGSTOP escalation, and a restart storm")
    return ok


def verify_net_leg(matches_per_shard: int, ticks: int, seed: int,
                   artifact_dir=None) -> bool:
    """The multi-host fleet link scenarios (DESIGN.md §25), over
    ``drive_proc_fleet(backend="tcp")`` — the proc topology with the
    supervisor↔runner control plane on the authenticated TCP link.
    Every scenario is judged against a fault-free tcp-backend control:

    - ``net_sever``/``net_half_open``: cut the established link (full
      shutdown / write-half only) mid-traffic; the runner must RESUME
      inside the reconnect window with ZERO failovers — the severed
      shard's matches never leave it, the link epoch never moves, and
      the untouched shard stays bit-identical to control.
    - ``net_dribble``: adversarial connections against the live
      listener (garbage-before-magic, slowloris, truncated-then-EOF)
      must each be refused and counted WITHOUT touching the served
      link — the whole fleet stays bit-identical to control.
    - ``net_host_kill``: SIGKILL the runner; a reaped local child is
      confirmed-dead immediately (no window), every match
      journal-recovers onto the survivor, survivors bit-identical to
      control — §16 failover unchanged by the TCP transport.
    - ``net_fence``: SIGSTOP the runner AND sever the link so the
      window expires; failover must wait for the expiry (zero
      failovers while the window is open), the dead incarnation is
      fenced rather than signalled, and when the old runner RESURRECTS
      it must be refused at handshake (HS_REFUSED_FENCE) and exit of
      its own accord.
    - ``net_placement_host_kill``: the §26 placement plane — kill one
      of two HOSTS behind the ingress; every match journal-fails-over
      cross-host onto the survivor, the route epoch is minted past the
      dead host, the ingress flips every affected route, and players +
      viewers keep streaming on the SAME virtual endpoints with the
      untouched host bit-identical to a fault-free control.
    """
    import os
    import signal
    import socket as _socket
    import time

    from ggrs_tpu.chaos import (
        drive_proc_fleet,
        fleet_recovery_violations,
        fleet_survivor_violations,
    )
    from ggrs_tpu.fleet import FleetTuning, SHARD_DEAD

    p = matches_per_shard
    ticks = max(120, min(ticks, 240))
    tuning = FleetTuning(
        heartbeat_interval_s=0.05, heartbeat_deadline_s=0.5,
        rpc_timeout_s=0.75, drain_deadline_s=0.4,
        spawn_timeout_s=120.0, restart_max=0,
        link_auth_token="chaos-net-token",
        link_reconnect_window_s=0.6, link_backoff_s=0.01,
        link_handshake_timeout_s=0.3,
    )
    survivors = [f"m{k}" for k in range(p)]           # pinned to s0
    affected = [f"m{k}" for k in range(p, 2 * p)]     # pinned to s1
    ok = True

    def link_of(ctx):
        return ctx["healthz"]["shards"]["s1"].get("link") or {}

    def report(name, violations, ctx, extra=None) -> bool:
        _write_artifact(artifact_dir, name, {
            "scenario": name,
            "verdict": "PASS" if not violations else "FAIL",
            "violations": violations,
            "matches_per_shard": p,
            "ticks": ticks,
            "tuning": tuning.as_dict(),
            "locations": ctx["locations"],
            "lost": ctx["lost"],
            "link": link_of(ctx),
            "failovers": int(
                ctx["registry"].value("ggrs_fleet_failovers_total") or 0
            ),
            **(extra or {}),
            "fleet_obs": fleet_metrics_digest(ctx["sup"]),
            "metrics": json_snapshot(ctx["sup"].merged_registry()),
        })
        if violations:
            print(f"  {name.upper()} VIOLATED:")
            for v in violations:
                print(f"    {v}")
            return False
        return True

    print("--- net ---")
    print(f"  s0 in-process + s1 subprocess over authenticated TCP x "
          f"{p} journaled matches, {ticks} ticks")
    control = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="tcp",
        tuning=tuning,
    )
    control["sup"].close()

    # 1 + 2. sever the established link (full, then write-half only):
    # the runner must resume inside the window with zero failovers
    for name, how in (("net_sever", "rdwr"), ("net_half_open", "wr")):
        def sever(i, ctx, how=how):
            if i == ticks // 2:
                ctx["sup"].shards["s1"].chaos_sever_link(how)

        chaos = drive_proc_fleet(
            ticks, matches_per_shard=p, seed=seed, backend="tcp",
            tuning=tuning, inject=sever, tick_sleep_s=0.005,
        )
        chaos["sup"].close()
        violations = fleet_survivor_violations(chaos, control, survivors)
        link = link_of(chaos)
        failovers = int(
            chaos["registry"].value("ggrs_fleet_failovers_total") or 0
        )
        if failovers:
            violations.append(
                f"{failovers} failovers despite an open reconnect window"
            )
        moved = [m for m in affected if chaos["locations"][m] != "s1"]
        if moved:
            violations.append(f"matches left the severed shard: {moved}")
        if chaos["lost"]:
            violations.append(f"matches lost: {chaos['lost']}")
        if not link.get("reconnects"):
            violations.append("link never recorded a resume")
        if link.get("window_expiries"):
            violations.append(
                f"{link['window_expiries']} window expiries on a "
                "recoverable sever"
            )
        if link.get("epoch") != 1:
            violations.append(
                f"epoch moved to {link.get('epoch')} without a failover"
            )
        print(f"  [{name}] link cut ({how}) @tick {ticks // 2}: "
              f"state={link.get('state')} epoch={link.get('epoch')} "
              f"reconnects={link.get('reconnects')} "
              f"failovers={failovers}")
        ok &= report(name, violations, chaos)

    # 3. dribble: adversarial connections against the live listener —
    # refused and counted, the served link untouched, fleet
    # bit-identical to control
    dribble_socks = []

    def dribble(i, ctx):
        if i != ticks // 3:
            return
        addr = ctx["sup"].shards["s1"]._link.address
        garbage = _socket.create_connection(addr, timeout=2.0)
        garbage.sendall(b"GET /healthz HTTP/1.1\r\n\r\n")
        dribble_socks.append(garbage)
        slow = _socket.create_connection(addr, timeout=2.0)
        slow.sendall(b"GA")  # the magic, then... nothing
        dribble_socks.append(slow)
        trunc = _socket.create_connection(addr, timeout=2.0)
        trunc.sendall(b"GA\x01\x00")  # a valid prefix, then EOF
        trunc.close()

    try:
        chaos = drive_proc_fleet(
            ticks, matches_per_shard=p, seed=seed, backend="tcp",
            tuning=tuning, inject=dribble, tick_sleep_s=0.005,
        )
    finally:
        for s in dribble_socks:
            try:
                s.close()
            except OSError:
                pass
    chaos["sup"].close()
    violations = fleet_survivor_violations(
        chaos, control, survivors + affected
    )
    link = link_of(chaos)
    refusals = link.get("refusals") or {}
    for reason in ("garbage", "timeout", "eof"):
        if not refusals.get(reason):
            violations.append(f"no {reason!r} refusal recorded")
    if link.get("reconnects"):
        violations.append(
            "dribble connections disturbed the established link"
        )
    failovers = int(
        chaos["registry"].value("ggrs_fleet_failovers_total") or 0
    )
    if failovers:
        violations.append(f"{failovers} failovers from unauthenticated "
                          "dribble traffic")
    print(f"  [net_dribble] 3 hostile conns @tick {ticks // 3}: "
          f"refusals={refusals} failovers={failovers}")
    ok &= report("net_dribble", violations, chaos, extra={
        "refusals": refusals,
    })

    # 4. host kill: SIGKILL over TCP — §16 journal failover must be
    # transport-agnostic (a reaped local child needs no window)
    timing = {}

    def host_kill(i, ctx):
        sup = ctx["sup"]
        if i == ticks // 2:
            timing["pid"] = sup.shards["s1"].pid
            timing["killed_at"] = time.monotonic()
            os.kill(timing["pid"], signal.SIGKILL)
        elif "killed_at" in timing and "detected_at" not in timing:
            if sup.shards["s1"].state == SHARD_DEAD:
                timing["detected_at"] = time.monotonic()

    chaos = drive_proc_fleet(
        ticks, matches_per_shard=p, seed=seed, backend="tcp",
        tuning=tuning, inject=host_kill,
    )
    chaos["sup"].close()
    violations = fleet_survivor_violations(chaos, control, survivors)
    violations += fleet_recovery_violations(
        chaos, affected, dead_shards=["s1"]
    )
    detect_s = (
        timing.get("detected_at", float("inf")) - timing["killed_at"]
    )
    if detect_s > tuning.heartbeat_deadline_s:
        violations.append(
            f"death detected in {detect_s:.2f}s > heartbeat deadline "
            f"{tuning.heartbeat_deadline_s}s"
        )
    orphans = chaos["sup"].shards["s1"].orphan_count()
    if orphans:
        violations.append(f"{orphans} orphan runner processes")
    recovered = sum(
        1 for m in affected if chaos["locations"][m] not in (None, "s1")
    )
    print(f"  [net_host_kill] pid {timing['pid']} SIGKILLed @tick "
          f"{ticks // 2}: detected in {detect_s * 1000:.0f} ms, "
          f"{recovered}/{p} matches journal-recovered, {orphans} orphans")
    ok &= report("net_host_kill", violations, chaos, extra={
        "recovered": recovered, "detect_seconds": detect_s,
        "orphans": orphans,
    })

    # 5. fence: stop the runner AND cut the link; the window must
    # expire before failover, the incarnation is fenced (not
    # signalled), and its resurrected self is refused at handshake
    fence = {}

    def fence_inject(i, ctx):
        s1 = ctx["sup"].shards["s1"]
        if i == ticks // 3:
            fence["pid"] = s1.pid
            fence["proc"] = s1._proc
            os.kill(fence["pid"], signal.SIGSTOP)
            s1.chaos_sever_link()
            return
        if "pid" not in fence:
            return
        if "resurrected" not in fence and s1.state == SHARD_DEAD:
            # confirmed dead via window expiry — bring the old
            # incarnation back from suspension: it must be fenced
            os.kill(fence["pid"], signal.SIGCONT)
            fence["resurrected"] = i
        if "resurrected" in fence:
            s1._link.pump()  # judge the stale runner's redials

    chaos = drive_proc_fleet(
        ticks, matches_per_shard=min(p, 4), seed=seed, backend="tcp",
        tuning=tuning, inject=fence_inject, tick_sleep_s=0.02,
    )
    s1 = chaos["sup"].shards["s1"]
    # the fenced runner exits on its own once refused; give it a beat
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        s1._link.pump()
        if fence.get("proc") is not None and fence["proc"].poll() is not None:
            break
        time.sleep(0.02)
    chaos["sup"].close()
    fence_affected = [
        m for m in chaos["match_ids"]
        if m not in [f"m{k}" for k in range(min(p, 4))]
    ]
    violations = fleet_recovery_violations(
        chaos, fence_affected, dead_shards=["s1"]
    )
    link = link_of(chaos)
    refusals = link.get("refusals") or {}
    if not link.get("window_expiries"):
        violations.append("reconnect window never expired")
    if (link.get("epoch") or 0) < 2:
        violations.append(
            f"epoch {link.get('epoch')} not bumped past the fenced "
            "incarnation"
        )
    if not refusals.get("fence"):
        violations.append("resurrected stale runner was never "
                          "fence-refused at handshake")
    exit_code = fence["proc"].poll() if fence.get("proc") else None
    if exit_code != 1:
        violations.append(
            f"fenced runner exit code {exit_code!r} (want 1: refused "
            "and exited on its own)"
        )
    fence_exit = chaos["healthz"]["shards"]["s1"].get("exit") or ""
    if "fenced" not in fence_exit:
        violations.append(
            f"exit reason {fence_exit!r} does not record the fence"
        )
    orphans = s1.orphan_count()
    if orphans:
        violations.append(f"{orphans} orphan runner processes")
    print(f"  [net_fence] SIGSTOP+sever @tick {ticks // 3}: window "
          f"expiries={link.get('window_expiries')} "
          f"epoch={link.get('epoch')} fence refusals="
          f"{refusals.get('fence', 0)} runner exit={exit_code}")
    ok &= report("net_fence", violations, chaos, extra={
        "refusals": refusals, "runner_exit": exit_code,
        "tuning": tuning.as_dict(),
    })

    # 6. cross-host placement (DESIGN.md §26): kill a whole HOST of the
    # two-host placement fleet mid-traffic — every match on it must
    # journal-fail-over ACROSS hosts onto the survivor while players and
    # viewers keep talking to the SAME virtual endpoints (the ingress
    # flips routes; no client ever re-addresses), the untouched host's
    # matches bit-identical to a fault-free control, zero orphans
    from ggrs_tpu.chaos import drive_placement_fleet

    pp = min(p, 2)
    pticks = max(32, min(ticks, 48))
    kill_tick = pticks // 2
    spectate = f"m{pp}"  # a viewer ON the doomed host's match
    p_control = drive_placement_fleet(
        pticks, matches_per_host=pp, seed=seed, n_spectators=2,
        spectate_match=spectate,
    )
    p_control["close"]()

    def kill_h1(i, ctx):
        if i == kill_tick:
            ctx["placement"].kill_host("h1")

    chaos = drive_placement_fleet(
        pticks, matches_per_host=pp, seed=seed, n_spectators=2,
        spectate_match=spectate, inject=kill_h1,
    )
    chaos["close"]()
    h0_matches = [f"m{k}" for k in range(pp)]
    h1_matches = [f"m{k}" for k in range(pp, 2 * pp)]
    violations = fleet_survivor_violations(chaos, p_control, h0_matches)
    violations += fleet_recovery_violations(chaos, h1_matches)
    for mid in h1_matches:
        loc = chaos["locations"][mid]
        if loc is None or loc[0] == "h1":
            violations.append(f"{mid}: not failed over cross-host ({loc})")
    # the public contract: virtual endpoints NEVER change — same vport
    # per match as the fault-free control, peers/viewers never re-aim
    if chaos["vports"] != p_control["vports"]:
        violations.append(
            f"virtual endpoints changed across the host kill: "
            f"{chaos['vports']} vs control {p_control['vports']}"
        )
    hz = chaos["healthz"]
    if (hz.get("route_epoch") or 0) < 2:
        violations.append(
            f"route epoch {hz.get('route_epoch')} not minted past the "
            "dead host (a stale h1 write could still flip a route)"
        )
    flips = int(
        chaos["registry"].value("ggrs_ingress_route_flips_total") or 0
    )
    if flips < len(h1_matches):
        violations.append(
            f"{flips} ingress route flips < {len(h1_matches)} failovers"
        )
    failovers = int(
        chaos["registry"].value("ggrs_placement_host_failovers_total") or 0
    )
    if failovers != len(h1_matches):
        violations.append(
            f"{failovers} host failovers != {len(h1_matches)} affected"
        )
    for v, stream in enumerate(chaos["viewer_streams"]):
        frames = [f for f, _ in stream]
        if frames != sorted(set(frames)):
            violations.append(f"viewer {v} stream reset/regressed")
        if not frames or frames[-1] < kill_tick + 4:
            violations.append(
                f"viewer {v} stalled at {frames[-1] if frames else None} "
                "after the host kill"
            )
    # §28: every failed-over match's merged timeline must carry the
    # FAILOVER event after its ADMIT — the causal record of the kill
    kill_timelines = _placement_timelines(chaos)
    for mid in h1_matches:
        if not first_occurrence_order(
            kill_timelines.get(mid, []), EV_ADMIT, EV_FAILOVER
        ):
            violations.append(
                f"{mid}: merged timeline missing ADMIT -> FAILOVER "
                f"({[e['ev'] for e in kill_timelines.get(mid, [])]})"
            )
    print(f"  [net_placement_host_kill] h1 killed @tick {kill_tick}: "
          f"{sum(1 for m in h1_matches if chaos['locations'][m] and chaos['locations'][m][0] != 'h1')}"
          f"/{len(h1_matches)} matches failed over cross-host, "
          f"route_epoch={hz.get('route_epoch')} flips={flips} "
          f"viewers at {[s[-1][0] if s else None for s in chaos['viewer_streams']]}")
    _write_artifact(artifact_dir, "net_placement_host_kill", {
        "scenario": "net_placement_host_kill",
        "verdict": "PASS" if not violations else "FAIL",
        "violations": violations,
        "matches_per_host": pp,
        "ticks": pticks,
        "locations": {m: list(v) if v else None
                      for m, v in chaos["locations"].items()},
        "vports": chaos["vports"],
        "lost": chaos["lost"],
        "route_epoch": hz.get("route_epoch"),
        "flips": flips,
        "failovers": failovers,
        "healthz": {k: v for k, v in hz.items() if k != "shards"},
        "timeline": kill_timelines,
        "slo": hz.get("slo"),
        **_timeline_trace_artifact(artifact_dir, "net_placement_host_kill",
                                   kill_timelines),
        "metrics": json_snapshot(chaos["registry"]),
    })
    if violations:
        print("  NET_PLACEMENT_HOST_KILL VIOLATED:")
        for v in violations:
            print(f"    {v}")
        ok = False

    # 7. cross-host live migration (§26 + §28): migrate one live match
    # h1 -> h0 mid-traffic; beyond the §26 contract (peer/viewers never
    # re-aim, survivors bit-identical), the §28 acceptance is causal:
    # ONE merged timeline — stitched from both hosts, the placement
    # plane, and the ingress's trace-keyed flip — must read
    # ADMIT -> MIGRATE_BEGIN -> ROUTE_FLIP -> MIGRATE_COMMIT in order,
    # and its Perfetto re-emission must schema-validate
    mig_mid = f"m{pp}"  # pinned to h1
    mig_tick = pticks // 3

    def migrate_m(i, ctx):
        if i == mig_tick:
            ctx["placement"].migrate(mig_mid, reason="chaos")

    chaos = drive_placement_fleet(
        pticks, matches_per_host=pp, seed=seed, n_spectators=2,
        spectate_match=spectate, inject=migrate_m,
    )
    chaos["close"]()
    untouched = [m for m in chaos["match_ids"] if m != mig_mid]
    violations = fleet_survivor_violations(chaos, p_control, untouched)
    violations += fleet_recovery_violations(chaos, [mig_mid])
    mig_loc = chaos["locations"][mig_mid]
    if mig_loc is None or mig_loc[0] != "h0":
        violations.append(
            f"{mig_mid}: not serving on h0 after migration ({mig_loc})"
        )
    if chaos["vports"] != p_control["vports"]:
        violations.append("virtual endpoints changed across the migration")
    mig_timelines = _placement_timelines(chaos)
    mig_events = mig_timelines.get(mig_mid, [])
    if not first_occurrence_order(
        mig_events, EV_ADMIT, EV_MIGRATE_BEGIN, EV_ROUTE_FLIP,
        EV_MIGRATE_COMMIT,
    ):
        violations.append(
            f"{mig_mid}: merged timeline out of causal order: "
            f"{[e['ev'] for e in mig_events]}"
        )
    origins = {e.get("origin", "").split("/")[0] for e in mig_events}
    if not {"h1", "placement"} <= origins:
        violations.append(
            f"{mig_mid}: timeline not cross-source (origins {origins})"
        )
    trace_info = _timeline_trace_artifact(
        artifact_dir, "net_placement_migrate", mig_timelines)
    if trace_info.get("timeline_trace_problems"):
        violations.append(
            "timeline Perfetto export failed schema validation: "
            f"{trace_info['timeline_trace_problems'][:2]}"
        )
    print(f"  [net_placement_migrate] {mig_mid} h1 -> "
          f"{mig_loc[0] if mig_loc else '?'} @tick {mig_tick}: "
          f"{len(mig_events)} timeline events "
          f"({' -> '.join(dict.fromkeys(e['ev'] for e in mig_events))})")
    _write_artifact(artifact_dir, "net_placement_migrate", {
        "scenario": "net_placement_migrate",
        "verdict": "PASS" if not violations else "FAIL",
        "violations": violations,
        "matches_per_host": pp,
        "ticks": pticks,
        "migrated": mig_mid,
        "migrated_to": list(mig_loc) if mig_loc else None,
        "locations": {m: list(v) if v else None
                      for m, v in chaos["locations"].items()},
        "vports": chaos["vports"],
        "lost": chaos["lost"],
        "timeline": mig_timelines,
        "slo": chaos["healthz"].get("slo"),
        **trace_info,
        "metrics": json_snapshot(chaos["registry"]),
    })
    if violations:
        print("  NET_PLACEMENT_MIGRATE VIOLATED:")
        for v in violations:
            print(f"    {v}")
        ok = False

    if ok:
        print(f"  OK: {p}-per-shard TCP fleet resumed severed links "
              "with zero failovers, shrugged off hostile dribble, "
              "failed over a killed host bit-identically, fenced a "
              "resurrected stale runner, and failed a dead HOST over "
              "cross-host behind unchanged virtual endpoints")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--matches", type=int, default=4,
                    help="in-bank 2-peer matches (default 4 -> B=9 slots)")
    ap.add_argument("--ticks", type=int, default=300)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--fault", choices=[*FAULTS, "lockstep", "spectator",
                                        "socket", "shard", "proc", "net",
                                        "all"],
                    default="all")
    ap.add_argument("--fleet-matches", type=int, default=32, metavar="B",
                    help="matches per shard for --fault shard (default 32; "
                         "the acceptance floor)")
    ap.add_argument("--artifact-dir", default=None, metavar="DIR",
                    help="write one machine-readable JSON artifact per "
                         "scenario (digest + verdict + DesyncReport paths)")
    args = ap.parse_args()

    names = (
        [*FAULTS, "lockstep", "spectator", "socket", "shard", "proc", "net"]
        if args.fault == "all"
        else [args.fault]
    )
    ok = True
    for name in names:
        if name == "lockstep":
            ok &= verify_lockstep_leg(
                args.matches, args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        elif name == "proc":
            ok &= verify_proc_leg(
                args.fleet_matches, args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        elif name == "net":
            ok &= verify_net_leg(
                min(args.fleet_matches, 8), args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        elif name == "spectator":
            ok &= verify_broadcast_leg(
                min(args.matches, 2), args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        elif name == "socket":
            ok &= verify_socket_leg(
                min(args.matches, 3), args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        elif name == "shard":
            ok &= verify_fleet_leg(
                args.fleet_matches, args.ticks, args.seed,
                artifact_dir=args.artifact_dir,
            )
        else:
            ok &= verify_leg(name, args.matches, args.ticks, args.seed,
                             artifact_dir=args.artifact_dir)
    print("chaos verdict:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
