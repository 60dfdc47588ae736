"""The configuration ``particles-2p-detect`` and its one cell,
``particles-2p-detect.wan-sat`` (PR 35): ``particles-2p`` as its source runs
it, desync detection on at the stress test's own interval, inside the native
bank.  ``test_benchmark.py`` runs its cases over every cell of
``BENCHMARK.json``, this one included; what only this cell has lives here:
the configuration against ``particles-2p``'s, the exchange in a rehearsal
(reports sent and compared at every session, none differing, no slot off the
bank), a state altered mid-run, the two per-layer metrics and the reducer
they brought, the reference's ``report_digests``."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import run  # noqa: E402
from benchmark.reducers import registry_ratio  # noqa: E402
from benchmark.reference import digest, particles, particles_detect  # noqa: E402
from ggrs_tpu.core import DesyncDetected, DesyncDetection  # noqa: E402
from ggrs_tpu.obs.registry import default_registry  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "particles-2p-detect.wan-sat"
TWIN = "particles-2p.wan-sat"
SEED = 2**31 + 35
SENT = "ggrs_pool_checksum_reports_sent_total"
COMPARED = "ggrs_pool_checksum_compares_total"
DESYNCS = "ggrs_pool_desyncs_total"
METRICS = ("checksum_exchange_share.sat", "checksum_fetch_ms_p50.sat")


def counted():
    registry = default_registry()
    return {n: registry.value(n) or 0.0 for n in (SENT, COMPARED, DESYNCS)}


def rehearse(monkeypatch, trace=False, alter=None):
    """One CPU rehearsal of the cell at 4 matches; returns the result, the
    run's ``Pool`` and what the run added to the three counters."""
    seen = {}
    compare, tick = run.compare, run.Pool.tick

    def spy(pool, *args):
        seen["pool"] = pool
        return compare(pool, *args)

    def altered(pool, row):
        # in the set-up's first ticks, each of which is fenced: the digests
        # land a tick after they are asked for however slow this CPU is
        if alter is not None and pool.ticks == 12:
            alter(pool)
        tick(pool, row)

    monkeypatch.setattr(run, "compare", spy)
    monkeypatch.setattr(run.Pool, "tick", altered)
    before = counted()
    result = run.run_cell(CELL, SEED, 0.25, trace, matches=4)
    added = {n: v - before[n] for n, v in counted().items()}
    return result, seen["pool"], added


def desyncs_of(pool):
    return {s: [e for e in pool.host.events(s) if isinstance(e, DesyncDetected)]
            for s in range(pool.sessions)}


# --- the configuration and the cell -----------------------------------------


def test_the_configuration_is_particles_2p_but_for_its_guarantee():
    spec, twin = run.load_cell(REPO, CELL), run.load_cell(REPO, TWIN)
    config, base = spec["config"], twin["config"]
    differs = {k for k in set(config) | set(base) if config.get(k) != base.get(k)}
    assert differs == {"name", "source", "deployment", "adapter",
                       "desync_detection", "guarantees", "departs_from_source",
                       "assumed"}
    assert config["adapter"] == "particles_detect"
    assert config["desync_detection"] == {"interval": 10}
    assert config["reduced"] == {}
    assert set(config["departs_from_source"]) == {"arithmetic"}
    assert (config["departs_from_source"]["arithmetic"]
            == base["departs_from_source"]["arithmetic"])
    assert set(config["guarantees"]) == set(base["guarantees"]) | {
        "desyncs_detected"}
    assert set(config["assumed"]) == set(base["assumed"]) | {"desync_interval"}
    # the same traffic, population and sizes: the cells differ in one thing
    assert spec["traffic"] == twin["traffic"]
    for key in ("matches", "trace_ticks", "witness_by_frame"):
        assert spec["size"][key] == twin["size"][key]
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "wan-sat"
    entry = next(c for c in BENCH["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and "particles.rs" in entry["source"]
    assert "desync-detection-interval" in entry["source"]


def test_the_cell_is_on_every_sat_list_and_brings_two_metrics():
    """Looked up by name, wherever later entries are appended: the cell and
    its configuration are entries of ``BENCHMARK.json``, it reports what its
    twin reports end to end, it is on every per-layer list its twin is on,
    and its two metrics are read there alone.  Beyond its twin's it reads
    only metrics that name this cell alone (the checksum exchange's)."""
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "session_ticks_per_s"
    for metric in BENCH["end_to_end"]:
        assert run._applies(metric, CELL) == run._applies(metric, TWIN)
    for metric in BENCH["per_layer"]:
        if run._applies(metric, TWIN):
            assert run._applies(metric, CELL), metric["name"]
    assert CELL in {w["name"] for w in BENCH["workloads"]}
    assert "particles-2p-detect" in {c["name"] for c in BENCH["configs"]}
    spec = run.load_cell(REPO, CELL)
    names = {m["name"] for m in spec["metrics"]["per_layer"]}
    twin = {m["name"] for m in run.load_cell(REPO, TWIN)["metrics"]["per_layer"]}
    alone = {m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]}
    assert set(METRICS) <= alone
    assert names == twin | alone
    assert {m["name"] for m in spec["metrics"]["end_to_end"]} == {
        "session_ticks_per_s", "setup_s"}


def test_the_adapter_turns_detection_on_through_the_config():
    from benchmark.adapters import particles as plain
    from benchmark.adapters import particles_detect as adapter
    from ggrs_tpu.sessions import SessionBuilder

    config = run.load_cell(REPO, CELL)["config"]
    assert adapter.session_config().desync_detection == DesyncDetection.on(
        config["desync_detection"]["interval"])
    assert plain.session_config().desync_detection == DesyncDetection.off()
    assert (SessionBuilder(adapter.session_config())._desync_detection
            == DesyncDetection.on(10))
    for name in ("make_game", "example_inputs", "inputs_to_array",
                 "raw_inputs_to_array"):
        assert getattr(adapter, name) is getattr(plain, name)


# --- the exchange, in a rehearsal -------------------------------------------


def test_a_rehearsal_exchanges_reports_on_the_bank_and_none_differs(
        no_chip_needed, monkeypatch):
    result, pool, added = rehearse(monkeypatch)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["slots_off_bank"]["value"] == 0
    assert result["checks"]["native_bank_inactive"]["value"] == 0
    assert result["checks"]["compiles_in_window"]["value"] == 0
    host = pool.host
    assert host.native_active and host.crossings == host.plan_ticks == pool.ticks
    # at least one report sent and one compared a session, none differing
    assert added[COMPARED] >= pool.sessions
    assert added[SENT] >= added[COMPARED]
    assert added[DESYNCS] == 0
    assert all(not found for found in desyncs_of(pool).values())
    interval = 10
    for s in range(pool.sessions):
        sent = host.flight_recorder(s).checksums.frames()
        assert sent and sent == list(range(interval, sent[-1] + 1, interval))
        # (the CPU device trails the host by up to a fence period and the
        # untimed hold: the digests land late here, and are sent late)
        assert sent[-1] >= 3 * interval
        assert host.desync_report(s) is None


def test_a_state_altered_mid_run_is_detected_by_its_peer_and_counted(
        no_chip_needed, monkeypatch):
    def alter(pool):
        # session 1 leaves the simulation its peer (session 0) runs: its
        # emitters stand one unit off from here on
        carry = pool.executor._carry
        live = carry["live"]
        pool.executor._carry = {
            "live": {**live, "emitter": live["emitter"].at[1].add(1)},
            "ring": carry["ring"],
        }

    result, pool, added = rehearse(monkeypatch, alter=alter)
    assert result["correct"] is False
    assert result["checks"]["state_mismatch_sessions"]["value"] == 1
    assert result["checks"]["slots_off_bank"]["value"] == 0
    found = desyncs_of(pool)
    assert found[0] and found[1], "both peers of match 0 see it"
    assert not any(found[s] for s in range(2, pool.sessions))
    first = found[0][0]
    assert first.addr == "m0p1" and first.local_checksum != first.remote_checksum
    assert first.frame == 20  # the first interval frame after tick 12
    assert added[DESYNCS] == len(found[0]) + len(found[1])
    report = pool.host.desync_report(0)
    assert report.kind == "checksum-compare"
    assert report.detected_frame == first.frame


def test_a_traced_rehearsal_reads_both_metrics(no_chip_needed, monkeypatch):
    result, pool, added = rehearse(monkeypatch, trace=True)
    assert result["correct"] is True, result["checks"]
    share = result["metrics"]["checksum_exchange_share.sat"]
    # what is missing is in flight at the end: 3 ticks of link, the lag
    assert share["unit"] == "%" and 80.0 <= share["value"] <= 100.0
    fetch = result["metrics"]["checksum_fetch_ms_p50.sat"]
    assert fetch["unit"] == "ms" and 0 < fetch["value"] < 1000
    assert result["metrics"]["fast_slot_share.sat"]["value"] == 100.0
    # (at 8 sessions on the CPU the twin cell reads 87% as well)
    assert result["metrics"]["span_coverage_share.sat"]["value"] > 75.0
    # one descriptor buffer a dispatch on one chip: the fetch is no part of
    # the launch
    assert result["metrics"]["launch_transfers_per_dispatch.sat"]["value"] == 1.0


# --- the reducer and the reference ------------------------------------------


def test_registry_ratio_reads_two_counters_or_nothing(monkeypatch):
    from ggrs_tpu.obs import registry

    fresh = registry.Registry()
    monkeypatch.setattr(registry, "DEFAULT", fresh)
    args = {"num": COMPARED, "den": SENT, "scale": 100.0}
    # a program without the counters (this PR's parent), or a pool that
    # never detects: nothing, never 0
    assert registry_ratio.reduce({}, args) is None
    sent = fresh.counter(SENT, "")
    compared = fresh.counter(COMPARED, "")
    assert registry_ratio.reduce({}, args) is None
    sent.inc(8)
    assert registry_ratio.reduce({}, args) is None
    compared.inc(6)
    assert registry_ratio.reduce({}, args) == pytest.approx(75.0)


def test_report_digests_names_the_interval_frames_and_their_digests():
    small = {"players": 2, "capacity": 200, "rate": 2, "ttl_frames": 50,
             "desync_detection": {"interval": 10}}
    rng = np.random.default_rng(7)
    state, by_frame = particles_detect.init_state(small, 2), {}
    by_frame[0] = state
    for f in range(1, 32):
        state = particles_detect.advance(
            small, state, rng.integers(0, 16, (2, 2)).astype(np.uint8))
        by_frame[f] = state
    reports = particles_detect.report_digests(small, by_frame)
    assert sorted(reports) == [10, 20, 30]
    for frame, digests in reports.items():
        assert digests == [
            digest.u128({k: v[m] for k, v in by_frame[frame].items()})
            for m in range(2)]
    assert reports[10][0] != reports[10][1] != reports[20][1]
    # the family's own reference, re-exported: the same functions
    for name in ("init_state", "advance", "witness", "state_bytes"):
        assert getattr(particles_detect, name) is getattr(particles, name)
