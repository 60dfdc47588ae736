"""Desync detection inside the native bank (docs/DESIGN.md §4).

The plain reference of the protocol is the per-session Python ``P2PSession``
(``tests/test_desync_device_executor.py`` is its two-peer form).  These tests
drive the same seeded matches through both tiers of ``HostSessionPool``, the
Python sessions and the bank, each fulfilled by a ``BatchedRequestExecutor``,
and hold the bank to the Python tier: the same ``ChecksumReport``s on the
wire, digests equal to ``benchmark/reference/digest.py`` over the plain
reference's states, the same ``DesyncDetected`` events when one session's
state is altered, with and without loss, on one device and over a mesh.
"""

import random
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax  # noqa: E402

from benchmark.adapters import boxgame as adapter  # noqa: E402
from benchmark.reference import boxgame as reference  # noqa: E402
from benchmark.reference import digest as reference_digest  # noqa: E402
from ggrs_tpu.core import DesyncDetected, DesyncDetection, Local, Remote  # noqa: E402
from ggrs_tpu.net import InMemoryNetwork  # noqa: E402
from ggrs_tpu.net.messages import ChecksumReport, Message  # noqa: E402
from ggrs_tpu.net.protocol import MAX_CHECKSUM_HISTORY_SIZE  # noqa: E402
from ggrs_tpu.obs import default_tracer  # noqa: E402
from ggrs_tpu.obs.registry import Registry  # noqa: E402
from ggrs_tpu.parallel import (  # noqa: E402
    BatchedRequestExecutor,
    HostedPool,
    HostSessionPool,
    make_mesh,
)
from ggrs_tpu.parallel.host_bank import _bank_eligible  # noqa: E402
from ggrs_tpu.parallel.session_pool import blank_desc  # noqa: E402
from ggrs_tpu.sessions import SessionBuilder  # noqa: E402

CONFIG = {"players": 2}
PLAYERS = 2
DELAY = 2
WINDOW = 8


class Recording:
    """A socket that notes every ``ChecksumReport`` its session sends."""

    def __init__(self, inner, log, session):
        self.inner, self.log, self.session = inner, log, session

    def send_to(self, msg, addr):
        body = Message.decode(bytes(msg.encode())).body
        if isinstance(body, ChecksumReport):
            self.log.append((self.session, body.frame, body.checksum))
        self.inner.send_to(msg, addr)

    def receive_all_datagrams(self):
        return self.inner.receive_all_datagrams()

    def receive_all_messages(self):
        return self.inner.receive_all_messages()


class Match:
    """``matches`` two-player matches, both peers of each in one pool."""

    def __init__(self, tier, monkeypatch, matches=3, interval=5, loss=0.0,
                 seed=11, mesh=None, detection=True, registry=None):
        if tier == "python":
            monkeypatch.setenv("GGRS_TPU_NO_NATIVE", "1")
        else:
            monkeypatch.delenv("GGRS_TPU_NO_NATIVE", raising=False)
        self.clock = [0]
        self.net = InMemoryNetwork(seed=seed, latency_ticks=3, loss=loss)
        self.host = HostSessionPool(metrics=registry)
        self.reports = []
        self.sessions = matches * PLAYERS
        for m in range(matches):
            for k in range(PLAYERS):
                builder = (
                    SessionBuilder(adapter.session_config())
                    .with_num_players(PLAYERS)
                    .with_clock(lambda: self.clock[0])
                    .with_rng(random.Random(seed * 7919 + 5 * m + k))
                    .with_max_prediction_window(WINDOW)
                    .with_input_delay(DELAY)
                )
                if detection:
                    builder = builder.with_desync_detection_mode(
                        DesyncDetection.on(interval))
                for j in range(PLAYERS):
                    who = Local() if j == k else Remote(f"m{m}p{j}")
                    builder = builder.add_player(who, j)
                self.host.add_session(builder, Recording(
                    self.net.socket(f"m{m}p{k}"), self.reports,
                    m * PLAYERS + k))
        assert self.host.native_active == (tier == "bank")
        game = adapter.make_game(CONFIG)
        across = {"mesh": mesh} if mesh is not None else {}
        self.executor = BatchedRequestExecutor(
            game.advance, game.init_state(), adapter.inputs_to_array,
            batch_size=self.sessions, ring_length=WINDOW + 2,
            max_burst=WINDOW + 1,
            raw_inputs_to_array=adapter.raw_inputs_to_array, **across)
        self.executor.warmup(adapter.example_inputs(CONFIG))
        self.hosted = HostedPool(self.host, self.executor)
        # what every player presses while its session stands at a frame:
        # held four frames, so a minority of ticks roll back
        rng = random.Random(seed + 1)
        self.schedule = [
            [rng.randrange(16) for _ in range(self.sessions)]
            for _ in range(200)
        ]
        self.events = [[] for _ in range(self.sessions)]
        self.ticks = 0

    def pressed(self, frame, session):
        return self.schedule[frame // 4][session]

    def run(self, ticks):
        host = self.host
        for _ in range(ticks):
            self.clock[0] = (self.ticks * 1000) // 60
            self.hosted.tick([
                (s, s % PLAYERS, self.pressed(host.current_frame(s), s))
                for s in range(self.sessions)
            ])
            # the CPU device is slow: keep the host from running ahead of it
            self.hosted.block_until_ready()
            self.net.tick()
            self.ticks += 1
            for s in range(self.sessions):
                self.events[s].extend(host.events(s))

    def desyncs(self):
        return sorted(
            (s, e.frame, e.local_checksum, e.remote_checksum, e.addr)
            for s, evs in enumerate(self.events) for e in evs
            if isinstance(e, DesyncDetected)
        )

    def reference_digest(self, match, frame):
        """The u128 of the plain reference's state of ``match`` after
        ``frame`` frames of the true inputs."""
        state = reference.init_state(CONFIG, 1)
        for f in range(frame):
            row = np.zeros((1, PLAYERS), np.uint8)
            if f >= DELAY:
                row[0] = [self.pressed(f - DELAY, match * PLAYERS + k)
                          for k in range(PLAYERS)]
            state = reference.advance(CONFIG, state, row)
        return reference_digest.u128({k: v[0] for k, v in state.items()})


def reported_to(reports, sessions):
    """The newest frame every session has reported."""
    newest = {s: -1 for s in range(sessions)}
    for s, frame, _ in reports:
        newest[s] = max(newest[s], frame)
    return min(newest.values())


def both_tiers(monkeypatch, ticks, alter_at=None, **kw):
    out = {}
    for tier in ("python", "bank"):
        match = Match(tier, monkeypatch, **kw)
        if alter_at is None:
            match.run(ticks)
        else:
            match.run(alter_at)
            live = match.executor._carry["live"]
            # session 3 leaves the simulation its peer (session 2) runs
            match.executor._carry = {
                "live": {**live, "pos": live["pos"].at[3].add(1)},
                "ring": match.executor._carry["ring"],
            }
            match.run(ticks - alter_at)
        out[tier] = match
    return out["python"], out["bank"]


@pytest.mark.parametrize("loss", [0.0, 0.05], ids=["lossless", "loss5"])
def test_the_bank_sends_the_reports_the_python_tier_sends(monkeypatch, loss):
    interval = 5
    python, bank = both_tiers(monkeypatch, 90, interval=interval, loss=loss)
    assert bank.host.native_active and not python.host.native_active
    assert all(bank.host.slot_state(s) == "native"
               for s in range(bank.sessions))
    # every session, both tiers: each interval frame once, in frame order
    for match in (python, bank):
        for s in range(match.sessions):
            frames = [f for who, f, _ in match.reports if who == s]
            assert frames == sorted(set(frames))
            assert frames == list(range(interval, frames[-1] + 1, interval))
    common = min(reported_to(python.reports, python.sessions),
                 reported_to(bank.reports, bank.sessions))
    # the exchange kept up: the bank is at most an interval and its lag
    # behind what the last ticks could have confirmed
    lag = bank.executor.checksum_lag_ticks_max
    assert common >= 90 - WINDOW - DELAY - 2 * interval - lag - 12 * (loss > 0)

    def upto(match):
        return {r for r in match.reports if r[1] <= common}

    assert upto(bank) == upto(python)
    # and they are the digests of the plain reference's states
    for s, frame, digest in sorted(upto(bank)):
        assert digest == bank.reference_digest(s // PLAYERS, frame), (s, frame)
    assert python.desyncs() == [] and bank.desyncs() == []
    # rollbacks happened, on a minority of ticks
    loads = bank.host._m_rollbacks.value
    assert 0 < loads < 0.5 * bank.ticks * bank.sessions


@pytest.mark.parametrize("loss", [0.0, 0.05], ids=["lossless", "loss5"])
def test_an_altered_state_raises_the_same_events_on_both_tiers(
        monkeypatch, loss):
    interval = 5
    python, bank = both_tiers(monkeypatch, 100, alter_at=42,
                              interval=interval, loss=loss)
    lag = bank.executor.checksum_lag_ticks_max
    assert python.desyncs(), "the Python tier must see the divergence"
    newest = max(frame for _, frame, *_ in bank.desyncs())
    assert newest >= max(f for _, f, *_ in python.desyncs()) - interval - lag
    # both peers of match 1, nobody else
    assert {s for s, *_ in bank.desyncs()} == {2, 3}
    if not loss:
        # the same events at the same frames, within an interval and the lag
        assert bank.desyncs() == [
            d for d in python.desyncs() if d[1] <= newest]
    else:
        # which reports the link drops depends on the tick they leave at,
        # so the tiers miss different ones: every event either tier raises
        # crosses what its two peers reported of that frame, and most
        # interval frames since the divergence raise one
        for match in (python, bank):
            sent = {(s, f): d for s, f, d in match.reports}
            for s, frame, local, remote, _ in match.desyncs():
                assert local == sent[s, frame]
                assert remote == sent[s ^ 1, frame]
        assert len(bank.desyncs()) >= 0.7 * len(
            [d for d in python.desyncs() if d[1] <= newest])
    first = min(frame for _, frame, *_ in bank.desyncs())
    assert 42 - WINDOW - DELAY <= first <= 42 + interval
    for s, frame, local, remote, addr in bank.desyncs():
        assert local != remote
        assert addr == f"m1p{1 - s % PLAYERS}"
    # the report the Python session yields, for a bank slot
    report = bank.host.desync_report(2)
    assert report is not None and report.kind == "checksum-compare"
    assert report.detected_frame == first
    assert report.first_divergent_frame == first
    assert report.local_checksum != report.remote_checksum
    assert bank.host.desync_report(0) is None
    assert all(bank.host.slot_state(s) == "native"
               for s in range(bank.sessions))


def test_the_reference_decoder_carries_the_exchange_too(monkeypatch):
    """``GGRS_TPU_NO_FASTPATH`` (the decoder the descriptor plane is held
    to) hands the executor plain lists: they carry the wanted rows as the
    plan does, and the same reports leave."""
    plan = Match("bank", monkeypatch, interval=5)
    plan.run(50)
    monkeypatch.setenv("GGRS_TPU_NO_FASTPATH", "1")
    lists = Match("bank", monkeypatch, interval=5)
    lists.run(50)
    assert lists.host.native_active and lists.host.plan_ticks == 0
    assert plan.host.plan_ticks == 50
    assert lists.reports and sorted(lists.reports) == sorted(plan.reports)
    assert lists.desyncs() == [] and plan.desyncs() == []
    assert lists.host._m_cs_compares.value == plan.host._m_cs_compares.value


def test_a_slot_evicted_from_the_bank_keeps_reporting_on_the_grid(
        monkeypatch):
    """A faulted slot resumes on a Python session (DESIGN.md §9) that
    reports on the interval's grid, so its peer in the bank still compares
    what it sends, and it compares its peer's: no report differs."""
    interval = 10
    match = Match("bank", monkeypatch, interval=interval)
    match.run(33)
    match.host.inject_slot_error(2)
    match.run(70)
    assert match.host.slot_state(2) == "evicted"
    assert all(match.host.slot_state(s) == "native"
               for s in range(match.sessions) if s != 2)
    frames = [f for s, f, _ in match.reports if s == 2]
    assert frames == sorted(set(frames)) and frames[-1] >= 80
    assert all(f % interval == 0 for f in frames)
    # both ends of match 1 went on comparing after the eviction
    session = match.host.session(2)
    assert session._last_sent_checksum_frame >= 80
    # (what the bank had sent of frame 30 went with the slot: its peer's
    # report of it waits for ever; every later one was compared and went)
    pending = session._player_reg.remotes["m1p1"].pending_checksums
    assert [f for f in pending if 30 < f <= 70] == []
    assert match.desyncs() == []
    for s, frame, digest in match.reports:
        assert digest == match.reference_digest(s // PLAYERS, frame), (s, frame)


@pytest.mark.parametrize("tweak, native", [
    (lambda b: b.with_desync_detection_mode(DesyncDetection.on(10)), True),
    (lambda b: b.with_desync_detection_mode(DesyncDetection.on(1)), True),
    (lambda b: b, True),
    (lambda b: b.with_sync_handshake(True), False),
    (lambda b: b.with_sparse_saving_mode(True), False),
    (lambda b: b.with_max_prediction_window(0), False),
], ids=["detect10", "detect1", "plain", "handshake", "sparse", "lockstep"])
def test_eligibility(tweak, native):
    """Detection is inside the bank's scope; the handshake, sparse saving
    and lockstep still are not."""
    net = InMemoryNetwork()
    pool = HostSessionPool()
    for me in (0, 1):
        builder = tweak(
            SessionBuilder(adapter.session_config())
            .with_clock(lambda: 0)
            .with_rng(random.Random(me))
            .add_player(Local(), me)
            .add_player(Remote(f"p{1 - me}"), 1 - me)
        )
        assert _bank_eligible(builder) == native
        pool.add_session(builder, net.socket(f"p{me}"))
    assert pool.native_active == native


def test_the_configs_mode_is_where_the_builder_starts():
    """A title's detection interval reaches the builder through its
    ``Config``; ``with_desync_detection_mode`` still overrides it."""
    import dataclasses

    config = adapter.session_config()
    assert SessionBuilder(config)._desync_detection == DesyncDetection.off()
    on = dataclasses.replace(config,
                             desync_detection=DesyncDetection.on(10))
    assert SessionBuilder(on)._desync_detection == DesyncDetection.on(10)
    assert SessionBuilder(on).with_desync_detection_mode(
        DesyncDetection.off())._desync_detection == DesyncDetection.off()


class Compiles:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def test_nothing_compiles_after_warmup_and_one_fetch_serves_a_tick(
        monkeypatch):
    interval = 5
    match = Match("bank", monkeypatch, interval=interval)
    calls = []
    fetch = match.executor._fetch_digests
    match.executor._fetch_digests = lambda *a: calls.append(1) or fetch(*a)
    meter = Compiles()
    match.run(3 * interval + WINDOW + DELAY)
    assert meter.n == 0
    # one fixed-shape read a tick that wants any digest, whatever the
    # number of sessions that want one: never more reads than ticks
    assert 0 < len(calls) <= match.ticks
    assert len(match.reports) > len(calls)
    assert match.host.crossings == match.host.plan_ticks == match.ticks


def report_bytes(frame, checksum):
    return bytes(Message(
        magic=0, body=ChecksumReport(checksum=checksum, frame=frame)).encode())


def test_the_histories_are_bounded_as_the_python_sessions_are(monkeypatch):
    """A report for a frame that has left the local history (32 reports
    deep) is never compared; one for a frame still in it is.  And a peer's
    pending window keeps its newest 32 reports."""
    registry = Registry()
    match = Match("bank", monkeypatch, matches=1, interval=1,
                  registry=registry)
    match.run(MAX_CHECKSUM_HISTORY_SIZE + 30)
    assert match.desyncs() == []
    sent = match.host._m_cs_sent.value
    assert sent >= 2 * (MAX_CHECKSUM_HISTORY_SIZE + 10)
    compared = match.host._m_cs_compares.value
    # a forged report for frame 3, long gone from the history: no compare
    match.host.inject_datagram(0, "m0p1", report_bytes(3, 12345))
    match.run(3)
    assert match.desyncs() == []
    # a forged report for a frame still in it: compared, and it differs
    recent = max(f for s, f, _ in match.reports if s == 0) - 2
    match.host.inject_datagram(0, "m0p1", report_bytes(recent, 12345))
    match.run(8)
    assert [(s, f, r) for s, f, _, r, _ in match.desyncs()] == [
        (0, recent, 12345)]
    assert match.host._m_cs_desyncs.value == 1
    assert match.host._m_cs_compares.value > compared
    # forty reports for frames far ahead: the window keeps the newest 32,
    # so when those frames are confirmed only they are compared
    ahead = match.host.current_frame(0) + 20
    for i in range(40):
        match.host.inject_datagram(0, "m0p1", report_bytes(ahead + i, 777))
    match.run(20 + 40 + WINDOW + 6)
    forged = sorted(f for s, f, _, r, _ in match.desyncs() if r == 777)
    assert forged == list(range(ahead + 8, ahead + 40))


def test_a_mesh_of_four_devices_reports_what_one_device_reports(monkeypatch):
    one = Match("bank", monkeypatch, matches=4, interval=5, loss=0.05)
    one.run(60)
    four = Match("bank", monkeypatch, matches=4, interval=5, loss=0.05,
                 mesh=make_mesh(4))
    four.run(60)
    assert len(four.executor._carry["ring"]["checksums"].sharding.device_set) == 4
    common = min(reported_to(one.reports, one.sessions),
                 reported_to(four.reports, four.sessions))
    assert common >= 30
    assert ({r for r in four.reports if r[1] <= common}
            == {r for r in one.reports if r[1] <= common})
    for s, frame, digest in four.reports:
        assert digest == four.reference_digest(s // PLAYERS, frame)
    assert four.desyncs() == [] and one.desyncs() == []
    assert four.host.native_active


def tick_outputs(match, ticks):
    """The bank's tick output, byte for byte, tick by tick."""
    outputs = []
    host = match.host
    for _ in range(ticks):
        match.run(1)
        outputs.append(bytes(host._out_buf.raw[: host._out_len.value]))
    return outputs


def test_with_detection_off_the_tick_is_what_it_was(monkeypatch):
    """A pool whose sessions do not detect: no wanted tail in the tick
    output, no wanted rows on the plan, no fetch, no span, the ten
    descriptor arrays.  And until the first interval frame is confirmed a
    detecting pool's output IS the other's, plus the empty tail."""
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    off = Match("bank", monkeypatch, detection=False)
    on = Match("bank", monkeypatch, interval=10)
    tracer.switch(True)
    try:
        outputs_off = tick_outputs(off, 8)
        spans_off = {e[1] for e in tracer.events()}
        tracer.clear()
        outputs_on = tick_outputs(on, 8)
    finally:
        tracer.switch(False)
        tracer.clear()
    tail = 10 * 8 + 8 + 1  # the timing tail of a traced tick: ten phases
    empty = bytes(16)  # no wanted row, nothing sent, compared or differing
    for a, b in zip(outputs_off, outputs_on):
        assert b[:-tail] == a[:-tail] + empty
    assert "device.checksum_fetch" not in spans_off
    assert "bank.checksum" not in spans_off  # the phase reads 0: no span
    host = off.host
    n = len(host._mirrors)
    hdr = np.frombuffer(outputs_off[-1], np.dtype(
        [("flags", "<u4"), ("rec_len", "<u4"), ("rest", "V40")]), count=n)
    assert (len(outputs_off[-1]) - tail
            == n * (48 + 24) + int(hdr["rec_len"].sum()))
    assert host._plan.checksum_wanted is None
    assert not off.executor._digest_fetches
    off.run(40)
    assert not off.executor._digest_fetches and off.reports == []
    assert sorted(blank_desc(4, 9, (2,), np.uint8)) == sorted([
        "pre_save", "pre_frame", "do_load", "load_frame", "postload_save",
        "postload_frame", "n_adv", "inputs", "save_mask", "save_frame"])


def test_the_exchange_is_traced_and_counted(monkeypatch):
    """``device.checksum_fetch`` under ``hosted.tick`` on the ticks that
    ask or land, the ``bank.checksum`` phase in the crossing, the three
    counters."""
    registry = Registry()
    tracer = default_tracer()
    tracer.switch(False)
    tracer.clear()
    match = Match("bank", monkeypatch, interval=5, registry=registry)
    match.run(12)
    tracer.switch(True)
    try:
        match.run(20)
        events = tracer.events()
    finally:
        tracer.switch(False)
        tracer.clear()
    fetches = [e for e in events if e[1] == "device.checksum_fetch"]
    assert fetches and all(e[6]["parent"] == "hosted.tick" for e in fetches)
    assert sum(e[6]["wanted"] for e in fetches) >= 3 * match.sessions
    assert sum(e[6]["landed"] for e in fetches) >= 3 * match.sessions
    assert all(e[6]["lag_ticks"] >= 0 for e in fetches)
    assert len({e[6]["tick"] for e in fetches}) < 20  # not on every tick
    phases = [e for e in events if e[1] == "bank.checksum"]
    assert 0 < len(phases) <= 20 and all(e[4] > 0 for e in phases)
    sent = registry.value("ggrs_pool_checksum_reports_sent_total")
    compared = registry.value("ggrs_pool_checksum_compares_total")
    assert sent >= 5 * match.sessions and 0 < compared <= sent
    assert not registry.value("ggrs_pool_desyncs_total")
