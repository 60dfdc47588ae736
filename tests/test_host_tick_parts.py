"""The host tick read by part from inside (ISSUE 37, DESIGN.md §14): the
spans, the argument and the counter that ``HostedPool.tick`` gained so that
each piece ROADMAP A1 and A8 remove has a number of its own.

- ``device.fulfill`` under ``device.fill``, ``checksum.deliver`` and
  ``checksum.ask`` under ``device.checksum_fetch``: once a tick (a landed or
  an asked fetch), with ``parent`` and ``tick``, named letter for letter as
  the benchmark's metric files name them, and no longer than what holds them;
- ``device_ready`` on ``device.launch``: read only where the tracer records;
- a tracer asleep records nothing, never asks ``is_ready``, and serves the
  same decoder, the same fetch, the same states and the same wire bytes;
- the process counts its own compiles, and names the tick one fell in;
- every span a metric file names has a row in DESIGN.md §14's table.
"""

from __future__ import annotations

import functools
import json
import random
import re
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax  # noqa: E402

from benchmark.adapters import boxgame as adapter  # noqa: E402
from ggrs_tpu.core import DesyncDetection, Local, Remote  # noqa: E402
from ggrs_tpu.net import InMemoryNetwork, _native  # noqa: E402
from ggrs_tpu.obs import default_tracer  # noqa: E402
from ggrs_tpu.obs.registry import Registry, default_registry  # noqa: E402
from ggrs_tpu.obs.trace import spans_by_tick  # noqa: E402
from ggrs_tpu.parallel import (  # noqa: E402
    BatchedRequestExecutor,
    HostedPool,
    HostSessionPool,
)
from ggrs_tpu.sessions import SessionBuilder  # noqa: E402

pytestmark = pytest.mark.skipif(
    _native.bank_lib() is None, reason="native session bank unavailable"
)

METRICS = REPO / "benchmark" / "metrics"
PLAYERS, DELAY, WINDOW, INTERVAL = 2, 2, 8, 5
COMPILES = "ggrs_process_backend_compiles_total"


def span_of(metric: str) -> str:
    """The one span a metric file of this PR reads."""
    args = json.loads((METRICS / f"{metric}.json").read_text())["args"]
    (name,) = args.get("add") or [args["span"]]
    return name


FULFILL = span_of("quiet_fulfill_ms_p50")
DELIVER = span_of("checksum_deliver_ms_p50")
ASK = span_of("checksum_ask_ms_p50")
FENCE = span_of("fence_wait_ms_p50.paced")
LAUNCH = span_of("device_ready_at_launch_share")
DECODE = span_of("plan_decode_ms_p50")
BUILD = span_of("command_build_ms_p50")


class Served:
    """``matches`` two-player BoxGame matches, both peers of each in one
    native-bank pool over one in-memory network, every datagram noted;
    ``detect``: desync detection on at ``INTERVAL`` (DESIGN.md §4)."""

    def __init__(self, detect: bool, matches: int = 3, seed: int = 11):
        self.clock = [0]
        self.net = InMemoryNetwork(seed=seed, latency_ticks=3)
        self.wire = []
        send = self.net._send

        def noted(src, dst, payload):
            self.wire.append((src, dst, bytes(payload)))
            send(src, dst, payload)

        self.net._send = noted
        # (its own registry: other files' tests read the process's counters)
        self.host = HostSessionPool(metrics=Registry())
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
                if detect:
                    builder = builder.with_desync_detection_mode(
                        DesyncDetection.on(INTERVAL))
                for j in range(PLAYERS):
                    who = Local() if j == k else Remote(f"m{m}p{j}")
                    builder = builder.add_player(who, j)
                self.host.add_session(builder, self.net.socket(f"m{m}p{k}"))
        assert self.host.native_active, self.host.native_reason
        game = adapter.make_game({"players": PLAYERS})
        self.executor = BatchedRequestExecutor(
            game.advance, game.init_state(), adapter.inputs_to_array,
            batch_size=self.sessions, ring_length=WINDOW + 2,
            max_burst=WINDOW + 1,
            raw_inputs_to_array=adapter.raw_inputs_to_array)
        self.executor.warmup(adapter.example_inputs({"players": PLAYERS}))
        self.hosted = HostedPool(self.host, self.executor)
        rng = random.Random(seed + 1)
        self.schedule = [[rng.randrange(16) for _ in range(self.sessions)]
                         for _ in range(200)]
        self.ticks = 0

    def run(self, ticks: int) -> None:
        host = self.host
        for _ in range(ticks):
            self.clock[0] = (self.ticks * 1000) // 60
            self.hosted.tick([
                (s, s % PLAYERS, self.schedule[host.current_frame(s) // 4][s])
                for s in range(self.sessions)
            ])
            # the CPU device is slow: a fence a tick, of the carry and of
            # the digests on their way, so that a digest lands the tick
            # after it is asked for whatever the machine's load
            self.hosted.block_until_ready()
            for fetch in self.executor._digest_fetches:
                fetch[4].block_until_ready()
            self.net.tick()
            self.ticks += 1


@pytest.fixture
def tracer():
    t = default_tracer()
    t.switch(False)
    t.clear()
    yield t
    t.switch(False)
    t.clear()


def traced_ticks(tracer, pool, warm: int, ticks: int):
    pool.run(warm)
    tracer.switch(True)
    pool.run(ticks)
    tracer.switch(False)
    assert tracer.dropped == 0
    by_tick = spans_by_tick(tracer.events())
    assert sorted(by_tick) == list(range(warm + 1, warm + ticks + 1))
    return by_tick


def named(events, name):
    return [e for e in events if e[1] == name]


def inside(child, parent) -> bool:
    return parent[3] <= child[3] and child[3] + child[4] <= parent[3] + parent[4]


def test_every_tick_has_its_parts_and_they_fit(tracer):
    pool = Served(detect=False, matches=4)
    by_tick = traced_ticks(tracer, pool, 30, 40)
    resims = 0
    for tick, events in by_tick.items():
        (fulfill,) = named(events, FULFILL)
        (fill,) = named(events, "device.fill")
        (decode,) = named(events, DECODE)
        (build,) = named(events, BUILD)
        (whole,) = named(events, "pool.tick")
        (crossing,) = named(events, "bank.crossing")
        (launch,) = named(events, LAUNCH)
        (fence,) = named(events, FENCE)
        assert fulfill[6]["parent"] == "device.fill" and fulfill[6]["tick"] == tick
        assert inside(fulfill, fill) and fulfill[4] <= fill[4]
        # the rows fulfilled there are the plan's quiet and save-only rows:
        # every slot but those that rolled back
        plan = decode[6]
        assert plan["eager"] == 0
        assert fulfill[6]["rows"] == plan["fast"] - plan["resim"]
        resims += plan["resim"]
        assert decode[4] + build[4] <= whole[4] - crossing[4]
        # every tick here is fenced, so the device had always finished
        assert launch[6]["device_ready"] == 1 and launch[6]["dispatches"] == 1
        assert fence[6].get("parent") is None and fence[6]["tick"] == tick
        assert not named(events, DELIVER) and not named(events, ASK)
    assert resims > 0  # some ticks rolled back: those rows were not quiet


def test_a_detecting_tick_splits_the_fetch_into_its_two_halves(tracer):
    pool = Served(detect=True)
    by_tick = traced_ticks(tracer, pool, 30, 40)
    asked = landed = 0
    for tick, events in by_tick.items():
        fetches = named(events, "device.checksum_fetch")
        delivers, asks = named(events, DELIVER), named(events, ASK)
        if not fetches:
            assert not delivers and not asks
            continue
        (fetch,) = fetches
        assert fetch[6]["parent"] == "hosted.tick"
        for child in delivers + asks:
            assert child[6]["parent"] == "device.checksum_fetch"
            assert child[6]["tick"] == tick and inside(child, fetch)
        assert sum(e[6]["rows"] for e in delivers) == fetch[6]["landed"]
        assert sum(e[6]["rows"] for e in asks) == fetch[6]["wanted"]
        assert len(asks) == (1 if fetch[6]["wanted"] else 0)
        assert sum(e[4] for e in delivers + asks) <= fetch[4]
        asked += len(asks)
        landed += len(delivers)
    # 40 ticks at an interval of 5: every fetch asked for landed a tick later
    assert asked >= 6 and landed >= 6
    assert pool.executor.checksum_lag_ticks_max == 1


@pytest.mark.parametrize("detect", [False, True], ids=["plain", "detecting"])
def test_a_sleeping_tracer_costs_nothing_and_changes_nothing(
        tracer, monkeypatch, detect):
    """Tracer asleep against awake, same seed: no event and no ``is_ready``
    of the carry asleep; the same decoder, the same fetches, bit-identical
    states and wire bytes."""
    legs = {}
    for on in (False, True):
        pool = Served(detect)
        pool.run(4)
        with monkeypatch.context() as patch:
            if not on:
                leaf = pool.executor._carry["ring"]["frames"]
                real = type(leaf).is_ready

                def not_of_the_carry(self):
                    # (a detecting pool polls its fetches, [B, 4]: it may)
                    assert self.shape != leaf.shape, "tracer off"
                    return real(self)

                patch.setattr(type(leaf), "is_ready", not_of_the_carry)
            tracer.switch(on)
            pool.run(60)
            tracer.switch(False)
        host = pool.host
        assert host.plan_ticks == host.crossings == pool.ticks == 64
        if on:
            assert named(tracer.events(), FULFILL)
            assert all("device_ready" in e[6]
                       for e in named(tracer.events(), LAUNCH))
        else:
            assert len(tracer) == 0
        legs[on] = (jax.device_get(pool.executor.live_states), pool.wire,
                    pool.executor._host_frames.copy(), host.fast_slot_ticks)
    for a, b in zip(jax.tree_util.tree_leaves(legs[False][0]),
                    jax.tree_util.tree_leaves(legs[True][0])):
        np.testing.assert_array_equal(a, b)
    assert legs[False][1] == legs[True][1] and len(legs[True][1]) > 60
    np.testing.assert_array_equal(legs[False][2], legs[True][2])
    assert legs[False][3] == legs[True][3]


def test_device_ready_is_asked_of_the_carry_only_where_the_tracer_records(
        tracer, monkeypatch):
    pool = Served(detect=False, matches=2)
    pool.run(4)
    asked = []
    leaf_type = type(pool.executor._carry["ring"]["frames"])
    real = leaf_type.is_ready
    monkeypatch.setattr(leaf_type, "is_ready",
                        lambda self: asked.append(self.shape) or real(self))
    pool.run(5)
    assert asked == []
    tracer.switch(True)
    pool.run(5)
    tracer.switch(False)
    # one small leaf a launch: the ring's frame tags, [B, R]
    assert asked == [(pool.sessions, WINDOW + 2)] * 5
    pool.run(5)
    assert len(asked) == 5


def compiles() -> int:
    return int(default_registry().value(COMPILES) or 0)


def test_the_process_counts_its_compiles_and_names_their_tick(tracer):
    salt = random.Random().randrange(1 << 30)  # a program no test has met
    first_met = jax.jit(lambda x: x * 3 + salt)
    x = np.arange(4, dtype=np.int32)
    before = compiles()
    first_met(x).block_until_ready()
    assert compiles() == before + 1
    first_met(x).block_until_ready()
    assert compiles() == before + 1
    assert len(tracer) == 0  # asleep: counted, no instant

    pool = Served(detect=True)  # warmup compiled its programs
    pool.run(8)
    tracer.switch(True)
    steady = compiles()
    pool.run(32)
    assert compiles() == steady
    assert not named(tracer.events(), "device.compile")
    # a program first met inside a tick tells the counter and the ring
    other = jax.jit(lambda x: x * 5 + salt)
    with tracer.root_span("hosted.tick", tick=40_000):
        with tracer.span("device.dispatch"):
            other(x).block_until_ready()
    tracer.switch(False)
    assert compiles() == steady + 1
    (instant,) = named(tracer.events(), "device.compile")
    assert instant[0] == "i" and instant[6]["secs"] > 0
    assert instant[6]["tick"] == 40_000
    assert instant[6]["parent"] == "device.dispatch"


# --- every span a metric names is a span DESIGN.md tables -------------------


@functools.lru_cache(maxsize=None)
def design_spans() -> frozenset:
    """The names in the first column of DESIGN.md §14's span table; a row
    such as ``bank.inbound/timers/...`` names one span a part."""
    text = (REPO / "docs" / "DESIGN.md").read_text()
    section = text[text.index("## 14. Tick tracing"):text.index("## 15. ")]
    names = set()
    for first in re.findall(r"^\| `([^`]+)` \|", section, flags=re.M):
        head, *rest = first.split("/")
        names.add(head)
        names.update(f"{head.rpartition('.')[0]}.{part}" for part in rest)
    return frozenset(names)


@pytest.mark.parametrize(
    "path", sorted(METRICS.glob("*.json")), ids=lambda p: p.stem)
def test_every_span_a_metric_file_names_has_a_row_in_the_design(path):
    args = json.loads(path.read_text()).get("args", {})
    spans = [*args.get("add", ()), *args.get("sub", ())]
    if "span" in args:
        spans.append(args["span"])
    missing = [s for s in spans if s not in design_spans()]
    assert not missing, f"{path.name} names {missing}: no row in DESIGN.md §14"
