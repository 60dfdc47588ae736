#!/usr/bin/env python
"""The span tree of the served tick, and a net for its stalls.

Drives ``HostedPool.tick`` — the native bank AND the device executor, as a
server's loop calls it — under the process's default tracer
(``ggrs_tpu.obs.trace.default_tracer()``, switched on for the run) and
prints where a tick's time goes, top-down: ``hosted.tick`` → ``pool.stage``,
``pool.tick`` (``pool.build_cmd``, ``bank.crossing`` and its native phases,
``pool.decode``, ``pool.supervise``), ``device.dispatch`` (``device.fill``,
``device.launch``), and ``device.fence`` beside it; p50 and worst per span,
each span's self time (its p50 less its children's).  Tracing does not
choose the path: what is timed is the descriptor-plane decode and the tick
program every pool serves.

    python scripts/profile_tick.py                   # 64 matches, 200 ticks
    python scripts/profile_tick.py --matches 24 --hz 60 --seconds 60 \
        --stall-ms 30 --stalls 3                     # the paced population;
                                                     # every span of each tick
                                                     # over 30 ms and of the
                                                     # tick before it
    python scripts/profile_tick.py --matches 24 --hz 60 --ab
                                                     # tracer on against off
    python scripts/profile_tick.py --matches 24 --hz 60 --profile 60
                                                     # 60 ticks under
                                                     # jax.profiler: ggrs.*
                                                     # spans beside jit_tick
    JAX_PLATFORMS=cpu python scripts/profile_tick.py --cell ecs-4p.wan-sat \
        --dump-hlo ecs.hlo                           # the program's text, no chip
    python scripts/profile_tick.py --cell ecs-4p.wan-sat --ticks 32 \
        --profile 16 --hlo ecs.hlo                   # device time by named scope
    python scripts/profile_tick.py --trace tick.perfetto.json
    python scripts/profile_tick.py --host-only --udp # bank alone, real UDP
    python scripts/profile_tick.py --decode          # §24 decode-plane A/B

The population is the benchmark's ``boxgame-2p`` (2-peer BoxGame matches,
input delay 2, window 8, link of 3 ticks) unless ``--host-only``.
(DESIGN.md §14; ``benchmark/run.py`` is the measuring command, this is the
microscope.)
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402


def build_pool(n_matches: int, tracer=None, fastpath=True, udp=False):
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.games import boxgame_config
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.parallel.host_bank import HostSessionPool
    from ggrs_tpu.sessions import SessionBuilder

    prev = os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
    if not fastpath:
        os.environ["GGRS_TPU_NO_FASTPATH"] = "1"
    try:
        pool = HostSessionPool(tracer=tracer)
        schedules = []
        if udp:
            # real loopback UDP, both sides pooled: every fd is drained
            # by the gen-2 one-crossing recv table (DESIGN.md §23a), so
            # the pool.drain split below is live
            from ggrs_tpu.net.sockets import UdpNonBlockingSocket

            net = _UdpNet()
            for m in range(n_matches):
                socks = [UdpNonBlockingSocket(0) for _ in (0, 1)]
                addrs = [
                    ("127.0.0.1", s.local_port()) for s in socks
                ]
                for me in (0, 1):
                    b = (
                        SessionBuilder(boxgame_config())
                        .with_clock(lambda: 0)
                        .with_rng(random.Random(3 + 5 * m + me))
                        .add_player(Local(), me)
                        .add_player(Remote(addrs[1 - me]), 1 - me)
                    )
                    pool.add_session(b, socks[me])
                    schedules.append(
                        lambda i, m=m, me=me:
                        ((i + 2 * m + me) // (2 + m % 3)) % 16
                    )
        else:
            net = InMemoryNetwork()
            for m in range(n_matches):
                names = (f"A{m}", f"B{m}")
                for me in (0, 1):
                    b = (
                        SessionBuilder(boxgame_config())
                        .with_clock(lambda: 0)
                        .with_rng(random.Random(3 + 5 * m + me))
                        .add_player(Local(), me)
                        .add_player(Remote(names[1 - me]), 1 - me)
                    )
                    pool.add_session(b, net.socket(names[me]))
                    schedules.append(
                        lambda i, m=m, me=me:
                        ((i + 2 * m + me) // (2 + m % 3)) % 16
                    )
        if not pool.native_active:
            raise SystemExit("native bank did not engage (no toolchain?)")
    finally:
        os.environ.pop("GGRS_TPU_NO_FASTPATH", None)
        if prev is not None:
            os.environ["GGRS_TPU_NO_FASTPATH"] = prev
    return pool, schedules, net


class _UdpNet:
    """Drop-in for InMemoryNetwork's ``tick()`` when the population runs
    over real loopback sockets (the kernel delivers; nothing to pump)."""

    def tick(self) -> None:
        pass


def drive(pool, schedules, net, ticks, base=0):
    """Host-only ticks: inputs through the batched ``stage_inputs`` crossing
    (descriptor plane, §21), saves fulfilled by hand."""
    n = len(pool)
    times = np.empty(ticks)
    for i in range(ticks):
        t0 = time.perf_counter()
        pool.stage_inputs([(h, h % 2, schedules[h](base + i)) for h in range(n)])
        for reqs in pool.advance_all():
            for r in reqs:
                if type(r).__name__ == "SaveGameState":
                    r.cell.save(r.frame, None, None)
        times[i] = (time.perf_counter() - t0) * 1e3
        net.tick()
    return times


# ---------------------------------------------------------------------------
# the served tick: a benchmark cell's population behind HostedPool
# ---------------------------------------------------------------------------

WARM_TICKS = 48  # sessions start, first saves and rollbacks land, caches warm


def build_cell(cell: str, matches, seed: int, ticks: int):
    """One of the benchmark's populations (``benchmark/cells``), built by the
    benchmark's own ``Pool`` so that what is profiled is what is measured."""
    from benchmark import run
    from ggrs_tpu.utils.device import place_compile_cache

    place_compile_cache()
    spec = run.load_cell(run.REPO, cell)
    config, traffic = spec["config"], spec["traffic"]
    matches = int(matches or spec["size"]["matches"])
    pool = run.Pool(config, traffic, matches, seed, int(spec["cell"]["chips"]))
    inputs = run.Inputs(traffic, seed, matches, int(config["players"]),
                        ticks + WARM_TICKS + 1, int(config["input_delay"]))
    if not pool.host.native_active:
        raise SystemExit(f"native bank did not engage: {pool.host.native_reason}")
    return pool, inputs


def serve(pool, inputs, ticks, hz=0.0, seconds=0.0, on_tick=None):
    """``ticks`` served ticks (or ``seconds`` of them), each fenced; with
    ``hz`` an open loop timed from the due time, as the paced cell is.
    Returns per tick the ms from its start (or due time) to its fence's end;
    ``on_tick(i, ms, late_ms)`` returning true stops the loop (``late_ms``:
    how long after its due time the tick started)."""
    out = []
    period = 1.0 / hz if hz else 0.0
    t0 = time.perf_counter() + 0.003
    n = int(round(seconds * hz)) if (hz and seconds) else ticks
    for i in range(n):
        due = t0 + i * period
        if hz:
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                if due - now > 0.0015:
                    time.sleep(due - now - 0.0015)
        else:
            due = now = time.perf_counter()
        pool.tick(inputs.row(pool.ticks))
        pool.fence()
        ms = 1e3 * (time.perf_counter() - due)
        out.append(ms)
        if on_tick is not None and on_tick(i, ms, 1e3 * (now - due)):
            break
    return np.asarray(out)


def print_tree(events, title):
    """The span tree: p50 and worst per span, and for a span with children
    its self time (p50 less the children's p50s)."""
    from ggrs_tpu.obs.trace import span_stats

    stats = span_stats(events)
    children = {}
    for name, st in stats.items():
        children.setdefault(st["parent"], []).append(name)
    full = max((stats[r]["p50_us"] for r in children.get(None, [])), default=1.0)
    print(title)
    print(f"{'span':<28}{'p50 us':>10}{'worst us':>11}{'self us':>10}  n")

    def walk(name, depth):
        st = stats[name]
        kids = sorted(children.get(name, []), key=lambda k: -stats[k]["p50_us"])
        own = st["p50_us"] - sum(stats[k]["p50_us"] for k in kids)
        own_s = f"{own:10.0f}" if kids else " " * 10
        label = "  " * depth + name
        print(f"{label:<28}{st['p50_us']:10.0f}{st['max_us']:11.0f}{own_s}"
              f"  {st['count']:<6}{bar(st['p50_us'], full)}")
        for k in kids:
            walk(k, depth + 1)

    for root in sorted(children.get(None, []), key=lambda k: -stats[k]["p50_us"]):
        walk(root, 0)


def print_tick(events, t_base):
    """Every span of one tick, in start order, on one clock (ms from
    ``t_base``), with the gap no span of the same depth covers before it."""
    depth = {}
    for e in sorted(events, key=lambda e: (e[3], -e[4])):
        args = dict(e[6] or {})
        parent = args.pop("parent", None)
        args.pop("tick", None)
        d = depth[e[1]] = depth.get(parent, -1) + 1
        label = "  " * d + e[1]
        print(f"    {label:<26} {(e[3] - t_base) / 1e6:10.3f} "
              f"+{e[4] / 1e6:9.3f} ms  {args if args else ''}")


class StallNet:
    """Keeps every span of each tick that took over ``limit_ms`` from its
    own start to its fence's end, and of the tick before it (the ring holds
    some 190 ticks; a stall is copied out at once).  The ticks that merely
    start late behind a stall are not stalls."""

    def __init__(self, tracer, pool, limit_ms, want):
        self.tracer, self.pool = tracer, pool
        self.limit_ms, self.want = limit_ms, want
        self.caught = []

    def __call__(self, i, ms, late_ms):
        if ms - late_ms <= self.limit_ms:
            return False
        from ggrs_tpu.obs.trace import spans_by_tick

        tick = self.pool.host._tick_no
        recent = spans_by_tick(self.tracer.events(last=128))
        self.caught.append((i, ms, late_ms, tick, recent.get(tick - 1, []),
                            recent.get(tick, [])))
        return len(self.caught) >= self.want

    def report(self):
        print(f"\n# ticks over {self.limit_ms:g} ms: {len(self.caught)}")
        for i, ms, late_ms, tick, before, stalled in self.caught:
            print(f"\n  loop tick {i} (pool tick {tick}): {ms:.3f} ms from its "
                  f"due time to its fence's end; it started {late_ms:.3f} ms "
                  f"after it was due")
            if not stalled:
                print("    (its spans had left the ring)")
                continue
            base = min(e[3] for e in before + stalled)
            print(f"   the tick before it (pool tick {tick - 1}):")
            print_tick(before, base)
            print(f"   the stalled tick (pool tick {tick}):")
            print_tick(stalled, base)
            # the deepest span that holds the stall: the shortest of those
            # over the limit; its children say whether it fell between them
            over = [e for e in stalled if e[4] > 1e6 * self.limit_ms]
            if not over:
                print("   no span of the tick is over the limit: the stall "
                      "fell between two root spans")
                continue
            held = min(over, key=lambda e: e[4])
            kids = sum(e[4] for e in stalled
                       if (e[6] or {}).get("parent") == held[1])
            print(f"   held in {held[1]}: {held[4] / 1e6:.3f} ms, of which its "
                  f"child spans cover {kids / 1e6:.3f} ms")


def tracer_ab(pool, inputs, tracer, hz, segment_ticks, segments):
    """Tracer switched on for whole segments against off, interleaved off,
    on, on, off, ... on one population: what the tracer costs when on."""
    legs = {False: [], True: []}
    for k in range(segments):
        on = (k % 4) in (1, 2)
        tracer.switch(on)
        legs[on].append(serve(pool, inputs, segment_ticks, hz=hz))
    tracer.switch(False)
    print(f"\n# tracer on against off: {segments} segments of {segment_ticks} "
          f"ticks, {'%g Hz' % hz if hz else 'back to back'}, interleaved")
    p50 = {}
    for on in (False, True):
        xs = np.concatenate(legs[on])
        p50[on] = float(np.percentile(xs, 50))
        per = ", ".join(f"{np.percentile(x, 50):.3f}" for x in legs[on])
        print(f"  tracer {'on ' if on else 'off'}: tick p50 {p50[on]:.4f} ms  "
              f"p95 {np.percentile(xs, 95):.3f} ms  (segments: {per})")
    print(f"  on - off: {1e3 * (p50[True] - p50[False]):+.1f} us a tick "
          f"({100 * (p50[True] / p50[False] - 1):+.2f}%)")


# ---------------------------------------------------------------------------
# one profile: the program's spans beside the device's operations
# ---------------------------------------------------------------------------

# ``write_slot``: the kernels that save a re-laid ring leaf in place AND take
# the lane sums of what they write (``ops/ring.py``); ``digest``: what XLA
# still digests (the leaves the select writes, the salt, the finalizer; in a
# pool with no leaf in place, ``jax.vmap(checksum_device)`` whole)
SCOPES = ("ring.pre_save", "ring.load", "ring.save", "advance", "digest",
          "write_slot")
_STRUCTURE = ("while", "body", "cond")


def scope_of(op_name: str) -> str:
    """``jit(tick)/while/body/ring.save/digest/vmap()/mul`` -> ``ring.save >
    digest``, ``jit(tick)/ring.pre_save/cond/branch_1_fun/write_slot/
    ring_write_slot/pallas_call`` -> ``ring.pre_save > write_slot``: the
    named scopes of the pool's ``tick`` on an operation's path,
    and under ``advance`` the game's own outermost scope, whatever its name
    (``.../advance/vmap(spawn)/select_n`` -> ``advance > spawn``): a part,
    bare or under the ``vmap`` the game's step runs in, that is neither the
    operation at the path's end nor a loop's."""
    found = []
    parts = op_name.split("/")
    for i, raw in enumerate(parts):
        part = raw[raw.find("(") + 1:].rstrip(")")
        if part in SCOPES:
            if part not in found:
                found.append(part)
        elif (found and found[-1] == "advance" and part
              and raw in (part, f"vmap({part})")
              and i < len(parts) - 1 and raw not in _STRUCTURE
              and not raw.startswith("branch")):
            found.append(part)
    return " > ".join(found) if found else "-"


def hlo_scopes(hlo_text: str):
    """Instruction name -> scope, from the compiled program's text.  An
    instruction's own ``metadata={op_name="..."}`` decides; one that has
    none (a fusion, an operation the compiler sank into a loop) takes the
    scope of the instruction whose body, condition or callee encloses it."""
    import re

    head = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
    opened = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
    op_name = re.compile(r"op_name=\"([^\"]*)\"")
    refs = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
    own, home, user = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = opened.match(line)
        if m:
            computation = m.group(1)
            continue
        m = head.match(line)
        if not m:
            continue
        name = m.group(1)
        home[name] = computation
        found = op_name.search(line)
        own[name] = scope_of(found.group(1)) if found else "-"
        for ref in refs.findall(line):
            user.setdefault(ref, name)

    def resolve(name, depth=0):
        scope = own.get(name, "-")
        if scope != "-" or depth > 16:
            return scope
        outer = user.get(home.get(name))
        return resolve(outer, depth + 1) if outer else "-"

    return {name: resolve(name) for name in own}


def entry_copies(hlo_text: str):
    """``copy`` instructions of the compiled program's ENTRY computation,
    counted by result shape (``s32[512,10,4,10000]`` -> 2): the layout
    conversions that stand at the program's boundary, outside every loop.
    A ring-sized one is a transposition of a whole ring leaf, paid every
    tick (DESIGN.md §3, "The ring's layout at the program's boundary")."""
    import re

    copy = re.compile(
        r"^\s+(?:ROOT\s+)?%[\w.\-]+ = (\w+\[[\d,]*\])\S* copy\(")
    found, inside = {}, False
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            inside = line.startswith("ENTRY")
            continue
        m = copy.match(line) if inside else None
        if m:
            found[m.group(1)] = found.get(m.group(1), 0) + 1
    return found


def dump_hlo(cell, matches, path):
    """The cell's tick program compiled for a v5e that is described, not
    attached: its text carries the ``jax.named_scope`` names as ``op_name``
    metadata, under the operation names a profile on the chip shows.  Built
    from shapes by the executor's own ``tick_program``, so the carry comes in
    and goes out in the layouts a pool of this size holds it in and nothing
    of the cell's size is allocated here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib

    import jax
    from jax.experimental import topologies
    from jax.sharding import (
        Mesh,
        NamedSharding,
        PartitionSpec,
        SingleDeviceSharding,
    )

    from benchmark import run
    from ggrs_tpu.parallel.batch import SESSION_AXIS
    from ggrs_tpu.parallel.session_pool import blank_desc, tick_program

    spec = run.load_cell(run.REPO, cell)
    config = spec["config"]
    adapter = importlib.import_module(f"benchmark.adapters.{config['adapter']}")
    sessions = int(matches or spec["size"]["matches"]) * int(config["players"])
    game = adapter.make_game(config)
    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    n = int(spec["cell"]["chips"])
    if n > 1:
        # the cell's mesh, of described chips: each shard's program, under
        # the operation names a profile of the real mesh shows
        mesh = Mesh(np.asarray(chips[:n]), (SESSION_AXIS,))
        where = {"mesh": mesh}
        placed = NamedSharding(mesh, PartitionSpec((SESSION_AXIS,)))
    else:
        where = {"device": chips[0]}
        placed = SingleDeviceSharding(chips[0])
    program = tick_program(game.advance, game.init_state(), sessions,
                           int(config["ring_length"]), **where)
    example = adapter.example_inputs(config)
    desc = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed),
        blank_desc(sessions, int(config["max_burst"]),
                   example.shape, example.dtype))
    compiled = program.tick.lower(program.carry, desc).compile()
    text = compiled.as_text()
    Path(path).write_text(text)
    found = hlo_scopes(text)
    print(f"{path}: {len(found)} operations, "
          f"{sum(v != '-' for v in found.values())} of them in a named scope")
    relaid = jax.tree_util.tree_flatten_with_path(program.formats)[0]
    print(f"  held row-major between ticks: {len(relaid)} ring leaves")
    for k, held in relaid:
        print(f"    {jax.tree_util.keystr(k)}: tile {held.layout.tiling[0]}")
    print("  copies in the ENTRY computation, by shape (a ring-sized one is a "
          "whole-ring transposition a tick):")

    def elements(shape):  # "s32[512,10,4,10000]" -> 204,800,000
        dims = shape[shape.index("[") + 1:-1]
        return int(np.prod([int(d) for d in dims.split(",") if d]))

    for shape, n in sorted(entry_copies(text).items(),
                           key=lambda kv: (-elements(kv[0]), kv[0])):
        print(f"    {n} x {shape}")
    memory = compiled.memory_analysis()
    print(f"  the compiler's reckoning: arguments "
          f"{memory.argument_size_in_bytes:,} bytes, aliased "
          f"{memory.alias_size_in_bytes:,}, temporaries "
          f"{memory.temp_size_in_bytes:,}")


def own_seconds(op_events):
    """Per operation name its own time in seconds: each event's duration
    less the events nested in it (a while loop's interval holds its body's
    operations on the same line).  ``op_events``: (start_ns, -duration_ns,
    name)."""
    own, stack = {}, []  # stack of [end, name, duration, nested]

    def close():
        _end, name, dur, nested = stack.pop()
        own[name] = own.get(name, 0.0) + (dur - nested) / 1e9

    for start, neg_dur, name in sorted(op_events):
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][3] += -neg_dur
        stack.append([start - neg_dur, name, -neg_dur, 0])
    while stack:
        close()
    return own


def profile_ticks(pool, inputs, ticks, hz, keep_dir=None, ops_json=None,
                  hlo=None):
    """``ticks`` served ticks under ``jax.profiler``: the ``ggrs.*`` spans on
    the host plane against ``jit_tick`` on the device plane, who owns the
    time between them, and the device's time by operation and scope."""
    import jax
    from jax.profiler import ProfileData

    trace_dir = Path(keep_dir or tempfile.mkdtemp(prefix="ggrs_profile_"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        serve(pool, inputs, ticks, hz=hz)
    finally:
        jax.profiler.stop_trace()
    xplane = max(trace_dir.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime)
    print(f"\n# one profile: {ticks} ticks, {xplane.stat().st_size / 1e6:.1f} MB "
          f"({xplane})")
    host, modules, ops, op_events = read_profile(
        ProfileData.from_file(str(xplane)).planes)
    print_profile(host, modules, ops, op_events,
                  hlo_scopes(Path(hlo).read_text()) if hlo else {}, ops_json)


def read_profile(planes):
    """``ggrs.*`` spans of the host planes by name and tick, and per device
    plane (a mesh has one a chip) its ``jit_tick`` programs, its operations'
    seconds by name and the operation events themselves."""
    from ggrs_tpu.obs.trace import ANNOTATION_PREFIX

    host, modules, ops, op_events = {}, {}, {}, {}
    for plane in planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                modules.setdefault(plane.name, []).extend(
                    (ev.start_ns, ev.duration_ns) for ev in line.events
                    if ev.name.startswith("jit_tick"))
            elif device and line.name == "XLA Ops":
                seconds = ops.setdefault(plane.name, {})
                events = op_events.setdefault(plane.name, [])
                for ev in line.events:
                    name = ev.name.partition(" = ")[0].lstrip("%")
                    seconds[name] = seconds.get(name, 0.0) + ev.duration_ns / 1e9
                    events.append((ev.start_ns, -ev.duration_ns, name))
            elif not device:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        stats = dict(ev.stats)
                        host.setdefault(ev.name[len(ANNOTATION_PREFIX):], {})[
                            stats.get("tick")] = (ev.start_ns, ev.duration_ns, stats)
    return host, modules, ops, op_events


def print_profile(host, modules, ops, op_events, scopes, ops_json=None):
    """What ``read_profile`` found, a column a device: launch, program and
    fence on one clock, the device's seconds by operation and by the scope
    ``scopes`` (``hlo_scopes`` of the program's text) gives each."""
    from ggrs_tpu.obs.trace import profile_clock_offset_ns

    print("  ggrs.* events on the host plane: "
          + ", ".join(f"{k} {len(v)}" for k, v in sorted(host.items())))
    anchors = [(s["perf_ns"], start) for start, _d, s
               in host.get("hosted.tick", {}).values() if "perf_ns" in s]
    if anchors:
        offsets = [start - perf for perf, start in anchors]
        print(f"  clock anchor (profile_ns - perf_ns): {profile_clock_offset_ns(anchors)}"
              f" ns, spread over {len(anchors)} ticks {max(offsets) - min(offsets)} ns")
    if not modules:
        print("  no device plane (CPU backend): nothing to set the spans against")
        return
    planes = sorted(modules, key=lambda name: int(name.rpartition(":")[2]))
    short = [name.rpartition("/")[2] for name in planes]  # device:TPU:n
    launches = sorted(host.get("device.launch", {}).items())
    fences = host.get("device.fence", {})
    names = ("launch start -> program start", "launch end -> program start",
             "program (jit_tick)", "program end -> fence end",
             "program end -> fence start", "fence span")
    columns = []
    for plane in planes:
        rows = []
        for (tick, (l_start, l_dur, _)), (m_start, m_dur) in zip(
                launches, sorted(modules[plane])):
            fence = fences.get(tick)
            if fence is None:
                continue
            f_start, f_dur, _ = fence
            rows.append((m_start - l_start, m_start - (l_start + l_dur), m_dur,
                         f_start + f_dur - (m_start + m_dur),
                         f_start - (m_start + m_dur), f_dur))
        columns.append(np.asarray(rows, float) / 1e3 if rows else None)
    if any(a is not None for a in columns):
        print(f"  {len(launches)} launches, "
              f"{sum(len(m) for m in modules.values())} jit_tick programs on "
              f"{len(planes)} device(s); us, p50 (p5 .. p95), a column a device: "
              + ", ".join(short))
        for k, name in enumerate(names):
            cells = []
            for a in columns:
                if a is None:
                    cells.append("-")
                    continue
                p5, p50, p95 = np.percentile(a[:, k], [5, 50, 95])
                cells.append(f"{p50:10.1f}  ({p5:.1f} .. {p95:.1f})")
            print(f"    {name:<32}" + "  ".join(cells))
    # the text of an executable loaded on the chip carries no op_name
    # metadata; the same program compiled for the described chip does, under
    # the same operation names (--dump-hlo, which needs no chip)
    if not scopes:
        print("  (no scopes: make the program's text with --dump-hlo OUT under "
              "JAX_PLATFORMS=cpu and pass --hlo OUT)")
    every = sorted({name for plane in planes for name in ops[plane]},
                   key=lambda n: -sum(ops[p].get(n, 0.0) for p in planes))
    print(f"  device time by operation ({len(every)} names), with the scope the "
          f"compiled program gives each; seconds, a column a device:")
    for name in every[:16]:
        print(f"    %{name:<34}{scopes.get(name, '?'):<26}"
              + "".join(f"{ops[p].get(name, 0.0):10.4f}" for p in planes))
    own = {plane: own_seconds(op_events[plane]) for plane in planes}
    if ops_json:
        import json

        Path(ops_json).write_text(json.dumps(
            {key: {name: {"total_s": ops[plane][name],
                          "own_s": own[plane].get(name, 0.0),
                          "scope": scopes.get(name, "?")} for name in ops[plane]}
             for key, plane in zip(short, planes)}, indent=0))
    by_scope = {}
    for k, plane in enumerate(planes):
        for name, sec in own[plane].items():
            row = by_scope.setdefault(scopes.get(name, "?"), [0.0] * len(planes))
            row[k] += sec
    totals = [sum(row[k] for row in by_scope.values()) or 1.0
              for k in range(len(planes))]
    print("  device time by scope, each operation's own time; seconds and share "
          "of the device's busy time, a column a device (busy: "
          + ", ".join(f"{t:.4f} s" for t in totals) + "):")
    for key, row in sorted(by_scope.items(), key=lambda kv: -sum(kv[1])):
        print(f"    {key:<40}" + "".join(
            f"{sec:10.4f} s {100 * sec / t:5.1f}%" for sec, t in zip(row, totals)))


def bar(us, full_us, width=24):
    n = 0 if full_us <= 0 else int(round(width * us / full_us))
    return "█" * max(0, min(width, n))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", default="boxgame-2p.wan-60hz",
                    help="the benchmark cell whose population is served "
                         "(benchmark/cells; default the paced cell's)")
    ap.add_argument("--matches", type=int, default=None, metavar="B",
                    help="matches (default: the cell's own; 64 with "
                         "--host-only)")
    ap.add_argument("--ticks", type=int, default=200)
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--hz", type=float, default=0.0,
                    help="pace the loop (open loop timed from the due time); "
                         "0: ticks back to back, each fenced")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="with --hz: run this long instead of --ticks")
    ap.add_argument("--stall-ms", type=float, default=0.0, metavar="N",
                    help="dump every span of each tick over N ms and of the "
                         "tick before it")
    ap.add_argument("--stalls", type=int, default=3,
                    help="stop after this many stalls (default 3)")
    ap.add_argument("--ab", action="store_true",
                    help="tracer on against off, interleaved segments")
    ap.add_argument("--ab-segments", type=int, default=8)
    ap.add_argument("--profile", type=int, default=0, metavar="TICKS",
                    help="also take TICKS ticks under jax.profiler and set "
                         "the ggrs.* spans against the device's operations")
    ap.add_argument("--profile-dir", default=None,
                    help="keep the profile here (default: a temp directory)")
    ap.add_argument("--hlo", default=None, metavar="FILE",
                    help="with --profile: the tick program's compiled text "
                         "(from --dump-hlo), which names each operation's scope")
    ap.add_argument("--dump-hlo", default=None, metavar="OUT",
                    help="compile the cell's tick program for a described "
                         "v5e chip (none needed: JAX_PLATFORMS=cpu) and write "
                         "its text, then exit")
    ap.add_argument("--ops-json", default=None, metavar="OUT.json",
                    help="with --profile: every device operation's total "
                         "and own seconds and its scope")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="also write the ring's Perfetto export")
    ap.add_argument("--host-only", action="store_true",
                    help="the bank alone (no executor), 2-peer matches over "
                         "an in-memory network or --udp")
    ap.add_argument("--udp", action="store_true",
                    help="with --host-only: real loopback UDP, so the gen-2 "
                         "one-crossing inbound drain (§23a, pool.drain) engages")
    ap.add_argument("--decode", action="store_true",
                    help="append the §24 decode-plane A/B: serial vs "
                         "parallel slow-slot decode (untraced, fast path "
                         "off so every slot is slow), with per-worker "
                         "utilization and GRO segments-per-datagram")
    ap.add_argument("--decode-backend", default="thread",
                    metavar="B", help="parallel leg backend for --decode "
                                      "(default thread)")
    args = ap.parse_args()

    from ggrs_tpu.obs import default_tracer

    tracer = default_tracer()
    if args.dump_hlo:
        dump_hlo(args.cell, args.matches, args.dump_hlo)
        return 0
    if args.host_only or args.udp or args.decode:
        args.matches = args.matches or 64
        host_only(args, tracer)
    else:
        served(args, tracer)
    if args.decode:
        # §24: the parallel slow-slot decode plane.  Untraced (a traced
        # pool keeps the interleaved reference decoder) and fast path
        # OFF, so every slot routes through the slow decoder and the
        # plane fans out every tick.  The serial leg is the kill-switch
        # posture; the wall delta between the legs IS the plane's win
        # (or, on a GIL build, its honest non-win).
        print(f"\n# §24 decode plane A/B: B={args.matches} matches, "
              f"fast path off (every slot slow), untraced")
        legs = (
            ("serial", {"GGRS_TPU_NO_PARALLEL_DECODE": "1"}),
            (args.decode_backend,
             {"GGRS_TPU_DECODE_BACKEND": args.decode_backend}),
        )
        for label, env in legs:
            saved = {k: os.environ.pop(k, None)
                     for k in ("GGRS_TPU_NO_PARALLEL_DECODE",
                               "GGRS_TPU_DECODE_BACKEND")}
            os.environ.update(env)
            try:
                p, s, n2 = build_pool(args.matches, fastpath=False,
                                      udp=args.udp)
                drive(p, s, n2, 16)
                dec0 = p.io_stats()["decode"]
                ns0, jobs0 = dec0["decode_ns"], dec0["jobs"]
                xs = drive(p, s, n2, args.ticks, base=16)
            finally:
                for k, v in saved.items():
                    os.environ.pop(k, None)
                    if v is not None:
                        os.environ[k] = v
            dec = p.io_stats()["decode"]
            print(f"  {label:<8}: p50 {np.percentile(xs, 50):6.2f} ms  "
                  f"p99 {np.percentile(xs, 99):6.2f} ms  "
                  f"(backend {dec['backend']}, "
                  f"{dec['parallel_ticks']} fanned ticks)")
            if dec["parallel_ticks"]:
                jobs = dec["jobs"] - jobs0
                in_pool_us = (dec["decode_ns"] - ns0) / 1000.0 / args.ticks
                print(f"            slow slots/tick "
                      f"{jobs / args.ticks:.1f}, in-pool decode "
                      f"{in_pool_us:.0f} us/tick over "
                      f"{dec['workers']} workers")
                total = sum(dec["worker_jobs"].values()) or 1
                spread = ", ".join(
                    f"{100 * v / total:.0f}%"
                    for v in sorted(dec["worker_jobs"].values(),
                                    reverse=True)
                )
                print(f"            worker utilization (jobs): {spread}")
            dio = p.io_stats()["drain"]
            if dio.get("gro_datagrams"):
                print(f"            gro: {dio['gro_segments']} segments "
                      f"from {dio['gro_datagrams']} trains "
                      f"({dio['gro_segments'] / dio['gro_datagrams']:.1f} "
                      f"segs/datagram)")
            del p, s, n2
    return 0


def served(args, tracer) -> None:
    total = args.ticks
    if args.hz and args.seconds:
        total = int(round(args.hz * args.seconds))
    budget = total * (1 + (args.ab_segments if args.ab else 0)) + args.profile
    pool, inputs = build_cell(args.cell, args.matches, args.seed, budget + 8)
    serve(pool, inputs, WARM_TICKS)
    host = pool.host
    net = (StallNet(tracer, pool, args.stall_ms, args.stalls)
           if args.stall_ms else None)
    tracer.clear()
    tracer.switch(True)
    times = serve(pool, inputs, total, hz=args.hz, on_tick=net)
    tracer.switch(False)
    pace = f"{args.hz:g} Hz from the due time" if args.hz else "back to back"
    print(f"# served tick profile: {args.cell}, {pool.sessions} sessions, "
          f"{len(times)} ticks {pace}, each fenced; native bank: "
          f"{host.native_reason}")
    print(f"# plan_ticks {host.plan_ticks} == crossings {host.crossings} == "
          f"ticks {pool.ticks}: {host.plan_ticks == host.crossings == pool.ticks}"
          f"; ring {len(tracer)} events, dropped {tracer.dropped}")
    print(f"# tick (to its fence's end): p50 {np.percentile(times, 50):.3f} ms  "
          f"p99 {np.percentile(times, 99):.3f} ms  worst {times.max():.3f} ms\n")
    print_tree(tracer.events(), "# spans of the ring's ticks")
    if net is not None:
        net.report()
    if args.trace:
        print(f"\nPerfetto export: {tracer.write(args.trace)} "
              f"(load in chrome://tracing)")
    if args.ab:
        tracer_ab(pool, inputs, tracer, args.hz, total, args.ab_segments)
    if args.profile:
        profile_ticks(pool, inputs, args.profile, args.hz, args.profile_dir,
                      args.ops_json, args.hlo)


def host_only(args, tracer) -> None:
    """The bank alone: ``stage_inputs`` + ``advance_all`` with saves
    fulfilled by hand; over real UDP the ``pool.drain`` span is live."""
    pool, schedules, net = build_pool(args.matches, udp=args.udp)
    drive(pool, schedules, net, 16)  # warm
    tracer.clear()
    tracer.switch(True)
    d0_cross = pool.drain_crossings
    times = drive(pool, schedules, net, args.ticks, base=16)
    tracer.switch(False)
    print(f"# host-bank tick profile: B={args.matches} matches "
          f"({2 * args.matches} sessions), {args.ticks} ticks; plan_ticks "
          f"{pool.plan_ticks} == crossings {pool.crossings}")
    print(f"# wall: p50 {np.percentile(times, 50):.2f} ms  "
          f"p99 {np.percentile(times, 99):.2f} ms per tick\n")
    print_tree(tracer.events(), "# spans of the ring's ticks")
    if pool.drain_crossings - d0_cross:
        dio = pool.io_stats()["drain"]
        print(f"  (batched inbound: {(pool.drain_crossings - d0_cross) / args.ticks:.1f}"
              f" drains/tick, {dio['datagrams']} datagrams over "
              f"{dio['recv_calls']} recvmmsg calls, "
              f"{dio['backpressure_stops']} backpressure stops)")
    if args.trace:
        print(f"\nPerfetto export: {tracer.write(args.trace)}")


if __name__ == "__main__":
    raise SystemExit(main())
