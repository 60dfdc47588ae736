"""Low-overhead span tracer with Chrome/Perfetto trace-event export
(DESIGN.md §14).

The metrics registry (§12) answers *how much* the pool does per tick; this
module answers *where the time goes* inside one tick.  A :class:`Tracer`
keeps a bounded ring of completed spans — tick → crossing → slot nesting on
the Python side, plus the native bank's per-phase timings re-emitted as
child spans of the crossing — and exports the window in the Chrome
trace-event JSON format, so one ``tracer.write(path)`` produces a file that
loads directly in ``chrome://tracing`` or https://ui.perfetto.dev.

One tracer serves the hosted path: :func:`default_tracer` is what
``HostSessionPool`` and ``BatchedRequestExecutor`` use when handed none.  It
records while an operator switched it on (:meth:`Tracer.switch`) **or**
while a ``jax.profiler`` trace is active, and while a profile is being
taken every span is also a ``jax.profiler.TraceAnnotation`` named
``ggrs.<span>`` with its args as metadata, so it lies in the profile beside
the device's operations by construction.  A root span's annotation carries
``perf_ns`` (its own ``perf_counter_ns`` start): the anchor between this
module's clock and the profiler's (:func:`profile_clock_offset_ns`).

Every span records its parent's name and the pool tick number in ``args``
(``parent``, ``tick``), so the seven-field ring event the fleet ships
between processes (``import_spans``) keeps its shape and a reader can build
the tree per tick: a layer's self time is its span minus what its children
cover.  Tracing never chooses the path: a traced pool runs the same decoder
and the same device program as an untraced one.

Design constraints, shared with the rest of ``ggrs_tpu.obs``:

- **Compiles out.**  ``Tracer(enabled=False)`` hands back a shared no-op
  context manager from ``span()`` and drops every ``add_*`` immediately —
  no clock reads, no allocation, nothing on the ring.  The chaos suite
  pins wire bytes bit-identical with tracing on vs off
  (tests/test_trace.py), and the bank's crossing count is pinned
  unchanged: the native timing tail rides the EXISTING tick output, so
  tracing adds zero extra ctypes crossings.
- **Monotonic clocks only.**  Spans are stamped with
  ``time.perf_counter_ns`` (never the session clock, never wall time), so
  tracing cannot perturb timer-driven protocol behavior.
- **Bounded.**  The ring drops the oldest span; ``dropped`` counts what
  fell off.  A flight-recorder-sized window (default 4096 spans) is the
  point: the *recent* tick structure, attached to desync reports and the
  ``/trace`` endpoint, not an unbounded profile.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import (
    Any, Deque, Dict, Iterable, List, Optional, Sequence, Tuple,
)

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NULL_TRACER", "default_tracer", "chrome_trace_events",
           "validate_chrome_trace", "profile_clock_offset_ns",
           "spans_by_tick", "span_stats", "ANNOTATION_PREFIX"]

# a span named ``pool.tick`` is the profile event ``ggrs.pool.tick``
ANNOTATION_PREFIX = "ggrs."

# event phases on the ring (Chrome trace-event "ph" values)
_PH_COMPLETE = "X"
_PH_INSTANT = "i"


class _NullSpan:
    """Shared no-op context manager: the whole cost of a disabled span is
    one attribute load and one method call returning this singleton."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager recording one complete ("X") event on exit: name,
    start, duration, and in ``args`` the enclosing span's name (``parent``)
    and the pool tick it belongs to (``tick``, inherited from the parent
    when not given).  While a profile is being taken it is a
    ``TraceAnnotation`` too, entered after the clock read and left before
    it, so the ring's span contains the profile's."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_stack",
                 "_ann", "_late", "_anchor")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any], anchor: bool = False) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._anchor = anchor
        self._ann = None
        self._late: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        args = self._args
        stack = self._stack = tracer._thread_stack()
        if stack:
            parent, tick = stack[-1]
            args["parent"] = parent
            if tick is not None:
                args.setdefault("tick", tick)
        stack.append((self._name, args.get("tick")))
        t0 = self._t0 = time.perf_counter_ns()
        if tracer._annotate:
            meta = dict(args, perf_ns=t0) if self._anchor else args
            ann = self._ann = TraceAnnotation(
                ANNOTATION_PREFIX + self._name, **meta)
            ann.__enter__()
        return self

    def set(self, **args) -> None:
        """Counts known only at the span's end (bytes built, rows decoded):
        they join ``args``, and the annotation's metadata."""
        self._args.update(args)
        if self._ann is not None:
            self._late = {**(self._late or {}), **args}

    def __exit__(self, *exc) -> bool:
        ann = self._ann
        if ann is not None:
            if self._late:
                ann.set_metadata(**self._late)
            ann.__exit__(None, None, None)
        t1 = time.perf_counter_ns()
        self._stack.pop()
        t0 = self._t0
        self._tracer._append(
            _PH_COMPLETE, self._name, self._cat, t0, t1 - t0,
            self._args or None,
        )
        return False


class Tracer:
    """Bounded ring of trace spans with Chrome trace-event export.

    Usage::

        tracer = Tracer()                      # or Tracer(enabled=False)
        with tracer.span("pool.tick", cat="py", tick=7):
            with tracer.span("bank.crossing", cat="native"):
                ...
        tracer.write("pool.trace.json")        # chrome://tracing loads this

    Spans nest naturally through ``with`` nesting (Chrome infers the tree
    from containment on one thread's timeline).  ``add_complete`` records a
    span from explicit timestamps — how the native bank's per-phase
    timings, measured inside the tick crossing, are re-emitted as child
    spans of the crossing without any Python-side context manager.
    """

    def __init__(self, capacity: int = 4096, enabled: bool = True) -> None:
        # `enabled` is what every call site reads (one attribute load); it
        # only changes in switch() and, for a tracer that follows the
        # profiler, in refresh() at a root span
        self.enabled = enabled
        self._switched_on = enabled
        self._follow_profiler = False  # the default tracer's alone
        self._annotate = False  # a jax.profiler trace was active at refresh
        self.capacity = capacity
        # (ph, name, cat, start_ns, dur_ns, tid, args)
        self._ring: Deque[Tuple] = deque(maxlen=capacity)
        self.recorded = 0  # total ever recorded (ring drops the oldest)
        self._cleared = 0  # of those, how many clear() threw away
        self._local = threading.local()  # per-thread stack of open spans

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def switch(self, on: bool) -> None:
        """The operator's switch: record from now on (or stop, unless a
        profile is being taken and this tracer follows the profiler)."""
        self._switched_on = bool(on)
        self.refresh()

    def refresh(self) -> bool:
        """Re-read whether a ``jax.profiler`` trace is active (about 20 ns):
        spans become annotations while one is, and a tracer that follows
        the profiler records for as long.  Called by ``root_span`` where no
        span is open, so once or twice a pool tick; pools read ``enabled``
        and arm or disarm the bank's phase timers when it changed."""
        profiling = TraceAnnotation.is_enabled()
        on = self._switched_on or (self._follow_profiler and profiling)
        self._annotate = profiling and on
        self.enabled = on
        return on

    def _thread_stack(self) -> List[Tuple[str, Optional[int]]]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------

    def span(self, name: str, cat: str = "py", **args):
        """Context manager timing one span; no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def root_span(self, name: str, cat: str = "py", **args):
        """A span that may be a tick's outermost (``hosted.tick``,
        ``pool.tick``, ``device.fence``): where no span is open on this
        thread it first re-reads the profiler's state (:meth:`refresh`),
        and its annotation carries ``perf_ns``, the clock anchor."""
        if self.enabled and self._thread_stack():
            return _Span(self, name, cat, args)
        if not self.refresh():
            return _NULL_SPAN
        return _Span(self, name, cat, args, anchor=True)

    def add_complete(self, name: str, start_ns: int, dur_ns: int,
                     cat: str = "native",
                     args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete span from explicit monotonic-ns timestamps
        (the native timing tail's re-emission path)."""
        if self.enabled:
            self._append(_PH_COMPLETE, name, cat, start_ns, dur_ns, args)

    def add_sequence(self, spans: Sequence[Tuple[str, int]], start_ns: int,
                     cat: str = "native",
                     args: Optional[Dict[str, Any]] = None) -> None:
        """Record ``(name, dur_ns)`` spans laid end to end from
        ``start_ns``, all with the same ``args`` (one shared dict: never
        mutated here).  A zero duration takes no event.  How durations
        that were accumulated, not observed as intervals, are shown — the
        native bank's per-phase timers."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        ring = self._ring
        off = start_ns
        n = 0
        for name, dur in spans:
            if dur:
                ring.append((_PH_COMPLETE, name, cat, off, dur, tid, args))
                n += 1
            off += dur
        self.recorded += n

    def add_instant(self, name: str, cat: str = "py", **args) -> None:
        """Record an instant event (faults, desyncs, evictions)."""
        if self.enabled:
            self._append(_PH_INSTANT, name, cat, time.perf_counter_ns(), 0,
                         args or None)

    def now_ns(self) -> int:
        """The tracer's clock (monotonic ns) — for callers timing a region
        by hand around a ctypes call."""
        return time.perf_counter_ns()

    def import_spans(self, events: List[Tuple], *, offset_ns: int = 0,
                     extra_args: Optional[Dict[str, Any]] = None) -> int:
        """Re-emit raw ring events shipped from ANOTHER process
        (DESIGN.md §18): each event's start time is shifted by
        ``offset_ns`` (the RTT-estimated clock offset between the two
        processes' ``perf_counter`` clocks) and recorded on THIS thread's
        track, so a runner's spans nest inside the supervisor span that
        covers the RPC which carried them.  ``extra_args`` (e.g.
        ``{"shard": "s1"}``) is folded into every event's args; the
        source thread id is preserved as ``src_tid``.  Returns the number
        of events imported; malformed entries are skipped, never raised.
        """
        if not self.enabled or not events:
            return 0
        n = 0
        for ev in events:
            try:
                ph, name, cat, start_ns, dur_ns, src_tid, args = ev
                start_ns = int(start_ns) - offset_ns
                dur_ns = int(dur_ns)
            except Exception:
                continue
            a: Dict[str, Any] = dict(args) if args else {}
            if extra_args:
                a.update(extra_args)
            a.setdefault("src_tid", src_tid)
            self._append(ph, str(name), str(cat), start_ns, dur_ns, a)
            n += 1
        return n

    def _append(self, ph: str, name: str, cat: str, start_ns: int,
                dur_ns: int, args: Optional[Dict[str, Any]]) -> None:
        self._ring.append(
            (ph, name, cat, start_ns, dur_ns, threading.get_ident(), args)
        )
        self.recorded += 1

    # ------------------------------------------------------------------
    # reads / export
    # ------------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        return self.recorded - self._cleared - len(self._ring)

    def events(self, last: int = 0) -> List[Tuple]:
        """The retained raw events, oldest first; ``last`` > 0 keeps only
        the newest ``last``."""
        out = list(self._ring)
        if last > 0:
            out = out[-last:]
        return out

    def clear(self) -> None:
        """Empty the ring; what is cleared does not count as dropped."""
        self._cleared += len(self._ring) + self.dropped
        self._ring.clear()

    def chrome_trace(self, last: int = 0) -> Dict[str, Any]:
        """The current window as a Chrome trace-event JSON object
        (``{"traceEvents": [...]}``) — loads in ``chrome://tracing`` and
        Perfetto.  Timestamps are microseconds relative to the oldest
        retained event."""
        events = self.events(last)
        return {
            "traceEvents": chrome_trace_events(events),
            "displayTimeUnit": "ms",
        }

    def write(self, path) -> str:
        """Serialize :meth:`chrome_trace` to ``path``; returns the path."""
        path = os.fspath(path)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name totals over the window: count and total/max
        duration in microseconds — the quick textual digest chaos runs
        print alongside the full export."""
        return {
            name: {k: st[k] for k in ("count", "total_us", "max_us")}
            for name, st in span_stats(self._ring).items()
        }


def chrome_trace_events(events: List[Tuple]) -> List[Dict[str, Any]]:
    """Convert raw ring events to Chrome trace-event dicts.  The time base
    is shifted so the oldest event sits at ts=0 (chrome://tracing dislikes
    raw multi-hour perf_counter offsets)."""
    if not events:
        return []
    base = min(e[3] for e in events)
    out: List[Dict[str, Any]] = []
    for ph, name, cat, start_ns, dur_ns, tid, args in events:
        ev: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": ph,
            "ts": (start_ns - base) / 1000.0,
            "pid": 1,
            "tid": tid & 0xFFFF,
        }
        if ph == _PH_COMPLETE:
            ev["dur"] = dur_ns / 1000.0
        else:
            ev["s"] = "t"  # instant scope: thread
        if args:
            ev["args"] = dict(args)
        out.append(ev)
    return out


def validate_chrome_trace(trace: Any, eps_us: float = 0.001) -> List[str]:
    """Schema validation for a Chrome/Perfetto trace-event export: the
    checks a load into ui.perfetto.dev would fail on, run in CI instead
    (DESIGN.md §18).  Returns a list of problems (empty = valid):

    - the object is ``{"traceEvents": [...]}`` and JSON-serializable;
    - every event has a string ``name``, a known ``ph``, numeric
      finite ``ts >= 0``, and ``pid``/``tid``;
    - complete ("X") events carry ``dur >= 0``;
    - per (pid, tid) track, complete events properly nest: sorted by
      start time, any two spans are either disjoint or one contains the
      other — partial overlap on one track is how a bad clock offset or
      a torn import shows up.

    ``eps_us`` is the nesting slack in microseconds: keep the tight
    default for single-process traces (one clock, exact containment);
    fleet traces carrying imported cross-process spans should allow the
    residual clock-offset error (tens of µs).
    """
    problems: List[str] = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["not a {'traceEvents': [...]} object"]
    events = trace["traceEvents"]
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as e:
        problems.append(f"not JSON-serializable: {e}")
    known_ph = {"X", "i", "I", "B", "E", "M", "b", "e", "n", "s", "t", "f"}
    tracks: Dict[Tuple, List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        name = ev.get("name")
        if not isinstance(name, str) or not name:
            problems.append(f"event {i}: missing/empty name")
            name = "?"
        ph = ev.get("ph")
        if ph not in known_ph:
            problems.append(f"event {i} ({name}): unknown ph {ph!r}")
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts != ts or ts < 0 \
                or ts in (float("inf"), float("-inf")):
            problems.append(f"event {i} ({name}): bad ts {ts!r}")
            continue
        if "pid" not in ev or "tid" not in ev:
            problems.append(f"event {i} ({name}): missing pid/tid")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur != dur or dur < 0:
                problems.append(f"event {i} ({name}): bad dur {dur!r}")
                continue
            tracks.setdefault((ev["pid"], ev["tid"]), []).append(
                (float(ts), float(dur), name)
            )
    # nesting per track: the epsilon absorbs ns→µs rounding (default)
    # or residual cross-process offset error (caller-raised)
    eps = eps_us
    for track, spans in tracks.items():
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack: List[Tuple[float, str]] = []  # (end_ts, name)
        for ts, dur, name in spans:
            end = ts + dur
            while stack and ts >= stack[-1][0] - eps:
                stack.pop()
            if stack and end > stack[-1][0] + eps:
                problems.append(
                    f"track {track}: span {name!r} [{ts:.3f}, {end:.3f}] "
                    f"partially overlaps enclosing {stack[-1][1]!r} "
                    f"(ends {stack[-1][0]:.3f})"
                )
                continue
            stack.append((end, name))
    return problems


def spans_by_tick(events: List[Tuple]) -> Dict[int, List[Tuple]]:
    """Complete events grouped by the pool tick in their ``args`` (ring
    order kept); events that name no tick are left out."""
    ticks: Dict[int, List[Tuple]] = {}
    for ev in events:
        args = ev[6]
        if ev[0] == _PH_COMPLETE and args and args.get("tick") is not None:
            ticks.setdefault(args["tick"], []).append(ev)
    return ticks


def span_stats(events: Iterable[Tuple]) -> Dict[str, Dict[str, Any]]:
    """Per span name over ``events``: its parent's name, how many ticks'
    worth were seen, and the median, worst and total duration (µs) — what
    a span tree is printed from (``scripts/profile_tick.py``)."""
    durs: Dict[str, List[int]] = {}
    parents: Dict[str, Optional[str]] = {}
    for ph, name, _cat, _t0, dur, _tid, args in events:
        if ph != _PH_COMPLETE:
            continue
        durs.setdefault(name, []).append(dur)
        parents.setdefault(name, (args or {}).get("parent"))
    out: Dict[str, Dict[str, Any]] = {}
    for name, ds in durs.items():
        ds.sort()
        out[name] = {
            "parent": parents[name], "count": len(ds),
            "p50_us": ds[len(ds) // 2] / 1e3, "max_us": ds[-1] / 1e3,
            "total_us": sum(ds) / 1e3,
        }
    return out


def profile_clock_offset_ns(anchors: List[Tuple[int, int]]) -> Optional[int]:
    """The offset that carries a ``perf_counter_ns`` time onto the
    profiler's clock (``profile_ns = perf_ns + offset``), from the root
    spans' annotations: pairs of (the annotation's ``perf_ns`` metadata, its
    start on the profile's clock).  Every pair is the same constant plus the
    microsecond between the clock read and the annotation's start; the
    smallest is the tightest.  Ring events that have no annotation (the
    native bank's phases) are placed in a profile with it."""
    if not anchors:
        return None
    return min(int(start) - int(perf) for perf, start in anchors)


# The hard no-op: for those who pass it explicitly.  It never wakes.
NULL_TRACER = Tracer(capacity=1, enabled=False)

# The process's tracer (as default_registry() is for counters): what the
# hosted path uses when handed none.  Off, a span is one attribute load and
# the shared no-op context manager; it wakes while switched on or while a
# jax.profiler trace is active.
_DEFAULT_TRACER = Tracer(capacity=4096, enabled=False)
_DEFAULT_TRACER._follow_profiler = True


def default_tracer() -> Tracer:
    """The process-wide tracer of the served path."""
    return _DEFAULT_TRACER
