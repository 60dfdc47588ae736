"""From a profiler trace (``.xplane.pb``) to the device's numbers: program
time per dispatch, busy and idle time, the device operations that took most
time, and the idle gaps named by what the host was doing in them.

Two steps, so the arithmetic can be checked on a hand-sized trace:
``load_lines`` reads the file into plain lists, ``reduce_lines`` does the
arithmetic on them.  Busy is the union of the intervals in which an
operation ran on the device ("XLA Ops" line of a ``/device:TPU:n`` plane);
the window runs from the first benchmark span's start, or the first
operation's if that is earlier, to the last operation's end: the lead-in in
which the host prepares the slice's first tick on a drained device is idle
time of the loop (a closed loop pays it after every fence), named like any
other gap.  A trace without a device plane (the CPU backend) reduces to
nothing.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 10_000  # gaps under 10 us lie between back-to-back operations
SHORT_GAPS = "between_device_ops.gaps_under_10us"
NO_SPAN = "no_bench_span"
TOP = 10

Line = Dict[str, Any]  # {"plane", "line", "names", "start_ns", "dur_ns"}


def newest_xplane(trace_dir: Path) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load_lines(xplane: Path, span_prefix: str) -> List[Line]:
    """The device planes' operation and module lines, and from the host
    planes only the events whose name starts with ``span_prefix``."""
    from jax.profiler import ProfileData

    out: List[Line] = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            names, starts, durs = [], [], []
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(span_prefix):
                    continue
                names.append(name)
                starts.append(ev.start_ns)
                durs.append(ev.duration_ns)
            if names:
                out.append({"plane": plane.name, "line": line.name,
                            "names": names, "start_ns": starts,
                            "dur_ns": durs})
    return out


def short_op_name(name: str) -> str:
    """``%while.33 = (s32[], ...) while(...)`` -> ``%while.33 (tuple)``: the
    trace names an operation by its whole HLO line, operands and all."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return head[:80]
    shape = "(tuple)" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    return f"{head} {shape}"[:80]


def _union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged busy segments of possibly nested or overlapping intervals."""
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    seg_end = np.append(e[idx[1:] - 1], e[-1])
    return s[idx], seg_end


def _name_gaps(gap_s: np.ndarray, gap_e: np.ndarray,
               spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Each idle gap's nanoseconds go to the host spans that overlap it."""
    named: Dict[str, float] = {}
    short = (gap_e - gap_s) < SHORT_GAP_NS
    if short.any():
        named[SHORT_GAPS] = float((gap_e - gap_s)[short].sum())
    spans = sorted(spans)
    span_s = np.asarray([s for s, _, _ in spans], float)
    for a, b in zip(gap_s[~short], gap_e[~short]):
        left = b - a
        # spans of one thread are disjoint and sorted: walk those that can
        # overlap [a, b)
        k = max(int(np.searchsorted(span_s, a, side="right")) - 1, 0)
        while k < len(spans) and spans[k][0] < b:
            s, e, name = spans[k]
            over = min(b, e) - max(a, s)
            if over > 0:
                named[name] = named.get(name, 0.0) + over
                left -= over
            k += 1
        if left > 0:
            named[NO_SPAN] = named.get(NO_SPAN, 0.0) + left
    return named


def reduce_lines(lines: List[Line], program: str, span_prefix: str) -> Dict[str, Any]:
    """``{"chips", "busy_s", "window_s", "program_ms", "device_ops",
    "idle_gaps"}`` averaged over the device planes, or ``{}`` where the trace
    holds no device operation."""
    spans = [
        (float(s), float(s + d), n)
        for ln in lines if not DEVICE_PLANE.match(ln["plane"])
        for n, s, d in zip(ln["names"], ln["start_ns"], ln["dur_ns"])
        if n.startswith(span_prefix)
    ]
    planes = sorted({ln["plane"] for ln in lines if DEVICE_PLANE.match(ln["plane"])})
    busy, window, programs = [], [], []
    op_time: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for plane in planes:
        ops = [ln for ln in lines if ln["plane"] == plane and ln["line"] == OPS_LINE]
        if not ops:
            continue
        starts = np.concatenate([np.asarray(ln["start_ns"], float) for ln in ops])
        durs = np.concatenate([np.asarray(ln["dur_ns"], float) for ln in ops])
        seg_s, seg_e = _union(starts, starts + durs)
        begin = min([seg_s[0]] + [s for s, _, _ in spans])
        busy.append(float((seg_e - seg_s).sum()))
        window.append(float(seg_e[-1] - begin))
        for ln in ops:
            for n, d in zip(ln["names"], ln["dur_ns"]):
                n = short_op_name(n)
                op_time[n] = op_time.get(n, 0.0) + d
        gap_s, gap_e = np.append(begin, seg_e[:-1]), seg_s
        for name, ns in _name_gaps(gap_s, gap_e, spans).items():
            gaps[name] = gaps.get(name, 0.0) + ns
        for ln in lines:
            if ln["plane"] == plane and ln["line"] == MODULES_LINE:
                programs += [d / 1e6 for n, d in zip(ln["names"], ln["dur_ns"])
                             if program in n]
    if not busy:
        return {}
    chips = len(busy)

    def top(table: Dict[str, float]) -> List[List[Any]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9 / chips] for name, ns in rows]

    return {
        "chips": chips,
        "busy_s": sum(busy) / 1e9 / chips,
        "window_s": sum(window) / 1e9 / chips,
        "program_ms": programs,
        "device_ops": top(op_time),
        "idle_gaps": top(gaps),
    }


def reduce_trace(trace_dir: Path, program: str, span_prefix: str) -> Dict[str, Any]:
    xplane = newest_xplane(trace_dir)
    if xplane is None:
        return {}
    return reduce_lines(load_lines(xplane, span_prefix), program, span_prefix)
