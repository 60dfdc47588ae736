"""What the program records of itself in a traced slice: the spans of
``ggrs_tpu.obs.trace.default_tracer()``, which wakes while ``run.py``'s
profiler runs and sleeps otherwise, so its ring holds the slice's ticks and
no others.  An event is the tracer's seven-field ring tuple ``(ph, name,
cat, start_ns, dur_ns, tid, args)`` with the enclosing span's name and the
pool tick in ``args`` (``parent``, ``tick``).

``slice_ticks`` is the one reader; everything else is arithmetic on plain
lists, so a hand-made list checks it.  A program without the tracer (the
parent of the PR that brought it), a ring that dropped an event, or a slice
without a ``hosted.tick`` span reads as nothing, never as 0.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int, int, Optional[Dict[str, Any]]]
Ticks = Dict[int, List[Event]]

ROOT = "hosted.tick"
# the native bank's phase timers subdivide a span on its own clock; they are
# no layer of the Python tree and never make their parent an inner node
SUBDIVISIONS = ("native.phase",)


def ticks_of(events: Iterable[Event], root: str = ROOT) -> Ticks:
    """Complete events by pool tick, for the ticks that have a ``root``
    span; in ring order."""
    by_tick: Ticks = {}
    for ev in events:
        args = ev[6]
        if ev[0] == "X" and args and args.get("tick") is not None:
            by_tick.setdefault(int(args["tick"]), []).append(ev)
    return {t: evs for t, evs in by_tick.items()
            if any(e[1] == root for e in evs)}


def slice_ticks() -> Optional[Ticks]:
    """The traced slice's ticks as the program recorded them, or None."""
    try:
        from ggrs_tpu.obs.trace import default_tracer
    except ImportError:
        return None
    tracer = default_tracer()
    if tracer.dropped > 0:
        return None
    return ticks_of(tracer.events()) or None


def _total_ns(events: Sequence[Event], names: Sequence[str]) -> Optional[int]:
    durs = [e[4] for e in events if e[1] in names]
    return sum(durs) if durs else None


def percentile_ms(ticks: Ticks, add: Sequence[str], sub: Sequence[str],
                  q: float) -> Optional[float]:
    """Per tick the summed duration of the ``add`` spans less that of the
    ``sub`` spans; the ``q``-th percentile over the ticks that have an
    ``add`` span (linear between order statistics)."""
    import numpy as np

    values = []
    for events in ticks.values():
        plus = _total_ns(events, add)
        if plus is None:
            continue
        values.append((plus - (_total_ns(events, sub) or 0)) / 1e6)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), float(q)))


def arg_share(ticks: Ticks, span: str, num: str, den: str,
              scale: float = 1.0) -> Optional[float]:
    """Sum of one count over the sum of another, both args of ``span``."""
    top = bottom = 0
    for events in ticks.values():
        for e in events:
            if e[1] == span and e[6] and den in e[6]:
                top += e[6].get(num, 0)
                bottom += e[6][den]
    if not bottom:
        return None
    return scale * top / bottom


def coverage(ticks: Ticks, root: str = ROOT,
             subdivisions: Sequence[str] = SUBDIVISIONS) -> Optional[float]:
    """Time in the leaf spans under ``root`` over time in ``root``: what is
    missing is the self time of the inner spans, the part of the tick that
    no span below them names."""
    leaves = total = 0
    for events in ticks.values():
        tree = [e for e in events if e[2] not in subdivisions]
        parent = {e[1]: (e[6] or {}).get("parent") for e in tree}
        inner = set(parent.values())

        def under_root(name: Optional[str]) -> bool:
            seen = set()
            while name is not None and name not in seen:
                if name == root:
                    return True
                seen.add(name)
                name = parent.get(name)
            return False

        for e in tree:
            if e[1] == root:
                total += e[4]
            elif e[1] not in inner and under_root(parent[e[1]]):
                leaves += e[4]
    if not total:
        return None
    return leaves / total
