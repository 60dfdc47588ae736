"""Per-slot flight recorder: a bounded ring of recent events, dumped on
quarantine/eviction for post-mortems (DESIGN.md §12).

Rollback netcode faults are archaeology: by the time a slot quarantines,
the packet or decision that doomed it is several ticks in the past.  The
recorder keeps the last ``capacity`` events per slot — supervision state
changes, faults, rollback decisions, and short digests of recent wire
traffic — so the dump that accompanies a quarantine pinpoints what the
slot was doing, without logging anything for healthy slots.

Events are ``(tick, kind, detail)`` triples.  ``detail`` is usually a
short pre-formatted string; hot-path events (the per-datagram wire
digests) may instead pass a small tuple of scalars, which ``dump``
formats lazily — recording must stay cheap enough to leave on for every
healthy slot.  A pool's recorders keep those digests in one columnar
store (:class:`WireTaps`) that a tick fills with one vector scatter, and
merge it with the ring by sequence number when read.  The recorder never
holds references into live session state, so a dump is safe to stash long
after the slot is gone.  Like the metrics registry, recording is
observational only and must never perturb session behavior.
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["ChecksumHistory", "FlightRecorder", "WireTaps", "EV_STATE",
           "EV_FAULT", "EV_ROLLBACK", "EV_WIRE", "EV_EVICT", "EV_DESYNC"]

# event kinds (free-form strings are allowed too; these are the ones the
# pool emits and the chaos summaries group by)
EV_STATE = "state"        # supervision transition (native -> quarantined...)
EV_FAULT = "fault"        # a SlotFault landed
EV_ROLLBACK = "rollback"  # the slot executed a rollback (load op)
EV_WIRE = "wire"          # outbound datagram digest (crc32, length)
EV_EVICT = "evict"        # eviction attempt / outcome
EV_DESYNC = "desync"      # a checksum mismatch / desync-class fault landed


class ChecksumHistory:
    """Bounded per-frame checksum window (desync forensics, DESIGN.md §14).

    The reference's desync detection compares one frame at a time and
    forgets; the first-divergent-frame bisection needs a *window* of
    recent (frame, checksum) pairs from both ends.  This is that window:
    a dict bounded to the newest ``capacity`` distinct frames.
    """

    __slots__ = ("_map", "_order", "capacity")

    def __init__(self, capacity: int = 256) -> None:
        self.capacity = capacity
        self._map: Dict[int, int] = {}
        self._order: Deque[int] = deque()

    def record(self, frame: int, checksum: int) -> None:
        if frame not in self._map:
            self._order.append(frame)
            while len(self._order) > self.capacity:
                self._map.pop(self._order.popleft(), None)
        self._map[frame] = checksum

    def get(self, frame: int):
        return self._map.get(frame)

    def items(self) -> Dict[int, int]:
        """A snapshot copy, safe to keep after the session is gone."""
        return dict(self._map)

    def frames(self) -> List[int]:
        return sorted(self._map)

    def __len__(self) -> int:
        return len(self._map)

    def __contains__(self, frame: int) -> bool:
        return frame in self._map


# one wire digest of the columnar store: the slot's sequence number of the
# event, the tick, the endpoint (a spectator ``e`` is stored as ``-1 - e``),
# the datagram's length and its crc32
_TAP_DTYPE = np.dtype([("seq", "<i8"), ("tick", "<i8"), ("ep", "<i4"),
                       ("len", "<u4"), ("crc", "<u4")])


class WireTaps:
    """The ``EV_WIRE`` digests of a pool's flight recorders, as columns
    (DESIGN.md §12): per slot the count of events of every kind recorded
    so far, which is each event's sequence number, and a ring
    ``capacity`` deep that holds a digest at its sequence number modulo
    ``capacity``.  A row whose sequence number falls below the slot's
    newest ``capacity`` events is stale, as the deque's oldest would be
    dropped.  :meth:`scatter` files a tick's datagrams from the bank's
    outbound table in one vector write; the recorders merge the live rows
    with their other events by sequence number when read."""

    __slots__ = ("capacity", "seq", "rows")

    def __init__(self, slots: int, capacity: int) -> None:
        self.capacity = capacity
        self.seq = np.zeros(slots, np.int64)  # events recorded, all kinds
        self.rows = np.zeros((slots, capacity), _TAP_DTYPE)
        self.rows["seq"] = -1  # never written

    def next_seq(self, slot: int) -> int:
        seq = int(self.seq[slot])
        self.seq[slot] = seq + 1
        return seq

    def put(self, slot: int, tick: int, ep: int, length: int,
            crc: int) -> None:
        """One digest (the per-datagram paths)."""
        seq = self.next_seq(slot)
        if self.capacity:
            self.rows[slot, seq % self.capacity] = (seq, tick, ep, length,
                                                    crc)

    def scatter(self, slots: np.ndarray, tick: int, ep: np.ndarray,
                length: np.ndarray, crc: np.ndarray) -> None:
        """A tick's digests in one write: ``slots`` ascending, each slot's
        rows in send order."""
        n = slots.size
        if not n:
            return
        runs = np.bincount(slots, minlength=self.seq.size)
        rank = np.arange(n) - (np.cumsum(runs) - runs)[slots]
        seq = self.seq[slots] + rank
        self.seq += runs
        cap = self.capacity
        if not cap:
            return
        if runs.max() > cap:
            # a slot sent more datagrams this tick than its ring holds:
            # only its newest ``cap`` land, as the deque would keep them
            keep = rank >= runs[slots] - cap
            slots, ep, length, crc, seq = (
                slots[keep], ep[keep], length[keep], crc[keep], seq[keep]
            )
        col = seq % cap
        rows = self.rows
        rows["seq"][slots, col] = seq
        rows["tick"][slots, col] = tick
        rows["ep"][slots, col] = ep
        rows["len"][slots, col] = length
        rows["crc"][slots, col] = crc

    def events(self, slot: int, floor: int) -> List[Tuple[int, int, str,
                                                        Any]]:
        """The slot's digests numbered ``floor`` or later, oldest first,
        as ``(seq, tick, EV_WIRE, (ep, len, crc))``."""
        rows = self.rows[slot]
        rows = np.sort(rows[rows["seq"] >= max(floor, 0)], order="seq")
        return [
            (seq, tick, EV_WIRE,
             (ep if ep >= 0 else f"spec{-1 - ep}", length, crc))
            for seq, tick, ep, length, crc in rows.tolist()
        ]


class FlightRecorder:
    """Bounded event ring for one pool slot.  A pool's recorders keep
    their wire digests in the pool's :class:`WireTaps` (``taps``, row
    ``slot``); a recorder on its own keeps them in its ring."""

    __slots__ = ("_ring", "_recorded", "_taps", "_slot", "checksums",
                 "remote_checksums")

    def __init__(self, capacity: int = 256,
                 checksum_window: int = 256,
                 taps: Optional[WireTaps] = None, slot: int = 0) -> None:
        # (seq, tick, kind, detail): seq orders the ring against the taps
        self._ring: Deque[Tuple[int, int, str, Any]] = deque(maxlen=capacity)
        self._recorded = 0
        self._taps = taps
        self._slot = slot
        # desync forensics (DESIGN.md §14): the local per-frame checksum
        # window plus one window per remote peer, fed from the desync
        # detection interval traffic — the bisection inputs
        self.checksums = ChecksumHistory(checksum_window)
        self.remote_checksums: Dict[Any, ChecksumHistory] = {}

    @property
    def recorded(self) -> int:
        """Total ever recorded (the ring drops the oldest)."""
        taps = self._taps
        if taps is None:
            return self._recorded
        return int(taps.seq[self._slot])

    def record_checksum(self, frame: int, checksum: int,
                        addr: Any = None) -> None:
        """Record one per-frame checksum: local when ``addr`` is None,
        else into the peer's window."""
        if addr is None:
            self.checksums.record(frame, checksum)
        else:
            hist = self.remote_checksums.get(addr)
            if hist is None:
                hist = ChecksumHistory(self.checksums.capacity)
                self.remote_checksums[addr] = hist
            hist.record(frame, checksum)

    def record(self, tick: int, kind: str, detail: Any = "") -> None:
        taps = self._taps
        if taps is None:
            seq = self._recorded
            self._recorded = seq + 1
        else:
            seq = taps.next_seq(self._slot)
        self._ring.append((seq, tick, kind, detail))

    def record_wire(self, tick: int, ep: Any, length: int, crc: int) -> None:
        """One outbound datagram's digest; ``ep`` is the endpoint index,
        or ``"spec<e>"`` for spectator ``e``."""
        taps = self._taps
        if taps is None:
            self.record(tick, EV_WIRE, (ep, length, crc))
            return
        if not isinstance(ep, int):
            ep = -1 - int(ep[4:])
        taps.put(self._slot, tick, ep, length, crc)

    def events(self, last: int = 0) -> List[Tuple[int, str, Any]]:
        """The retained events, oldest first; ``last`` > 0 keeps only the
        newest ``last``."""
        taps = self._taps
        if taps is None:
            merged = self._ring
        else:
            # the newest ``capacity`` events: the deque holds the newest
            # ``capacity`` of the other kinds, the taps every live digest
            floor = self.recorded - self._ring.maxlen
            merged = [
                e for e in heapq.merge(self._ring,
                                       taps.events(self._slot, floor))
                if e[0] >= floor
            ]
        out = [(tick, kind, detail) for _, tick, kind, detail in merged]
        if last > 0:
            out = out[-last:]
        return out

    def dump(self, last: int = 32) -> str:
        """Human-readable dump of the newest ``last`` events — the
        post-mortem attached to quarantine/eviction logs and chaos
        summaries."""
        events = self.events(last)
        if not events:
            return "  (no recorded events)"
        dropped = self.recorded - len(self)
        lines = []
        if len(events) < self.recorded:
            lines.append(
                f"  ... {self.recorded - len(events)} earlier events "
                f"({dropped} beyond ring capacity)"
            )
        for tick, kind, detail in events:
            if kind == EV_WIRE and isinstance(detail, tuple):
                ep, length, crc = detail
                detail = f"ep={ep} len={length}B crc={crc:08x}"
            lines.append(f"  t{tick:06d} {kind:<9s} {detail}")
        return "\n".join(lines)

    def __len__(self) -> int:
        if self._taps is None:
            return len(self._ring)
        return min(self.recorded, self._ring.maxlen)
