"""The one traffic generator: every mix is a data file of parameters under
``benchmark/traffic/`` that this module turns into inputs.

A mix fixes the loop (``closed``: ticks back to back; ``open``: tick *i* due
at ``t0 + i / rate_hz``), the simulated network (one-way latency in ticks,
loss) and the input schedule; how many matches a cell hosts under it is the
cell's own file's to say (``benchmark/cells/``), so any configuration can run
under any mix.  The schedule is a pure function of ``--seed``: the same seed
gives the same inputs, and every seed gives the same set of hold lengths in
another phase, so no seed changes the amount of work.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import numpy as np

LOOPS = ("closed", "open")


def load_traffic(path: Path) -> Dict[str, Any]:
    traffic = json.loads(Path(path).read_text())
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}")
    if traffic["loop"] == "open" and not traffic.get("rate_hz"):
        raise ValueError(f"{path}: an open loop needs rate_hz")
    if traffic["schedule"]["kind"] not in SCHEDULES:
        raise ValueError(
            f"{path}: unknown schedule {traffic['schedule']['kind']!r} "
            f"(known: {sorted(SCHEDULES)})"
        )
    return traffic


def _stepped_masks(params: Dict[str, Any], seed: int, matches: int,
                   players: int, ticks: int) -> np.ndarray:
    """Button masks held for ``period_min + m % period_span`` ticks (match
    ``m``), each player one tick out of phase with the last.  Player ``p``
    steps through ``cycles[p % len(cycles)]``, a list of masks in which
    neighbours differ, so that every step is a change a repeat-last
    prediction misses.  The seed shifts the phase and nothing else."""
    i = np.arange(ticks, dtype=np.int64)[:, None, None]
    m = np.arange(matches, dtype=np.int64)[None, :, None]
    me = np.arange(players, dtype=np.int64)[None, None, :]
    period = int(params["period_min"]) + m % int(params["period_span"])
    step = (i + int(seed) + 2 * m + me) // period
    cycles = np.asarray(params["cycles"], np.int64)
    mine = cycles[np.arange(players) % len(cycles)]          # [players, steps]
    return mine[me, step % cycles.shape[1]].astype(np.uint8)


SCHEDULES = {"stepped_masks": _stepped_masks}


def schedule(traffic: Dict[str, Any], seed: int, matches: int, players: int,
             ticks: int) -> np.ndarray:
    """``[ticks, matches, players]`` u8: what player ``p`` of match ``m``
    presses at tick ``i``."""
    spec = traffic["schedule"]
    return SCHEDULES[spec["kind"]](spec, seed, matches, players, ticks)


def frame_inputs(rows: np.ndarray, delay: int) -> np.ndarray:
    """``rows[tick]`` as the simulation consumes them under an input delay:
    frame ``f`` runs on what was pressed at tick ``f - delay``, and the first
    ``delay`` frames, which no press could reach, on the blank input."""
    if delay <= 0:
        return rows
    return np.concatenate([np.zeros_like(rows[:delay]), rows[:-delay]])
