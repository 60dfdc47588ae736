"""Plain reference of the ``particles_detect`` family: the ``particles``
family's own (same game, same states), and what its guarantee adds: which
frames a peer must report a digest of, and which digest.  Imports nothing of
the program."""

from __future__ import annotations

from typing import Dict, List

from benchmark.reference import digest
from benchmark.reference.particles import (  # noqa: F401  (the reference's interface)
    State,
    advance,
    init_state,
    state_bytes,
    witness,
)


def report_digests(config: dict,
                   states_by_frame: Dict[int, State]) -> Dict[int, List[int]]:
    """For every frame of ``states_by_frame`` that the configuration's
    interval makes a report frame (k x interval, k >= 1): the u128 every peer
    of each match must report of it, ``[matches]`` long.  ``states_by_frame``
    maps a frame count to the reference's state after that many frames."""
    interval = int(config["desync_detection"]["interval"])
    out = {}
    for frame, state in states_by_frame.items():
        if frame > 0 and frame % interval == 0:
            matches = len(next(iter(state.values())))
            out[frame] = [
                digest.u128({k: v[m] for k, v in state.items()})
                for m in range(matches)
            ]
    return out
