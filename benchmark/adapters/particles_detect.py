"""The program's side of the ``particles_detect`` family: the ``particles``
family (bevy_ggrs's particle stress test) as its source runs it, with desync
detection on at the interval the configuration's file states.  The harness
builds every session as ``SessionBuilder(adapter.session_config())``, so the
mode reaches the builder through the ``Config``."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from benchmark.adapters import particles
from benchmark.adapters.particles import (  # noqa: F401  (the adapter's interface)
    example_inputs,
    inputs_to_array,
    make_game,
    raw_inputs_to_array,
)
from ggrs_tpu.core import DesyncDetection

_CONFIG = Path(__file__).resolve().parents[1] / "configs" / "particles-2p-detect.json"


def session_config():
    interval = json.loads(_CONFIG.read_text())["desync_detection"]["interval"]
    return dataclasses.replace(
        particles.session_config(),
        desync_detection=DesyncDetection.on(int(interval)),
    )
