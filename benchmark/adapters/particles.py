"""The program's side of the ``particles`` family: ``ParticleWorld`` (bevy_ggrs's
particle stress test as a slot table).  Its input is the same one-byte mask on
the wire as ex_game's (here it moves the player's emitter), so the encoding is
the ``boxgame`` adapter's."""

from __future__ import annotations

from benchmark.adapters.boxgame import (  # noqa: F401  (the adapter's interface)
    example_inputs,
    inputs_to_array,
    raw_inputs_to_array,
    session_config,
)
from ggrs_tpu.games import ParticleWorld


def make_game(config: dict):
    return ParticleWorld(int(config["players"]), int(config["capacity"]),
                         int(config["rate"]), int(config["ttl_frames"]))
