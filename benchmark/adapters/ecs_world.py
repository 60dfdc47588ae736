"""The program's side of the ``ecs_world`` family: ``EcsWorld`` (component
tables as one pytree).  Its input is the same one-byte mask on the wire as
ex_game's (here it moves the player's rally point), so the encoding is the
``boxgame`` adapter's."""

from __future__ import annotations

from benchmark.adapters.boxgame import (  # noqa: F401  (the adapter's interface)
    example_inputs,
    inputs_to_array,
    raw_inputs_to_array,
    session_config,
)
from ggrs_tpu.games import EcsWorld


def make_game(config: dict):
    return EcsWorld(int(config["players"]), int(config["entities_per_player"]))
