"""Bundled example games.

The reference ships BoxGame — a 2-4 player "ice physics" ship game — as the
example/integration workload (/root/reference/examples/ex_game/ex_game.rs).
Here the equivalent lives in the library so tests, benches, and examples share
one deterministic workload.  ``boxgame`` is the TPU flagship: state is a
player-vectorized pytree, ``advance`` is pure JAX, and the fixed-point variant
is bitwise deterministic across XLA backends (the float variant, like the
reference's float example, is only deterministic within one backend —
/root/reference/examples/README.md:16-21).
"""

from .boxgame import (
    BOX_INPUT_DOWN,
    BOX_INPUT_LEFT,
    BOX_INPUT_RIGHT,
    BOX_INPUT_UP,
    BoxGame,
    boxgame_config,
)
from .chipvm import ChipVM
from .ecs_world import EcsWorld
from .particles import ParticleWorld
from .rtscmd import RtsCmd, RtsCmdGame, decode_commands, encode_commands

__all__ = [
    "BOX_INPUT_UP",
    "BOX_INPUT_DOWN",
    "BOX_INPUT_LEFT",
    "BOX_INPUT_RIGHT",
    "BoxGame",
    "ChipVM",
    "EcsWorld",
    "ParticleWorld",
    "RtsCmd",
    "RtsCmdGame",
    "boxgame_config",
    "decode_commands",
    "encode_commands",
]
