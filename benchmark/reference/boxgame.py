"""Plain reference of the ``boxgame`` family: upstream ex_game's BoxGame in
16.16 fixed point, all matches at once on a leading axis.

A copy of the arithmetic of ``ggrs_tpu/games/boxgame.py`` ``advance_np``
(the repo's own oracle), kept here so that no later PR can change what the
benchmark compares against.  Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

_FP = 16
_ONE = 1 << _FP
_WINDOW = np.asarray([800 * _ONE, 600 * _ONE], np.int64)
_ACCEL = int(0.12 * _ONE)
_MAX_SPEED = 6 * _ONE
_FRICTION_NUM = 252
_ROT_STEP = 3
_ROT_PERIOD = 256
_SIN_FP = np.round(
    np.sin(2.0 * np.pi * np.arange(_ROT_PERIOD) / _ROT_PERIOD) * _ONE
).astype(np.int64)

State = Dict[str, np.ndarray]


def init_state(config: dict, matches: int) -> State:
    """``[matches, players, ...]`` initial state: ships on a circle of radius
    150 around the centre, facing outward."""
    p = int(config["players"])
    angles = (np.arange(p) * (_ROT_PERIOD // p)) % _ROT_PERIOD
    r = 150 * _ONE
    cos = _SIN_FP[(angles + _ROT_PERIOD // 4) % _ROT_PERIOD]
    sin = _SIN_FP[angles]
    pos = np.stack(
        [_WINDOW[0] // 2 + ((r * cos) >> _FP), _WINDOW[1] // 2 + ((r * sin) >> _FP)],
        axis=1,
    ).astype(np.int32)
    one = {
        "pos": pos,
        "vel": np.zeros((p, 2), np.int32),
        "rot": angles.astype(np.int32),
    }
    return {k: np.repeat(v[None], matches, axis=0) for k, v in one.items()}


def advance(config: dict, state: State, inputs: np.ndarray) -> State:
    """One frame of every match.  ``inputs``: ``[matches, players]`` button
    masks (bit 0 up, 1 down, 2 left, 3 right)."""
    inp = inputs.astype(np.int64)
    up, down, left, right = (inp & 1), (inp >> 1) & 1, (inp >> 2) & 1, (inp >> 3) & 1
    turn, thrust = right - left, up - down
    rot = np.remainder(state["rot"] + turn * _ROT_STEP, _ROT_PERIOD)
    cos = _SIN_FP[np.remainder(rot + _ROT_PERIOD // 4, _ROT_PERIOD)]
    sin = _SIN_FP[rot]
    acc = np.stack(
        [thrust * ((_ACCEL * cos) >> _FP), thrust * ((_ACCEL * sin) >> _FP)], axis=-1
    )
    vel = np.clip(state["vel"].astype(np.int64) + acc, -_MAX_SPEED, _MAX_SPEED)
    vel = (vel * _FRICTION_NUM) >> 8
    pos = np.remainder(state["pos"].astype(np.int64) + vel, _WINDOW)
    return {
        "pos": pos.astype(np.int32),
        "vel": vel.astype(np.int32),
        "rot": rot.astype(np.int32),
    }


def state_bytes(config: dict) -> int:
    """Bytes of one session's state from the configuration's shapes: pos and
    vel ``[players, 2]`` and rot ``[players]``, all int32."""
    p = int(config["players"])
    return 4 * (2 * p + 2 * p + p)
