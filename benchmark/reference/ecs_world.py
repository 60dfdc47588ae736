"""Plain reference of the ``ecs_world`` family: a bevy_ggrs-style component
world in 16.16 fixed point, all matches at once on a leading axis.

A copy of the arithmetic of ``ggrs_tpu/games/ecs_world.py`` ``advance_np``
(the repo's own oracle), kept here so that no later PR can change what the
benchmark compares against.  Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

_FP = 16
_ONE = 1 << _FP
_WORLD = np.asarray([1024 * _ONE, 1024 * _ONE], np.int64)
_ACCEL = int(0.08 * _ONE)
_MAX_V = 4 * _ONE
_FRICTION_NUM = 248
_RALLY_STEP = 2 * _ONE
_CONTACT_RANGE_SQ = 24 ** 2
_MAX_HEALTH = 100

State = Dict[str, np.ndarray]


def _one(config: dict) -> State:
    p, epp = int(config["players"]), int(config["entities_per_player"])
    e = p * epp
    w, h = int(_WORLD[0]), int(_WORLD[1])
    owner = np.repeat(np.arange(p, dtype=np.int32), epp)
    corners = np.asarray(
        [[w // 4, h // 4], [3 * w // 4, 3 * h // 4], [3 * w // 4, h // 4],
         [w // 4, 3 * h // 4]], np.int64,
    )[:p]
    lane = np.arange(e, dtype=np.int64) % epp
    pos = corners[owner] + np.stack(
        [(lane % 8) * 4 * _ONE, (lane // 8) * 4 * _ONE], axis=1
    )
    return {
        "pos": pos.astype(np.int32),
        "vel": np.zeros((e, 2), np.int32),
        "health": np.full((e,), _MAX_HEALTH, np.int32),
        "rally": corners.astype(np.int32),
        "owner": owner,
    }


def init_state(config: dict, matches: int) -> State:
    return {k: np.repeat(v[None], matches, axis=0) for k, v in _one(config).items()}


def advance(config: dict, state: State, inputs: np.ndarray) -> State:
    """One frame of every match.  ``inputs``: ``[matches, players]`` masks
    that move each player's rally point."""
    inp = inputs.astype(np.int64)
    up, down, left, right = (inp & 1), (inp >> 1) & 1, (inp >> 2) & 1, (inp >> 3) & 1
    delta = np.stack([right - left, down - up], axis=-1) * _RALLY_STEP
    rally = np.remainder(state["rally"].astype(np.int64) + delta, _WORLD)

    owner = state["owner"]
    target = np.take_along_axis(rally, owner[:, :, None].astype(np.int64), axis=1)
    diff = target - state["pos"]
    vel = state["vel"].astype(np.int64) + np.sign(diff) * _ACCEL
    vel = np.clip(vel, -_MAX_V, _MAX_V)
    vel = (vel * _FRICTION_NUM) >> 8
    pos = np.remainder(state["pos"].astype(np.int64) + vel, _WORLD)

    # whole pixels are under 1,024, so the squared distance fits int32
    px = (pos >> _FP).astype(np.int32)
    dx = px[:, :, None, 0] - px[:, None, :, 0]
    dy = px[:, :, None, 1] - px[:, None, :, 1]
    dist_sq = dx * dx + dy * dy
    alive = state["health"] > 0
    enemy = owner[:, :, None] != owner[:, None, :]
    touching = (
        (dist_sq <= _CONTACT_RANGE_SQ) & enemy & alive[:, :, None] & alive[:, None, :]
    )
    hits = touching.sum(axis=2)
    health = np.where(alive, state["health"] - hits, 0)

    spawn = _one(config)["pos"][None]
    dead = health <= 0
    pos = np.where(dead[..., None], spawn, pos)
    vel = np.where(dead[..., None], 0, vel)
    health = np.where(dead, _MAX_HEALTH, health)
    return {
        "pos": pos.astype(np.int32),
        "vel": vel.astype(np.int32),
        "health": health.astype(np.int32),
        "rally": rally.astype(np.int32),
        "owner": owner,
    }


def witness(state: State) -> int:
    """Units below full health: the contact pass has found an enemy in range
    (a run's check that the traffic really drives it)."""
    return int((state["health"] < _MAX_HEALTH).sum())


def state_bytes(config: dict) -> int:
    """Bytes of one session's state from the configuration's shapes: pos and
    vel ``[E, 2]``, health and owner ``[E]``, rally ``[players, 2]``, int32."""
    p = int(config["players"])
    e = p * int(config["entities_per_player"])
    return 4 * (2 * e + 2 * e + e + e + 2 * p)
