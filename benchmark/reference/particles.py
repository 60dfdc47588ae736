"""Plain reference of the ``particles`` family: bevy_ggrs's particle stress
test as a slot table in 16.16 fixed point, all matches at once on a leading
axis.

A copy of the arithmetic of ``ggrs_tpu/games/particles.py`` ``advance_np``
(the repo's own oracle), kept here so that no later PR can change what the
benchmark compares against.  Imports nothing of the program.  Where the
program selects over the whole slot axis, this writes the spawn window by
index.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

_FP = 16
_ONE = 1 << _FP
_WORLD = np.asarray([1024 * _ONE, 1024 * _ONE], np.int64)
_EMITTER_STEP = 2 * _ONE
_GRAVITY = _ONE >> 4
_SPEED_MASK, _SPEED_BIAS = 4 * _ONE - 1, 2 * _ONE
_ROT_MASK, _ROT_BIAS = 2 * _ONE - 1, _ONE
_SCALE_MASK, _SCALE_BIAS = _ONE - 1, _ONE >> 1
_DEPTH_MASK = 15
_RNG_SEED = 0x2545F491
_MASK = np.uint64(0xFFFFFFFF)
_GOLDEN = np.uint64(0x9E3779B9)
_K_PLAYER = np.uint64(0x85EBCA6B)
_K_INDEX = np.uint64(0xC2B2AE35)
_K_LANE = 0x27D4EB2F
_MIX_A = np.uint64(0x7FEB352D)
_MIX_B = np.uint64(0x846CA68B)
_WORDS = {"translation": 3, "rotation": 4, "scale": 3, "velocity": 2}

State = Dict[str, np.ndarray]


def _mix(x: np.ndarray) -> np.ndarray:
    """A 32-bit finalizer on u64 carriers masked to 32 bits."""
    x = x ^ (x >> np.uint64(16))
    x = (x * _MIX_A) & _MASK
    x = x ^ (x >> np.uint64(15))
    x = (x * _MIX_B) & _MASK
    return x ^ (x >> np.uint64(16))


def _sizes(config: dict):
    return (int(config["players"]), int(config["capacity"]),
            int(config["rate"]), int(config["ttl_frames"]))


def init_state(config: dict, matches: int) -> State:
    """An empty table, the emitters on the world's quarter points, and the
    resources (RNG state, spawn cursor, frame)."""
    p, c, _, _ = _sizes(config)
    w, h = int(_WORLD[0]), int(_WORLD[1])
    corners = np.asarray(
        [[w // 4, h // 4], [3 * w // 4, 3 * h // 4], [3 * w // 4, h // 4],
         [w // 4, 3 * h // 4]], np.int32,
    )[:p]
    state = {k: np.zeros((matches, n, c), np.int32) for k, n in _WORDS.items()}
    state["ttl"] = np.zeros((matches, c), np.int32)
    state["emitter"] = np.repeat(corners[None], matches, axis=0)
    state["resources"] = np.repeat(
        np.asarray([[_RNG_SEED, 0, 0]], np.int32), matches, axis=0)
    return state


def advance(config: dict, state: State, inputs: np.ndarray) -> State:
    """One frame of every match.  ``inputs``: ``[matches, players]`` masks
    that move each player's emitter."""
    p, c, rate, ttl_frames = _sizes(config)
    matches = len(inputs)
    rng, cursor, frame = (state["resources"][:, i].astype(np.int64) for i in range(3))

    # steer
    inp = inputs.astype(np.int64)
    up, down, left, right = (inp & 1), (inp >> 1) & 1, (inp >> 2) & 1, (inp >> 3) & 1
    delta = np.stack([right - left, down - up], axis=-1) * _EMITTER_STEP
    emitter = np.remainder(state["emitter"].astype(np.int64) + delta, _WORLD)

    # age
    ttl = np.where(state["ttl"] > 0, state["ttl"] - 1, 0).astype(np.int32)

    # integrate the live
    live = (ttl > 0)[:, None, :]
    translation, velocity = state["translation"].copy(), state["velocity"].copy()
    translation[:, :2] += np.where(live, state["velocity"], 0)
    velocity[:, 1] -= np.where(live[:, 0], _GRAVITY, 0).astype(np.int32)
    rotation, scale = state["rotation"].copy(), state["scale"].copy()

    # spawn into the free slots of the window at the cursor
    n = np.arange(p * rate)
    slots = (cursor[:, None] + n[None]) % c                      # [matches, n]
    player, index = n // rate, n % rate
    base = _mix(
        ((rng[:, None] & 0xFFFFFFFF).astype(np.uint64)
         + (frame[:, None] & 0xFFFFFFFF).astype(np.uint64) * _GOLDEN
         + player[None].astype(np.uint64) * _K_PLAYER
         + index[None].astype(np.uint64) * _K_INDEX) & _MASK
    )
    w = [
        _mix(base ^ np.uint64(((lane + 1) * _K_LANE) & 0xFFFFFFFF))
        .astype(np.uint32).view(np.int32)
        for lane in range(10)
    ]
    at = emitter[:, player, :].astype(np.int32)                  # [matches, n, 2]
    new = {
        "translation": [at[..., 0], at[..., 1], (w[0] & _DEPTH_MASK) << _FP],
        "velocity": [(w[1] & _SPEED_MASK) - _SPEED_BIAS,
                     (w[2] & _SPEED_MASK) - _SPEED_BIAS],
        "rotation": [(w[3 + i] & _ROT_MASK) - _ROT_BIAS for i in range(4)],
        "scale": [(w[7 + i] & _SCALE_MASK) + _SCALE_BIAS for i in range(3)],
    }
    tables = {"translation": translation, "velocity": velocity,
              "rotation": rotation, "scale": scale}
    free = np.take_along_axis(ttl, slots, axis=1) == 0
    rows = np.arange(matches)[:, None]
    for k, words in new.items():
        for i, value in enumerate(words):
            old = tables[k][rows, i, slots]
            tables[k][rows, i, slots] = np.where(free, value, old)
    ttl[rows, slots] = np.where(free, ttl_frames, ttl[rows, slots])

    rng = _mix((rng.astype(np.uint64) + _GOLDEN) & _MASK)
    resources = np.stack(
        [rng.astype(np.uint32).view(np.int32),
         ((cursor + p * rate) % c).astype(np.int32),
         (frame + 1).astype(np.int32)], axis=1)
    return dict(tables, ttl=ttl, emitter=emitter.astype(np.int32),
                resources=resources)


def witness(state: State) -> int:
    """Particles born in this frame into a table that is full, away from the
    quarter points where the emitters start: the traffic has filled the
    table and now recycles it, and its masks have steered the emitters
    (before the table fills, or under masks that steer nothing: 0).  A slot
    born this frame holds the greatest ``ttl`` of its table and still lies
    on its emitter."""
    ttl = state["ttl"]
    full = (ttl > 0).all(axis=1)
    born = ttl == ttl.max(axis=1, keepdims=True)
    w, h = int(_WORLD[0]), int(_WORLD[1])
    x, y = state["translation"][:, 0], state["translation"][:, 1]
    home = np.isin(x, (w // 4, 3 * w // 4)) & np.isin(y, (h // 4, 3 * h // 4))
    return int((born & ~home)[full].sum())


def state_bytes(config: dict) -> int:
    """Bytes of one session's state from the configuration's shapes: 13 int32
    words a slot, the emitters ``[players, 2]`` and three resource words."""
    p, c, _, _ = _sizes(config)
    return 4 * (13 * c + 2 * p + 3)
