"""ParticleWorld: bevy_ggrs's particle stress test as one pytree.

bevy_ggrs ships ``examples/stress_tests/particles.rs`` to ask how much world
a session can roll back: every frame each player spawns ``--rate`` particles,
each a rolled-back entity with ``Transform``, ``Velocity`` and a time to
live, and a rolled-back RNG resource drives the spawns.  The TPU-native form
is a fixed-capacity slot table — one array per component over a slot axis —
plus the rolled-back resources, all int32 in 16.16 fixed point (the source
computes in f32, which is bit-stable on one platform only; here the device,
the NumPy oracle and every peer agree bit for bit).

One slot holds what the source snapshots of a particle: ``translation`` (3
words), ``rotation`` (4), ``scale`` (3), ``velocity`` (2), ``ttl`` (1): 13
words, 52 bytes; ``ttl == 0`` is a free slot (its other words keep what they
last held).  Resources: each player's ``emitter`` position, and ``resources``
= (RNG state, spawn cursor, frame).

Systems per frame:
  1. steer — each player's emitter moves by the direction bits of that
     player's input mask and wraps around the world (as EcsWorld's rally
     point does), so one input displaces every later particle of that player;
  2. age — ``ttl -= 1`` where live; reaching 0 frees the slot;
  3. integrate — live particles: translation += velocity, velocity.y -= g;
  4. spawn — each player emits ``rate`` particles at its emitter into the
     FREE slots among ``cursor .. cursor + players * rate - 1 (mod
     capacity)``; velocity, depth, rotation and scale come from a
     counter-based hash of (RNG state, frame, player, index).

With ``ttl_frames = capacity / (players * rate)`` the cursor comes round to a
slot in the very frame its particle expires: from frame ``ttl_frames`` on
every slot is live, and ``players * rate`` die and are born a frame.

No gather or scatter is indexed per session (docs/DESIGN.md §3): the spawn is
a select over the slot axis against the cursor, as the ring's write is over
its axis.  ``advance_np`` is the independent oracle and writes the spawn
window by index instead.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp

_FP = 16
_ONE = 1 << _FP
WORLD_W = 1024 * _ONE
WORLD_H = 1024 * _ONE
_EMITTER_STEP = 2 * _ONE
_GRAVITY = _ONE >> 4  # 1/16 pixel a frame a frame
_SPEED_MASK = (4 * _ONE) - 1  # spawn velocity in [-2, 2) pixels a frame
_SPEED_BIAS = 2 * _ONE
_ROT_MASK = (2 * _ONE) - 1  # rotation words in [-1, 1)
_ROT_BIAS = _ONE
_SCALE_MASK = _ONE - 1  # scale words in [0.5, 1.5)
_SCALE_BIAS = _ONE >> 1
_DEPTH_MASK = 15  # translation.z: one of 16 whole layers
_RNG_SEED = 0x2545F491
_MASK32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9
_K_PLAYER = 0x85EBCA6B
_K_INDEX = 0xC2B2AE35
_K_LANE = 0x27D4EB2F
_MIX_A = 0x7FEB352D
_MIX_B = 0x846CA68B
_HASH_LANES = 10  # depth, velocity 2, rotation 4, scale 3


def _mix(x: jax.Array) -> jax.Array:
    """A 32-bit finalizer (xor-shift, odd multiply, twice): u32 in, u32 out."""
    x = x ^ (x >> 16)
    x = x * np.uint32(_MIX_A)
    x = x ^ (x >> 15)
    x = x * np.uint32(_MIX_B)
    return x ^ (x >> 16)


def _mix_np(x: np.ndarray) -> np.ndarray:
    """``_mix`` on u64 carriers masked to 32 bits (NumPy warns on u32 wrap)."""
    m = np.uint64(_MASK32)
    x = x ^ (x >> np.uint64(16))
    x = (x * np.uint64(_MIX_A)) & m
    x = x ^ (x >> np.uint64(15))
    x = (x * np.uint64(_MIX_B)) & m
    return x ^ (x >> np.uint64(16))


def _u32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


class ParticleWorld:
    """Factory with the standard game interface: init_state / advance (JAX)
    and advance_np (NumPy oracle)."""

    def __init__(self, num_players: int = 2, capacity: int = 10000,
                 rate: int = 100, ttl_frames: int = 50) -> None:
        assert 1 <= num_players <= 4
        assert rate >= 1 and ttl_frames >= 1
        # the cursor comes round to a slot in the frame its particle expires
        assert capacity == num_players * rate * ttl_frames
        self.num_players = num_players
        self.capacity = capacity
        self.rate = rate
        self.ttl_frames = ttl_frames

    # -- state ---------------------------------------------------------

    def init_state_np(self) -> Dict[str, np.ndarray]:
        P, C = self.num_players, self.capacity
        corners = np.asarray(
            [
                [WORLD_W // 4, WORLD_H // 4],
                [3 * WORLD_W // 4, 3 * WORLD_H // 4],
                [3 * WORLD_W // 4, WORLD_H // 4],
                [WORLD_W // 4, 3 * WORLD_H // 4],
            ],
            np.int32,
        )[:P]
        return {
            "translation": np.zeros((3, C), np.int32),
            "rotation": np.zeros((4, C), np.int32),
            "scale": np.zeros((3, C), np.int32),
            "velocity": np.zeros((2, C), np.int32),
            "ttl": np.zeros((C,), np.int32),
            "emitter": corners.copy(),
            # RNG state, spawn cursor, frame
            "resources": np.asarray([_RNG_SEED, 0, 0], np.int32),
        }

    def init_state(self) -> Dict[str, jax.Array]:
        return jax.tree_util.tree_map(jnp.asarray, self.init_state_np())

    # -- advance: jax ---------------------------------------------------

    def advance(self, state: Any, inputs: Any) -> Any:
        rng, cursor, frame = (state["resources"][i] for i in range(3))
        with jax.named_scope("steer"):
            emitter = self._steer(state["emitter"], inputs)
        with jax.named_scope("age"):
            ttl = self._age(state["ttl"])
        with jax.named_scope("integrate"):
            translation, velocity = self._integrate(
                state["translation"], state["velocity"], ttl
            )
        with jax.named_scope("spawn"):
            tables = self._spawn(
                {
                    "translation": translation,
                    "rotation": state["rotation"],
                    "scale": state["scale"],
                    "velocity": velocity,
                    "ttl": ttl,
                },
                emitter, rng, cursor, frame,
            )
        step = self.num_players * self.rate
        resources = jnp.stack(
            [
                _i32(_mix(_u32(rng) + np.uint32(_GOLDEN))),
                jnp.remainder(cursor + step, self.capacity),
                frame + 1,
            ]
        )
        return dict(tables, emitter=emitter, resources=resources)

    def _steer(self, emitter: jax.Array, inputs: Any) -> jax.Array:
        inp = jnp.asarray(inputs, jnp.int32)
        up = (inp >> 0) & 1
        down = (inp >> 1) & 1
        left = (inp >> 2) & 1
        right = (inp >> 3) & 1
        delta = jnp.stack([right - left, down - up], axis=1) * _EMITTER_STEP
        world = jnp.asarray([WORLD_W, WORLD_H], jnp.int32)
        return jnp.remainder(emitter + delta, world)

    def _age(self, ttl: jax.Array) -> jax.Array:
        return jnp.where(ttl > 0, ttl - 1, 0)

    def _integrate(self, translation, velocity, ttl):
        live = (ttl > 0)[None, :]
        moved = jnp.concatenate([translation[:2] + velocity, translation[2:]])
        fallen = velocity - jnp.asarray([[0], [_GRAVITY]], jnp.int32)
        return (jnp.where(live, moved, translation),
                jnp.where(live, fallen, velocity))

    def _spawn(self, tables, emitter, rng, cursor, frame):
        P, C, rate = self.num_players, self.capacity, self.rate
        slot = jnp.arange(C, dtype=jnp.int32)
        rel = jnp.remainder(slot - cursor, C)  # place in the spawn window
        player = sum(
            ((rel >= p * rate).astype(jnp.int32) for p in range(1, P)),
            jnp.zeros_like(rel),
        )
        index = rel - player * rate
        born = (rel < P * rate) & (tables["ttl"] == 0)
        at = sum(
            jnp.where((player == p)[None, :], emitter[p][:, None], 0)
            for p in range(P)
        )  # [2, C]: the emitter of the slot's player
        base = _mix(
            _u32(rng)
            + _u32(frame) * np.uint32(_GOLDEN)
            + _u32(player) * np.uint32(_K_PLAYER)
            + _u32(index) * np.uint32(_K_INDEX)
        )
        w = [
            _i32(_mix(base ^ np.uint32(((lane + 1) * _K_LANE) & _MASK32)))
            for lane in range(_HASH_LANES)
        ]
        new = {
            "translation": jnp.stack(
                [at[0], at[1], (w[0] & _DEPTH_MASK) << _FP]
            ),
            "velocity": jnp.stack(
                [(w[1] & _SPEED_MASK) - _SPEED_BIAS,
                 (w[2] & _SPEED_MASK) - _SPEED_BIAS]
            ),
            "rotation": jnp.stack(
                [(w[3 + i] & _ROT_MASK) - _ROT_BIAS for i in range(4)]
            ),
            "scale": jnp.stack(
                [(w[7 + i] & _SCALE_MASK) + _SCALE_BIAS for i in range(3)]
            ),
            "ttl": jnp.full((C,), self.ttl_frames, jnp.int32),
        }
        return {
            k: jnp.where(born if k == "ttl" else born[None, :], v, tables[k])
            for k, v in new.items()
        }

    # -- advance: numpy oracle ------------------------------------------

    def advance_np(self, state: Dict[str, np.ndarray], inputs: np.ndarray) -> Dict[str, np.ndarray]:
        P, C, rate = self.num_players, self.capacity, self.rate
        rng, cursor, frame = (int(v) for v in state["resources"])

        inp = np.asarray(inputs).astype(np.int64)
        up, down, left, right = inp & 1, (inp >> 1) & 1, (inp >> 2) & 1, (inp >> 3) & 1
        delta = np.stack([right - left, down - up], axis=1) * _EMITTER_STEP
        world = np.asarray([WORLD_W, WORLD_H], np.int64)
        emitter = np.remainder(state["emitter"] + delta, world).astype(np.int32)

        ttl = np.where(state["ttl"] > 0, state["ttl"] - 1, 0).astype(np.int32)

        live = ttl > 0
        translation, velocity = state["translation"].copy(), state["velocity"].copy()
        translation[:2, live] += state["velocity"][:, live]
        velocity[1, live] -= _GRAVITY
        rotation, scale = state["rotation"].copy(), state["scale"].copy()

        n = np.arange(P * rate)
        slots = (cursor + n) % C
        player, index = n // rate, n % rate
        base = _mix_np(
            (np.uint64(rng & _MASK32)
             + np.uint64(frame & _MASK32) * np.uint64(_GOLDEN)
             + player.astype(np.uint64) * np.uint64(_K_PLAYER)
             + index.astype(np.uint64) * np.uint64(_K_INDEX)) & np.uint64(_MASK32)
        )
        w = [
            _mix_np(base ^ np.uint64(((lane + 1) * _K_LANE) & _MASK32))
            .astype(np.uint32).view(np.int32)
            for lane in range(_HASH_LANES)
        ]
        free = ttl[slots] == 0
        to = slots[free]
        translation[0, to] = emitter[player[free], 0]
        translation[1, to] = emitter[player[free], 1]
        translation[2, to] = ((w[0] & _DEPTH_MASK) << _FP)[free]
        velocity[0, to] = ((w[1] & _SPEED_MASK) - _SPEED_BIAS)[free]
        velocity[1, to] = ((w[2] & _SPEED_MASK) - _SPEED_BIAS)[free]
        for i in range(4):
            rotation[i, to] = ((w[3 + i] & _ROT_MASK) - _ROT_BIAS)[free]
        for i in range(3):
            scale[i, to] = ((w[7 + i] & _SCALE_MASK) + _SCALE_BIAS)[free]
        ttl[to] = self.ttl_frames

        rng = int(_mix_np(np.uint64((rng + _GOLDEN) & _MASK32)))
        resources = np.asarray(
            [rng, (cursor + P * rate) % C, frame + 1], np.uint32
        ).view(np.int32)
        return {
            "translation": translation,
            "rotation": rotation,
            "scale": scale,
            "velocity": velocity,
            "ttl": ttl,
            "emitter": emitter,
            "resources": resources,
        }
