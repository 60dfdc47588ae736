"""HBM-resident state ring.

The reference keeps ``max_prediction + 1`` saved states in a ring of
host-memory cells indexed ``frame % len`` (/root/reference/src/sync_layer.rs:144-166).
The TPU equivalent stacks every saved state into one pytree with a leading ring
axis that lives in HBM for the whole session, and neither *save* nor *load*
moves a byte to the host.  Checksums for each slot are kept in a parallel
``(R, 4)`` uint32 array so desync/synctest comparisons are device-side too.

Two forms of write, by who indexes (docs/DESIGN.md §3):

- ``save`` / ``save_many`` write one slot at a dynamic index: an in-place
  slice update when the index is ONE scalar shared by the whole batch (the
  replay path, ``ops/replay.py``).
- ``save_where`` selects over the whole ring axis and holds no dynamic index:
  the form for sessions batched under ``vmap`` at frames of their own (the
  served pool), where an indexed write would be a scatter that XLA:TPU runs
  as a serial loop over the sessions.

*load* is a dynamic slice (a gather under a per-session frame).

A ring is logical shapes only: in which layout the device holds a leaf
between ticks is chosen where the carry is built, not here
(``parallel/session_pool.py`` ``ring_leaf_layout``: a large, lane-wide leaf
of the served pool is held row-major, so that it crosses the tick program's
boundary without a transposition; DESIGN §3 "The ring's layout at the
program's boundary").
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .checksum import CHECKSUM_LANES


class DeviceStateRing:
    """Functional ring buffer over a pytree-of-arrays.

    All methods are pure (return new buffers) and jittable; a ring is just a
    pytree ``{"states": stacked pytree, "checksums": (R, 4) u32,
    "frames": (R,) i32}`` and can live inside ``lax.scan`` carries.  The class
    only holds the static ring length and offers the index math; this mirrors
    how ``SavedStates`` owns cells while the session owns frame bookkeeping.
    """

    def __init__(self, length: int) -> None:
        assert length >= 1
        self.length = length

    # -- construction --------------------------------------------------

    def init(self, template_state: Any) -> Any:
        """Build ring buffers by broadcasting ``template_state`` into every
        slot (slot frames start as NULL_FRAME = -1)."""
        stacked = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(
                jnp.asarray(leaf)[None, ...],
                (self.length,) + jnp.asarray(leaf).shape,
            ).copy(),
            template_state,
        )
        return {
            "states": stacked,
            "checksums": jnp.zeros((self.length, CHECKSUM_LANES), jnp.uint32),
            "frames": jnp.full((self.length,), -1, jnp.int32),
        }

    # -- index math ----------------------------------------------------

    def slot(self, frame: jax.Array) -> jax.Array:
        """``frame % R`` with traced frames (frame >= 0)."""
        return jax.lax.rem(jnp.asarray(frame, jnp.int32), jnp.int32(self.length))

    # -- save / load ---------------------------------------------------

    def save(
        self, ring: Any, frame: jax.Array, state: Any, checksum: jax.Array
    ) -> Any:
        """Write ``state`` (+ checksum) into the slot for ``frame``."""
        i = self.slot(frame)
        return {
            "states": jax.tree_util.tree_map(
                lambda buf, leaf: jax.lax.dynamic_update_index_in_dim(
                    buf, jnp.asarray(leaf, buf.dtype), i, axis=0
                ),
                ring["states"],
                state,
            ),
            "checksums": jax.lax.dynamic_update_index_in_dim(
                ring["checksums"], checksum, i, axis=0
            ),
            "frames": ring["frames"].at[i].set(jnp.asarray(frame, jnp.int32)),
        }

    def save_where(
        self,
        ring: Any,
        frame: jax.Array,
        state: Any,
        checksum: jax.Array,
        pred: jax.Array,
    ) -> Any:
        """Predicated ``save``: the slot keeps its current contents where
        ``pred`` (scalar bool) is false.  This is the masked form batched
        heterogeneous fulfillment needs — under ``vmap`` each session decides
        independently whether this tick's write happens, at a frame of its
        own.

        Written as ONE select over the ring axis, with no dynamic index: a
        slot index that differs per session turns ``save``'s
        dynamic-update-slice into a scatter under ``vmap``, which XLA:TPU
        runs as a serial loop over the sessions, once per buffer (DESIGN §3
        "Per-session slots").  The select is elementwise over ``[B, R, ...]``
        and fuses into the caller's scan body.  A negative ``frame`` (an idle
        descriptor row's -1) matches no slot.

        The price is that every call rewrites the whole ring instead of one
        slot: 512 sessions x 10 slots x 40 B = 205 KB a write for
        ``boxgame-2p``, 256 x 18 x 3,104 B = 14.3 MB for ``ecs-4p`` (0.66 ms
        of 19 writes a tick at 819 GB/s, against 92 ms for the scatter).
        That is the wrong trade once B x R x state bytes reaches gigabytes
        (ROADMAP B2/M7): choose the write there from those three numbers,
        which this method can see.  Measured there (``particles-2p``):
        512 x 10 x 520,028 B = 2.66 GB a write; 11 writes a tick and a tick
        program of 198 ms at PR 29, 3 writes (pre-save, 2 burst steps) and
        68.65 ms since PR 30 ends the burst at the batch's deepest plan
        (PERF.md section 5)."""
        hit = (
            jnp.arange(self.length, dtype=jnp.int32) == self.slot(frame)
        ) & pred

        def upd(buf: jax.Array, leaf: Any) -> jax.Array:
            mask = hit.reshape((self.length,) + (1,) * (buf.ndim - 1))
            return jnp.where(mask, jnp.asarray(leaf, buf.dtype)[None, ...], buf)

        return {
            "states": jax.tree_util.tree_map(upd, ring["states"], state),
            "checksums": upd(ring["checksums"], checksum),
            "frames": upd(ring["frames"], frame),
        }

    def save_many(
        self,
        ring: Any,
        frames: jax.Array,
        states: Any,
        checksums: jax.Array,
    ) -> Any:
        """Write ``n`` consecutive saves in one scatter per leaf.

        ``frames`` is a (n,) i32 vector whose slots must be DISTINCT
        (n <= ring length guarantees it for consecutive frames); ``states``
        leaves carry a leading (n,) axis (e.g. the stacked ys of a resim
        scan); ``checksums`` is (n, 4).  Equivalent to folding ``save`` over
        the n entries but costs one scatter per buffer instead of n — the
        replay's steady tick uses this to take ring maintenance off the
        per-resim-step critical path.
        """
        idx = self.slot(frames)
        return {
            "states": jax.tree_util.tree_map(
                lambda buf, leaf: buf.at[idx].set(
                    jnp.asarray(leaf, buf.dtype)
                ),
                ring["states"],
                states,
            ),
            "checksums": ring["checksums"].at[idx].set(checksums),
            "frames": ring["frames"].at[idx].set(
                jnp.asarray(frames, jnp.int32)
            ),
        }

    def load(self, ring: Any, frame: jax.Array) -> Any:
        """Read the state stored in the slot for ``frame``."""
        i = self.slot(frame)
        return jax.tree_util.tree_map(
            lambda buf: jax.lax.dynamic_index_in_dim(buf, i, axis=0, keepdims=False),
            ring["states"],
        )

    def load_checksum(self, ring: Any, frame: jax.Array) -> jax.Array:
        return jax.lax.dynamic_index_in_dim(
            ring["checksums"], self.slot(frame), axis=0, keepdims=False
        )

    def frame_at(self, ring: Any, frame: jax.Array) -> jax.Array:
        """The frame number actually stored in ``frame``'s slot (NULL_FRAME if
        never written) — the device analog of ``GameStateCell.frame``."""
        return ring["frames"][self.slot(frame)]
