"""HBM-resident state ring.

The reference keeps ``max_prediction + 1`` saved states in a ring of
host-memory cells indexed ``frame % len`` (/root/reference/src/sync_layer.rs:144-166).
The TPU equivalent stacks every saved state into one pytree with a leading ring
axis that lives in HBM for the whole session, and neither *save* nor *load*
moves a byte to the host.  Checksums for each slot are kept in a parallel
``(R, 4)`` uint32 array so desync/synctest comparisons are device-side too.

Forms of write, by who indexes and by size (docs/DESIGN.md §3):

- ``save`` / ``save_many`` write one slot at a dynamic index: an in-place
  slice update when the index is ONE scalar shared by the whole batch (the
  replay path, ``ops/replay.py``).
- ``save_where`` selects over the whole ring axis and holds no dynamic index:
  the form for sessions batched under ``vmap`` at frames of their own (the
  served pool), where an indexed write would be a scatter that XLA:TPU runs
  as a serial loop over the sessions.  It rewrites all ``R`` slots to change
  one: right while a ring leaf is kilobytes or megabytes.
- ``save_where_batch`` is ``save_where`` for the whole batch at once, and
  chooses per leaf: the select, or ``write_slot``, a kernel that moves the
  one slot a session saves and nothing else, for the leaves the caller holds
  row-major (gigabytes of ring, where the select moved 2.66 GB to change
  0.27).  Asked to, it also takes the digest of what it saves, by the same
  rule: the lane sums out of the kernels that write for a leaf written in
  place, ``lane_sums`` in XLA for a leaf the select writes.

*load* is a dynamic slice (a gather under a per-session frame).

A ring is logical shapes only: in which layout the device holds a leaf
between ticks is chosen where the carry is built, not here
(``parallel/session_pool.py`` ``ring_leaf_layout``: a large, lane-wide leaf
of the served pool is held row-major, so that it crosses the tick program's
boundary without a transposition; DESIGN §3 "The ring's layout at the
program's boundary").
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .checksum import (
    CHECKSUM_LANES,
    _as_u32_words,
    finish_digest,
    lane_sums,
    lane_terms,
    wrap_sum,
)

# The in-place write holds two blocks of one slot in VMEM, each double
# buffered (the state to save, the slot as written): four slots' worth,
# under the 16 MiB a kernel may use on a v5e.
_SLOT_BLOCK_MAX_BYTES = 2 << 20


def writes_slot_in_place(shape: Sequence[int], itemsize: int) -> bool:
    """Whether ``write_slot`` takes a ring leaf ``[B, R, ...]``: one slot has
    to be a block of its own (two trailing dimensions or more: a slot of
    ``[B, R, N]`` is one row of a tile that holds other slots) and to fit
    the kernel's VMEM.  The kernel's own limits; WHERE an in-place write
    pays is the caller's rule (``parallel/session_pool.py``)."""
    return (
        len(shape) >= 4
        and math.prod(shape[2:]) * itemsize <= _SLOT_BLOCK_MAX_BYTES
    )


def write_slot(
    buf: jax.Array,
    leaf: jax.Array,
    slot: jax.Array,
    pred: jax.Array,
    interpret: bool = False,
    digest_offset: Optional[int] = None,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``buf[b, slot[b]] = leaf[b]`` for every session ``b`` whose ``pred[b]``
    is true, and no other byte of ``buf`` read or written.  At least one
    ``pred`` has to be true (``save_where_batch`` asks first).

    ``buf`` is ``[B, R, ...]`` held row-major (so that ``[b, r]`` is one
    contiguous block), ``leaf`` ``[B, ...]``, ``slot`` ``[B]`` int32 in
    ``[0, R)``, ``pred`` ``[B]`` bool.  One grid step a session; the ring is
    operand AND aliased result, and only the result is blocked, at
    ``(b, slot[b])`` read from scalar memory: a session that writes puts its
    state there, whole, so the slot as it was is never read.  A session that
    does not write must move nothing: its step visits the block of the
    nearest session before it that does (of the first that does, for those
    before it) and leaves it as it is, and consecutive steps at one block
    index neither fetch nor write back.  So a write moves state in and slot
    out for the sessions that save, where the select of ``save_where`` reads
    and writes all ``R`` slots of every session.

    **The digest is taken here** when ``digest_offset`` is given: the second
    result is then ``[B, 4]`` u32, row ``b`` the four lane sums
    (``ops/checksum.py`` ``lane_terms``) of the words session ``b`` writes,
    each at its 1-based global index ``digest_offset + 1 + `` its row-major
    place in ``leaf[b]``: what ``lane_sums(words(leaf[b]), digest_offset)``
    gives, read off the block that is in VMEM for the write, so that no
    second pass reads the state again and a session that does not save is
    not digested.  A row whose ``pred`` is false holds nothing meant (the
    caller selects by ``pred``).  ``None``: no digest in the kernel, and
    ``None`` for the second result.

    ``interpret`` runs the same kernel under Pallas's interpreter (off the
    TPU: the program tier-1 runs is the program the chip runs)."""
    sessions = buf.shape[0]
    rest = buf.shape[2:]
    zeros = (0,) * len(rest)
    at = jnp.arange(sessions, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(pred, at, -1))
    visit = jnp.where(last < 0, jnp.argmax(pred).astype(jnp.int32), last)
    digest = digest_offset is not None
    if digest:
        assert buf.dtype.itemsize == 4, buf.dtype  # a word an element
        # each word's index less its place along the block's own axes
        strides = [math.prod(rest[d + 1:]) for d in range(len(rest))]
        first = np.uint32(digest_offset + 1)

    def kernel(visit_ref, slot_ref, pred_ref, leaf_ref, ring_ref, out_ref,
               lanes_ref=None):
        del visit_ref, slot_ref  # read by the block maps
        del ring_ref  # the result's buffer: every block written is written whole
        b = pl.program_id(0)

        @pl.when(pred_ref[b] != 0)
        def _():
            state = leaf_ref[...]
            out_ref[...] = state[:, None]
            if lanes_ref is None:
                return
            words = state[0]
            if words.dtype != jnp.uint32:
                words = jax.lax.bitcast_convert_type(words, jnp.uint32)
            idx = first
            for d, stride in enumerate(strides):
                if rest[d] > 1:
                    idx = idx + jax.lax.broadcasted_iota(
                        jnp.uint32, rest, d
                    ) * np.uint32(stride)
            # (a lane the tile pads, 10,000 words in 79 tiles of 128, is no
            # element of the block: the reduction never sees it)
            for lane, terms in enumerate(lane_terms(words, idx)):
                lanes_ref[CHECKSUM_LANES * b + lane] = wrap_sum(terms)

    ring_shape = jax.ShapeDtypeStruct(buf.shape, buf.dtype)
    ring_spec = pl.BlockSpec(
        (1, 1) + rest,
        lambda b, visit, slot, pred: (visit[b], slot[b]) + zeros,
    )
    # the lanes as one flat vector of scalar memory, written a scalar a time
    lanes_shape = jax.ShapeDtypeStruct(
        (sessions * CHECKSUM_LANES,), jnp.int32
    )
    with jax.named_scope("write_slot"):
        out = pl.pallas_call(
            kernel,
            out_shape=(ring_shape, lanes_shape) if digest else ring_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(sessions,),
                in_specs=[
                    pl.BlockSpec(
                        (1,) + rest,
                        lambda b, visit, slot, pred: (visit[b],) + zeros,
                    ),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=(
                    (ring_spec, pl.BlockSpec(memory_space=pltpu.SMEM))
                    if digest
                    else ring_spec
                ),
            ),
            # operands: visit, slot, pred, leaf, buf -> the ring is the result
            input_output_aliases={4: 0},
            name="ring_write_slot",
            interpret=interpret,
        )(
            visit,
            jnp.asarray(slot, jnp.int32)[visit],
            jnp.asarray(pred, jnp.int32),
            jnp.asarray(leaf, buf.dtype),
            buf,
        )
    if not digest:
        return out, None
    written, lanes = out
    return written, jax.lax.bitcast_convert_type(lanes, jnp.uint32).reshape(
        sessions, CHECKSUM_LANES
    )


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
        That is the wrong trade once B x R x state bytes reaches gigabytes:
        measured there (``particles-2p``), 512 x 10 x 520,028 B = 2.66 GB
        read and written at each of a tick's 3 writes, 30.5 of a 51.6 ms
        tick program (PERF.md section 5, PR 32).  ``save_where_batch`` is
        the way out: per leaf, this select or ``write_slot``."""
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

    def save_where_batch(
        self,
        ring: Any,
        frame: jax.Array,
        state: Any,
        checksum: Optional[jax.Array],
        pred: jax.Array,
        in_place: Any,
        interpret: bool = False,
    ) -> Any:
        """``save_where`` for a whole batch at once: ``ring`` ``[B, R, ...]``,
        ``frame`` and ``pred`` ``[B]``, ``state`` ``[B, ...]``, ``checksum``
        ``[B, 4]``; the same bytes as ``jax.vmap(save_where)``.

        ``in_place`` is shaped like ``ring["states"]`` and says per leaf which
        form writes it: false, the select (every leaf of a small pool; the
        digests and frame tags always: kilobytes); true, ``write_slot``, for
        a leaf the caller holds row-major and ``writes_slot_in_place`` takes.
        It stands at batch level because a kernel whose block maps read the
        sessions' slots has no useful rule under ``vmap``.

        ``checksum`` ``None``: the digest of each saved state is taken here,
        and is ``checksum_device``'s bit for bit.  A leaf written in place is
        digested by the kernel that writes it, in the pass that has the slot
        in VMEM and for the sessions that save only (a write in which none
        saves digests nothing), and the lanes add up: the kernels' partial
        sums, the select's leaves' ``lane_sums`` at their offsets, then
        ``finish_digest``.  The same rule as the write, per leaf, from
        ``in_place`` (docs/DESIGN.md §3 "Per-session slots"); with no leaf
        written in place every lane is XLA's, and the caller may as well
        hand in ``jax.vmap(checksum_device)``'s digests, as the pool does."""
        bufs, tree = jax.tree_util.tree_flatten(ring["states"])
        per_state = tree.flatten_up_to(state)
        # (the caller may hold a slot with unit axes the state has not)
        leaves = [
            leaf.reshape(buf.shape[:1] + buf.shape[2:])
            for leaf, buf in zip(per_state, bufs)
        ]
        direct = tree.flatten_up_to(in_place)
        by_select = [i for i, d in enumerate(direct) if not d]
        by_kernel = [i for i, d in enumerate(direct) if d]
        digest_here = checksum is None
        # where each leaf's words start among one state's, as
        # checksum_device counts them
        one_state = [
            jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
            for leaf in per_state
        ]
        offsets = np.cumsum(
            [0] + [(l.size * l.dtype.itemsize + 3) // 4 for l in one_state]
        ).tolist()
        lanes = jnp.zeros(pred.shape + (CHECKSUM_LANES,), jnp.uint32)
        if by_kernel:
            # an idle row's frame of -1 matches no slot: the index the block
            # map reads is clamped, and the predicate says nothing is written
            ok = pred & (frame >= 0)
            slot = jnp.where(ok, self.slot(frame), 0)

            def written(before, lanes):
                after, partial = zip(*(
                    write_slot(
                        buf, leaves[i], slot, ok, interpret,
                        offsets[i] if digest_here else None,
                    )
                    for i, buf in zip(by_kernel, before)
                ))
                return list(after), sum(partial) if digest_here else lanes

            # a write in which no session saves (the second step of a burst
            # whose sessions rolled back one frame) runs no kernel: the ring
            # passes through the conditional uncopied
            after, lanes = jax.lax.cond(
                jnp.any(ok),
                written,
                lambda before, lanes: (before, lanes),
                [bufs[i] for i in by_kernel],
                lanes,
            )
            for i, buf in zip(by_kernel, after):
                bufs[i] = buf
        if digest_here:
            with jax.named_scope("digest"):
                for i in by_select:
                    lanes = lanes + jax.vmap(
                        lambda l, at=offsets[i]: lane_sums(
                            _as_u32_words(l), at
                        )
                    )(leaves[i])
                checksum = finish_digest(one_state, lanes)
        out = jax.vmap(self.save_where)(
            {**ring, "states": [bufs[i] for i in by_select]},
            frame,
            [leaves[i] for i in by_select],
            checksum,
            pred,
        )
        for i, buf in zip(by_select, out["states"]):
            bufs[i] = buf
        return {**out, "states": tree.unflatten(bufs)}

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
