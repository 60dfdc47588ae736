"""Pallas TPU kernel for the 4-lane state digest.

``ops.checksum`` digests every saved frame (the per-save hot op of the whole
framework: one digest per SaveGameState, /root/reference's analog being the
user-side fletcher16 over serialized bytes,
/root/reference/examples/ex_game/ex_game.rs:45-55).  The XLA implementation
(`checksum._leaf_digest`) expresses the four lanes as four separate
reductions; whether they fuse into one pass over the words is up to the
compiler.  This kernel guarantees it: one grid sweep over (block, 128)-tiled
u32 words computes all four lanes per block on the VPU and accumulates them
in SMEM, so the block-aligned prefix of a leaf is digested in exactly one
read of HBM (a ragged tail of < one block folds in via the XLA formulas —
no padding copy of the leaf).

Bit-for-bit identical to the XLA path by construction: the same per-word
formulas in the same mod-2^32 integer arithmetic — every lane is a
commutative sum of per-word terms, so block order cannot change the result.
``tests/test_pallas_checksum.py`` asserts equality on the interpreter
(CPU; ``interpret=True`` is a test-only argument) and ``chip_smoke.py``'s
``pallas`` leg asserts it compiled by Mosaic on the chip, on a 256 MiB leaf
and a ragged one.

Enablement: ``leaf_digest_pallas`` is opt-in via ``use_pallas_checksums`` /
the ``GGRS_TPU_PALLAS_CHECKSUM`` env var ("on"/"off", default off) and only
engages on the TPU backend for leaves of at least ``MIN_PALLAS_WORDS`` words
— below that, kernel launch overhead exceeds the whole digest.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the per-word lane terms are ops.checksum's own, so the kernel's and the XLA
# formulas can never drift apart
from .checksum import lane_sums, lane_terms, wrap_sum

# (sublanes, lanes) per grid step: 256×128 u32 = 128 KiB of VMEM per block,
# comfortably inside the ~16 MiB VMEM budget with room for double-buffering
_BLOCK_ROWS = 256
_LANES = 128
MIN_PALLAS_WORDS = 1 << 15  # below ~32k words the launch overhead dominates


def _digest_kernel(w_ref, out_ref):
    """One (BLOCK_ROWS, 128) tile of a block-ALIGNED word stream: per-word
    lane terms accumulated into the (4,) SMEM output across sequential grid
    steps (the caller folds any ragged tail in separately)."""
    i = pl.program_id(0)
    w = w_ref[...]
    # cast the int32 program id BEFORE multiplying: int32 × uint32 promotes
    # to int64 under jax_enable_x64, and an int64 intermediate may fail to
    # lower in Mosaic on real TPU
    base = jnp.uint32(i) * np.uint32(_BLOCK_ROWS * _LANES)
    row = jax.lax.broadcasted_iota(jnp.uint32, w.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, w.shape, 1)
    # 1-based global word index, as in checksum._leaf_digest
    idx = base + row * np.uint32(_LANES) + col + np.uint32(1)

    lane0, lane1, lane2, lane3 = (wrap_sum(t) for t in lane_terms(w, idx))

    @pl.when(i == 0)
    def _init():
        out_ref[0] = lane0
        out_ref[1] = lane1
        out_ref[2] = lane2
        out_ref[3] = lane3

    @pl.when(i != 0)
    def _acc():
        out_ref[0] += lane0
        out_ref[1] += lane1
        out_ref[2] += lane2
        out_ref[3] += lane3


def leaf_digest_pallas(words: jax.Array, interpret: bool = False) -> jax.Array:
    """4-lane digest of a 1-D u32 word vector — one pallas pass.

    Same contract as the four-lane block of ``checksum._leaf_digest`` after
    ``_as_u32_words``.  The kernel sweeps the block-aligned prefix (no
    padding copy of the leaf — the whole point is a single HBM read); a
    ragged tail (< one block) is folded in with the XLA lane formulas at the
    right index offset, which is exact because every lane is a commutative
    mod-2^32 sum.
    """
    n = words.shape[0]
    per_block = _BLOCK_ROWS * _LANES
    blocks = n // per_block
    if blocks == 0:
        return lane_sums(words)
    n_aligned = blocks * per_block
    tiled = words[:n_aligned].reshape(blocks * _BLOCK_ROWS, _LANES)
    acc = pl.pallas_call(
        _digest_kernel,
        out_shape=jax.ShapeDtypeStruct((4,), jnp.int32),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec(
                (_BLOCK_ROWS, _LANES),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(tiled)
    lanes = jax.lax.bitcast_convert_type(acc, jnp.uint32)
    if n != n_aligned:
        lanes = lanes + lane_sums(words[n_aligned:], n_aligned)
    return lanes


# ---------------------------------------------------------------------------
# enablement policy
# ---------------------------------------------------------------------------

_override: Optional[bool] = None


def use_pallas_checksums(enable: Optional[bool]) -> None:
    """Force the pallas digest on/off (None = fall back to the
    ``GGRS_TPU_PALLAS_CHECKSUM`` env var, default off).  Takes effect for
    programs traced afterwards; already-jitted programs keep whatever path
    they compiled with."""
    global _override
    _override = enable


def pallas_enabled() -> bool:
    if _override is not None:
        return _override
    return os.environ.get("GGRS_TPU_PALLAS_CHECKSUM", "off").lower() in (
        "on",
        "1",
        "true",
    )


def maybe_pallas_digest(words: jax.Array) -> Optional[jax.Array]:
    """The digest via pallas when enabled, on TPU, and the leaf is large
    enough to amortize the launch; ``None`` otherwise (caller uses XLA)."""
    if (
        pallas_enabled()
        and words.shape[0] >= MIN_PALLAS_WORDS
        and jax.default_backend() == "tpu"
    ):
        return leaf_digest_pallas(words)
    return None
