"""Pallas digest kernel vs the XLA lane formulas — bitwise equality.

The kernel must reproduce ``checksum._leaf_digest``'s four lanes exactly
(same mod-2^32 arithmetic, same 1-based index weights) or every desync gate
built on checksum equality would silently compare different functions.  On
CPU the kernel runs in interpreter mode; the TPU path compiles the same
program."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ggrs_tpu.ops.checksum import _as_u32_words, _leaf_digest, checksum_device
from ggrs_tpu.ops import pallas_checksum as pc


def _xla_lanes(words: jnp.ndarray) -> np.ndarray:
    """The four lanes exactly as checksum._leaf_digest computes them."""
    n = words.shape[0]
    idx = jnp.arange(1, n + 1, dtype=jnp.uint32)
    lane0 = jnp.sum(words, dtype=jnp.uint32)
    lane1 = jnp.sum(words * idx, dtype=jnp.uint32)
    lane2 = jnp.sum(words * (idx * np.uint32(40503) + jnp.uint32(1)), dtype=jnp.uint32)
    rot = (words << jnp.uint32(13)) | (words >> jnp.uint32(19))
    lane3 = jnp.sum(rot ^ (idx * np.uint32(2246822519)), dtype=jnp.uint32)
    return np.asarray(jnp.stack([lane0, lane1, lane2, lane3]))


@pytest.mark.parametrize(
    "n",
    [
        1,
        100,
        pc._LANES,                      # exactly one row
        pc._BLOCK_ROWS * pc._LANES,     # exactly one block
        pc._BLOCK_ROWS * pc._LANES + 1,  # one word into the second block
        3 * pc._BLOCK_ROWS * pc._LANES - 7,  # multi-block, ragged tail
    ],
)
def test_kernel_matches_xla_lanes(n):
    words = jnp.asarray(
        np.random.default_rng(n).integers(0, 2**32, size=(n,), dtype=np.uint32)
    )
    got = np.asarray(pc.leaf_digest_pallas(words, interpret=True))
    np.testing.assert_array_equal(got, _xla_lanes(words))


def test_ragged_tail_folds_at_correct_offset():
    # all-zero words: lanes 0-2 are 0, lane3 is sum(idx*B) — index-dependent,
    # so a tail folded at the wrong global offset (or dropped) would differ
    for n in (
        pc._BLOCK_ROWS * pc._LANES // 2 + 3,   # below one block: pure XLA path
        2 * pc._BLOCK_ROWS * pc._LANES + 17,   # kernel head + ragged tail
    ):
        words = jnp.zeros((n,), jnp.uint32)
        got = np.asarray(pc.leaf_digest_pallas(words, interpret=True))
        np.testing.assert_array_equal(got, _xla_lanes(words))


def test_leaf_digest_routing_unchanged_when_disabled(monkeypatch):
    # default-off policy: _leaf_digest must not engage pallas unless enabled
    # AND on TPU AND the leaf is large enough
    big = jnp.asarray(
        np.random.default_rng(0).integers(
            0, 2**31, size=(pc.MIN_PALLAS_WORDS + 5,), dtype=np.int32
        )
    )
    base = np.asarray(_leaf_digest(big))
    pc.use_pallas_checksums(True)
    try:
        # on CPU the backend gate keeps the XLA path — results identical
        np.testing.assert_array_equal(np.asarray(_leaf_digest(big)), base)
    finally:
        pc.use_pallas_checksums(None)


def test_words_view_of_mixed_dtypes_roundtrip():
    # the pallas path consumes the same _as_u32_words stream as XLA; a mixed
    # pytree digest must be invariant to which implementation digests leaves
    state = {
        "a": jnp.asarray(np.arange(300, dtype=np.float32)),
        "b": jnp.asarray(np.arange(77, dtype=np.uint8)),
    }
    lanes = checksum_device(state)
    assert lanes.shape == (4,)
    for leaf in jax.tree_util.tree_leaves(state):
        w = _as_u32_words(jnp.asarray(leaf))
        got = np.asarray(pc.leaf_digest_pallas(w, interpret=True))
        np.testing.assert_array_equal(got, _xla_lanes(w))
