"""Plain reference of the digest the device keeps of every saved state
(``ggrs_tpu/ops/checksum.py`` ``checksum_device``): four u32 lanes over the
state's 32-bit words in the order of its sorted keys, composed into the u128
the wire carries.  A NumPy copy of the arithmetic, kept here so that a tick
program that drops or alters the digest is seen.  Imports nothing of the
program.  Every leaf of today's families is int32; any other width is an
error here, not a guess.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

_MASK = 0xFFFFFFFF
_GOLDEN = 2654435761
_PRIME_A = 40503
_PRIME_B = 2246822519


def _salt(leaves) -> np.ndarray:
    """Lanes mixed from the state's static structure: leaf count, and each
    leaf's word count, dtype kind and item size."""
    acc = len(leaves) & _MASK
    for leaf in leaves:
        acc = (acc * _GOLDEN + leaf.size) & _MASK
        acc ^= acc >> 15
        acc = (acc * _PRIME_B + ord(leaf.dtype.kind) * 256 + 4) & _MASK
    lanes = []
    for i in range(4):
        acc = (acc * _GOLDEN + i + 1) & _MASK
        acc ^= acc >> 13
        lanes.append(acc)
    return np.asarray(lanes, np.uint64)


def u128(state: Dict[str, np.ndarray]) -> int:
    """The digest of one session's state as the program's
    ``ring_checksum`` reports it."""
    leaves = [np.asarray(state[k]) for k in sorted(state)]
    if any(leaf.dtype.itemsize != 4 for leaf in leaves):
        raise TypeError("the reference digest covers 32-bit leaves only")
    m = np.uint64(_MASK)
    words = np.concatenate(
        [leaf.reshape(-1).view(np.uint32) for leaf in leaves]
    ).astype(np.uint64)
    idx = np.arange(1, len(words) + 1, dtype=np.uint64)
    rot = ((words << np.uint64(13)) | (words >> np.uint64(19))) & m
    # u64 sums wrap modulo 2**64, which keeps them right modulo 2**32
    lanes = np.asarray([
        words.sum(),
        (words * idx).sum(),
        (words * ((idx * np.uint64(_PRIME_A) + np.uint64(1)) & m)).sum(),
        (rot ^ ((idx * np.uint64(_PRIME_B)) & m)).sum(),
    ], np.uint64) & m
    acc = (_salt(leaves) * np.uint64(_GOLDEN) + lanes) & m
    acc ^= acc >> np.uint64(15)
    return sum(int(lane) << (32 * i) for i, lane in enumerate(acc))
