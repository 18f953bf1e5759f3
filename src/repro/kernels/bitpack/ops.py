"""Jit'd wrappers around the bitpack Pallas kernel.

Handles padding of arbitrary flat masks into the (rows, 1024) tiled layout,
byte extraction, and the value-stream compaction that rides the kernel's
per-block popcounts. Whether the kernel runs compiled or interpreted
follows the platform (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.bitpack import kernel as K

_BLOCK_ELEMS = K.BLOCK_ROWS * K.BLOCK_COLS


def _to_tiles(x):
    n = x.size
    pad = (-n) % _BLOCK_ELEMS
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad))
    return xf.reshape(-1, K.BLOCK_COLS), n


@jax.jit
def _bitpack_flat(mask):
    """-> (byte vector, total popcount)."""
    tiles, n = _to_tiles(mask)
    byte_mat, counts = K.bitpack(tiles, interpret=interpret_mode())
    return byte_mat.reshape(-1), jnp.sum(counts[::K.SUBLANES, 0])


def bitpack_bytes(mask) -> bytes:
    """Flat mask (nonzero = set bit) -> the bitmap byte stream, identical to
    ``ref.bitpack_ref`` / ``np.packbits(bitorder="little")``."""
    mask = jnp.asarray(mask)
    n = mask.size
    byte_vec, _ = _bitpack_flat(mask)
    nb = (n + 7) // 8
    return np.asarray(byte_vec[:nb], np.uint8).tobytes()


def bitmap_payload(x):
    """Dense flat vector -> (bitmap bytes, set-entry values in index order).

    The kernel packs the presence bits and counts them per block; the value
    compaction is the same O(Q) cumsum+scatter used by
    ``core.sparsify.compact_mask``, sized by the popcount total.
    """
    x = jnp.asarray(x).reshape(-1).astype(jnp.float32)
    mask = x != 0.0
    byte_vec, count = _bitpack_flat(mask)
    n = x.size
    k = int(count)
    packed = np.asarray(byte_vec[: (n + 7) // 8], np.uint8).tobytes()
    if k == 0:
        return packed, np.zeros(0, np.float32)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask, pos, k)  # k == out-of-bounds -> dropped
    vals = jnp.zeros((k,), jnp.float32).at[tgt].set(x, mode="drop")
    return packed, np.asarray(vals)
