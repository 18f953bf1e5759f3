"""Pallas TPU kernel packing a presence mask into bitmap bytes.

The bitmap codec (``repro.comm.codecs.BitmapCodec``) serializes a sparse
payload as a Q-bit presence bitmap followed by the set-bit values. The
bit-pack is a streaming op — one HBM->VMEM pass over the mask per
(8,128)-aligned tile — so it rides the same dense tiling scheme as the DGC
kernels in ``repro.kernels.dgc``:

  * ``bitpack`` : mask [R, 1024] -> bytes [R, 128] int32 (each 0..255,
                  LSB-first within a byte, matching
                  ``np.packbits(bitorder="little")``) + per-block popcounts
                  (the compaction offsets of the value stream).

``interpret`` is a required keyword; the ops layer derives it from the
platform (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 256  # (256, 1024) f32 tile = 1 MB per operand
BLOCK_COLS = 8 * LANES  # 1024


def _grid(rows):
    return (rows // BLOCK_ROWS,)


def _bitpack_kernel(m_ref, bytes_out, count_out):
    m = (m_ref[...] != 0.0).astype(jnp.bfloat16)  # [BR, 1024]
    # byte j of a row covers lanes j*8 .. j*8+7, LSB-first: lane j*8+b
    # contributes bit b. That lane-to-byte gather is one matmul with the
    # [1024, 128] weight holding 2^b at (j*8+b, j): 0/1 times powers of two
    # is exact in bf16 and sums exactly in f32, and the MXU needs no strided
    # lane access (which Mosaic does not lower).
    r = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_COLS, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_COLS, LANES), 1)
    w = jnp.where((r >> 3) == c, jnp.left_shift(1, r & 7), 0)
    packed = jnp.dot(m, w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    bytes_out[...] = packed.astype(jnp.int32)
    count = jnp.sum(m.astype(jnp.float32)).astype(jnp.int32)
    count_out[...] = jnp.full((SUBLANES, LANES), count)


def bitpack(mask, *, interpret):
    """mask [R, BLOCK_COLS] (any dtype; nonzero = set) ->
    (bytes [R, LANES] int32 in 0..255, per-block popcounts
    [R/BR*8, LANES]: each block's count fills one (8,128) tile)."""
    R = mask.shape[0]
    nb = R // BLOCK_ROWS
    blk = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    return pl.pallas_call(
        _bitpack_kernel,
        grid=_grid(R),
        in_specs=[blk],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nb * SUBLANES, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(mask)
