"""Pallas TPU kernels for DGC sparsification (paper §IV / Alg. 4 l.6-12).

TPU adaptation of DGC's GPU radix-select: a dense three-pass scheme that the
VPU executes on (8,128)-aligned tiles streaming HBM->VMEM once per pass:

  1. ``update_max``   : u' = σ·u + g ; v' = v + u' ; per-block max|v'|
  2. ``tail_hist``    : counts[b] = #{ |v'| >= edge_b · hi }   (accumulated
                        across the sequential TPU grid)
  3. ``apply_mask``   : ĝ = v'·[|v'| >= th] ; u'' = u'·¬mask ; v'' = v'·¬mask

The threshold pick between passes 2 and 3 is O(bins) glue in jnp. Every
``pallas_call`` wrapper takes ``interpret`` as a required keyword; the ops
layer derives it from the platform (``repro.kernels.interpret_mode``).
Per-block scalars leave the kernels as whole (8,128) tiles or through
SMEM, the layouts the TPU compiler accepts for them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 256  # (256, 1024) f32 tile = 1 MB per operand
BLOCK_COLS = 8 * LANES  # 1024


def _grid(rows):
    return (rows // BLOCK_ROWS,)


# ---------------------------------------------------------------------------
# Pass 1: fused momentum-correction update + block max
# ---------------------------------------------------------------------------


def _update_max_kernel(sigma_ref, u_ref, v_ref, g_ref, u_out, v_out, max_out):
    sigma = sigma_ref[0, 0]
    u_new = sigma * u_ref[...] + g_ref[...]
    v_new = v_ref[...] + u_new
    u_out[...] = u_new
    v_out[...] = v_new
    max_out[...] = jnp.full((SUBLANES, LANES), jnp.max(jnp.abs(v_new)))


def update_max(u, v, g, sigma, *, interpret):
    """u,v,g [R, BLOCK_COLS] f32 -> (u', v', block_max [R/BR*8, 128]): each
    block's max|v'| fills one (8,128) tile of the last output."""
    R = u.shape[0]
    nb = R // BLOCK_ROWS
    sig = jnp.full((1, 1), sigma, jnp.float32)
    blk = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    return pl.pallas_call(
        _update_max_kernel,
        grid=_grid(R),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), blk, blk, blk],
        out_specs=[blk, blk, pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
            jax.ShapeDtypeStruct((nb * SUBLANES, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(sig, u, v, g)


# ---------------------------------------------------------------------------
# Pass 2: tail-count histogram (counts of |v| >= edge)
# ---------------------------------------------------------------------------


def _hist_kernel(edges_ref, v_ref, counts_ref, *, bins):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for b in range(bins):
            counts_ref[b] = 0

    a = jnp.abs(v_ref[...])  # [BR, BC]

    # one edge at a time: a [bins, BR, BC] broadcast would not fit VMEM
    def count_edge(b, carry):
        counts_ref[b] += jnp.sum((a >= edges_ref[b]).astype(jnp.int32))
        return carry

    jax.lax.fori_loop(0, bins, count_edge, 0)


def tail_hist(v, edges, *, interpret):
    """v [R, BLOCK_COLS]; edges [bins] -> counts [bins] (int32), accumulated
    in SMEM across the sequential grid."""
    R = v.shape[0]
    bins = edges.shape[0]
    blk = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_hist_kernel, bins=bins),
        grid=_grid(R),
        in_specs=[smem, blk],
        out_specs=smem,
        out_shape=jax.ShapeDtypeStruct((bins,), jnp.int32),
        interpret=interpret,
    )(edges.astype(jnp.float32), v)


# ---------------------------------------------------------------------------
# Pass 3: masked apply (inverted sparsification of u and v)
# ---------------------------------------------------------------------------


def _apply_kernel(th_ref, u_ref, v_ref, ghat_out, u_out, v_out):
    th = th_ref[0, 0]
    v = v_ref[...]
    mask = (jnp.abs(v) >= th).astype(jnp.float32)
    ghat_out[...] = v * mask
    keep = 1.0 - mask
    u_out[...] = u_ref[...] * keep
    v_out[...] = v * keep


def apply_mask(u, v, th, *, interpret):
    """-> (ghat, u'', v'') all [R, BLOCK_COLS] f32."""
    R = u.shape[0]
    thr = jnp.asarray(th, jnp.float32).reshape(1, 1)
    blk = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    return pl.pallas_call(
        _apply_kernel,
        grid=_grid(R),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32)] * 3,
        interpret=interpret,
    )(thr, u, v)
