"""Jit'd wrappers around the DGC Pallas kernels.

Handles padding/reshaping of arbitrary flat vectors into the kernels'
(rows, 1024) tiled layout and the threshold selection glue. Whether the
kernels run compiled or interpreted follows the platform
(``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsify import keep_count
from repro.kernels import interpret_mode
from repro.kernels.dgc import kernel as K
from repro.kernels.dgc import ref

_BLOCK_ELEMS = K.BLOCK_ROWS * K.BLOCK_COLS


def _to_tiles(x):
    n = x.size
    pad = (-n) % _BLOCK_ELEMS
    xf = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad))
    return xf.reshape(-1, K.BLOCK_COLS), n, pad


def _from_tiles(t, n, shape, dtype):
    return t.reshape(-1)[:n].reshape(shape).astype(dtype)


def _threshold(vt, hi, n: int, phi: float, bins: int, interpret: bool):
    """|vt| threshold keeping >= keep_count(n, φ) entries, from ``hi`` =
    max|vt| and the ``tail_hist`` counts."""
    edges = jnp.linspace(0.0, 1.0, bins + 1)[:-1] * hi
    edges = jnp.maximum(edges, jnp.finfo(jnp.float32).tiny)
    counts = K.tail_hist(vt, edges, interpret=interpret)
    th = ref.pick_threshold(counts, edges, keep_count(n, phi))
    # All-zero v: the tiny-floored edges collapse to a threshold that keeps
    # NOTHING. th=0 keeps everything instead (all zeros — semantically a
    # no-op) and preserves the documented ">= k sent" guarantee.
    return jnp.where(hi > 0.0, th, 0.0)


@partial(jax.jit, static_argnames=("sigma", "phi", "bins"))
def dgc_step_pallas(u, v, g, sigma: float, phi: float, *, bins: int = 64):
    """Alg. 4 lines 6-12 via the three Pallas passes. Same contract as
    ``repro.core.sparsify.dgc_step`` with impl='hist'."""
    interpret = interpret_mode()
    shape, dtype = v.shape, v.dtype
    ut, n, _ = _to_tiles(u)
    vt, _, _ = _to_tiles(v)
    gt, _, _ = _to_tiles(g)
    u2, v2, bmax = K.update_max(ut, vt, gt, sigma, interpret=interpret)
    th = _threshold(v2, jnp.max(bmax), n, phi, bins, interpret)
    ghat, u3, v3 = K.apply_mask(u2, v2, th, interpret=interpret)
    return (
        _from_tiles(ghat, n, shape, dtype),
        _from_tiles(u3, n, shape, dtype),
        _from_tiles(v3, n, shape, dtype),
    )


def _tiles_threshold(x, phi: float, bins: int, interpret: bool):
    """-> (x as tiles, n, threshold) for a selection without momentum. The
    maximum is one XLA reduction: an ``update_max`` pass with zero momentum
    would hold three more copies of ``x`` (zeros, u', v') at once."""
    xt, n, _ = _to_tiles(x)
    return xt, n, _threshold(xt, jnp.max(jnp.abs(xt)), n, phi, bins, interpret)


@partial(jax.jit, static_argnames=("phi", "bins"))
def omega_pallas(x, phi: float, *, bins: int = 64):
    """Ω(V, φ) via hist-threshold Pallas passes. Returns (sparse, mask)."""
    interpret = interpret_mode()
    shape, dtype = x.shape, x.dtype
    xt, n, th = _tiles_threshold(x, phi, bins, interpret)
    ghat, _, _ = K.apply_mask(jnp.zeros_like(xt), xt, th, interpret=interpret)
    sparse = _from_tiles(ghat, n, shape, dtype)
    return sparse, (jnp.abs(x) >= th).reshape(shape)


@partial(jax.jit, static_argnames=("phi", "bins"))
def threshold_pallas(x, phi: float, *, bins: int = 64):
    """|x| threshold keeping >= keep_count(n, φ) entries, via the Pallas
    hist pass (tail_hist); selection glue for the flat-buffer sync's
    ``sparsify.pack_phi(impl="pallas")``. Returns a scalar f32 threshold
    (0.0 on an all-zero input, i.e. keep-everything)."""
    return _tiles_threshold(x, phi, bins, interpret_mode())[2]
