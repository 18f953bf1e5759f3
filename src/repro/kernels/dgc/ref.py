"""Pure-jnp oracle for the DGC Pallas kernels (same bin semantics)."""
from __future__ import annotations

import jax.numpy as jnp


def update_max_ref(u, v, g, sigma):
    u_new = sigma * u + g
    v_new = v + u_new
    return u_new, v_new, jnp.max(jnp.abs(v_new))


def tail_hist_ref(v, edges):
    a = jnp.abs(v).reshape(-1)
    return jnp.sum((a[None, :] >= edges[:, None]).astype(jnp.int32), axis=1)


def pick_threshold(counts, edges, k):
    """Largest edge whose tail count >= k (guarantees >= k kept)."""
    ok = counts >= k
    idx = jnp.maximum(jnp.sum(ok.astype(jnp.int32)) - 1, 0)
    return edges[idx]


def apply_mask_ref(u, v, th):
    mask = (jnp.abs(v) >= th).astype(v.dtype)
    return v * mask, u * (1.0 - mask), v * (1.0 - mask)


def dgc_step_ref(u, v, g, sigma, phi, bins=64):
    """Full reference pipeline matching ops.dgc_step_pallas."""
    from repro.core.sparsify import keep_count

    u2, v2, hi = update_max_ref(u, v, g, sigma)
    edges = jnp.linspace(0.0, 1.0, bins + 1)[:-1] * hi
    edges = jnp.maximum(edges, jnp.finfo(jnp.float32).tiny)
    counts = tail_hist_ref(v2, edges)
    th = pick_threshold(counts, edges, keep_count(v.size, phi))
    return apply_mask_ref(u2, v2, th)
