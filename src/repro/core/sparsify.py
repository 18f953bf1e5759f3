"""Gradient/model-difference sparsification (paper §IV; DGC, Lin et al. 2018).

``Ω(V, φ)`` keeps the top ``(1-φ)`` fraction of entries by magnitude and
zeroes the rest. Two selection implementations:

  * ``topk``  -- exact ``lax.top_k`` (reference; used in tests and small runs)
  * ``hist``  -- histogram threshold estimation (TPU adaptation of DGC's
                 sampled radix-select; the Pallas kernel in
                 ``repro.kernels.dgc`` implements the same two-pass scheme)
  * ``fused`` -- exact top-k via threshold select + compaction + a small
                 finisher top-k (``repro.kernels.fused_sync``):
                 bit-identical selection to ``topk`` without the
                 whole-vector TopK sort

All functions operate on a single array (a leaf or a flat vector); pytree
orchestration lives in ``repro.core.hfl``.

The exchange-payload helpers put their ops under the sync's named scopes,
which the profiler's trace carries as metadata: ``sync.select`` (choosing
Ω), ``sync.compact`` (gathering the kept values and indices into the
payload) and ``sync.merge`` (scattering a payload back to dense).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def keep_count(size: int, phi: float) -> int:
    """Number of entries transmitted for sparsity parameter φ."""
    return max(1, int(round((1.0 - phi) * size)))


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


def topk_mask(x, k: int):
    """Boolean mask of the k largest-|x| entries. x any shape."""
    flat = jnp.abs(x).reshape(-1)
    _, idx = jax.lax.top_k(flat, k)
    mask = jnp.zeros(flat.shape, bool).at[idx].set(True)
    return mask.reshape(x.shape)


def threshold_for_phi(x, phi: float, *, bins: int = 64):
    """Histogram estimate of the |x| threshold keeping ~(1-φ) of entries.

    Linear bins over [0, max|x|]; picks the smallest bin edge whose
    right-tail count is <= k. Guaranteed to keep AT LEAST k entries
    (threshold rounds down), mirroring DGC's sampled threshold.
    """
    a = jnp.abs(x).reshape(-1).astype(jnp.float32)
    k = keep_count(a.size, phi)
    hi = jnp.max(a)
    edges = jnp.linspace(0.0, 1.0, bins + 1)[:-1]  # bin lower edges (scaled)
    # one-pass tail counts: sort once, then #(a >= e) = Q - #(a < e) via a
    # single searchsorted over all edges. Scatter-free and O(Q log Q),
    # vs the old O(bins*Q) broadcast-compare that materialised a [bins, Q]
    # boolean (the Pallas `tail_hist` kernel is the TPU analogue).
    a_sorted = jnp.sort(a)
    counts = a.size - jnp.searchsorted(a_sorted, edges * hi, side="left")
    # counts is decreasing in edge; find largest edge with count >= k
    ok = counts >= k
    idx = jnp.sum(ok.astype(jnp.int32)) - 1
    return edges[jnp.maximum(idx, 0)] * hi


def mask_at_least_k(x, th, k: int):
    """Mask of ``|x| >= max(th, tiny)``, padded to honour the ">= k kept"
    contract when fewer entries survive the floor.

    The tiny floor exists so exact zeros are never "selected" by a zero
    threshold — but on an all-zero (or fewer-than-k-nonzeros) input it
    would keep fewer than k entries, silently under-filling downstream
    fixed-size payloads. Padding with the first positions is semantically
    exact: the padded entries are (near-)zero, so sending them is a no-op.
    """
    a = jnp.abs(x)
    base = a >= jnp.maximum(th, jnp.finfo(jnp.float32).tiny)
    first_k = (jnp.arange(a.size).reshape(a.shape) < k)
    return jnp.where(jnp.sum(base) >= k, base, base | first_k)


def threshold_mask(x, phi: float, *, bins: int = 64):
    th = threshold_for_phi(x, phi, bins=bins)
    return mask_at_least_k(x, th, keep_count(x.size, phi))


def omega(v, phi: float, *, impl: str = "topk"):
    """Ω(V, φ): sparse form of v. Returns (sparse_v, mask)."""
    if phi <= 0.0:
        return v, jnp.ones(v.shape, bool)
    if impl == "topk":
        mask = topk_mask(v, keep_count(v.size, phi))
    elif impl == "hist":
        mask = threshold_mask(v, phi)
    elif impl == "pallas":
        from repro.kernels.dgc import ops as _k

        return _k.omega_pallas(v, phi)
    elif impl == "fused":
        from repro.kernels.fused_sync import ops as _f

        vals, idx = _f.fused_pack_phi(v, phi)
        flat_mask = jnp.zeros((v.size,), bool).at[idx].set(True)
        mask = flat_mask.reshape(v.shape)
        return v * mask.astype(v.dtype), mask
    else:
        raise ValueError(impl)
    return v * mask.astype(v.dtype), mask


# ---------------------------------------------------------------------------
# DGC step (Alg. 4 lines 6-12): momentum correction + error feedback
# ---------------------------------------------------------------------------


def dgc_step(u, v, g, sigma: float, phi: float, *, impl: str = "topk"):
    """One MU-side sparse-momentum step.

        u <- σ·u + g              (momentum correction)
        v <- v + u                (error accumulation)
        ĝ  = v ⊙ mask             (transmitted)
        u <- u ⊙ ¬mask            (momentum-factor masking)
        v <- v ⊙ ¬mask

    Returns (ĝ, u', v').
    """
    u = sigma * u + g
    v = v + u
    ghat, mask = omega(v, phi, impl=impl)
    keep = (~mask).astype(v.dtype)
    return ghat, u * keep, v * keep


# ---------------------------------------------------------------------------
# Sparse exchange payloads (top-k values + indices)
# ---------------------------------------------------------------------------


def pack_topk(x, k: int):
    """-> (values [k], indices [k] int32) of the k largest-|x| entries."""
    flat = x.reshape(-1)
    with jax.named_scope("sync.select"):
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
    with jax.named_scope("sync.compact"):
        return flat[idx], idx.astype(jnp.int32)


def unpack_topk(values, indices, size: int, shape=None):
    with jax.named_scope("sync.merge"):
        out = jnp.zeros((size,), values.dtype).at[indices].add(values)
        return out.reshape(shape) if shape is not None else out


def compact_mask(x, mask, k: int):
    """Compact the masked entries of ``x`` into a fixed-size (values [k],
    indices [k] int32) payload without a top-k.

    One cumsum + two scatters, O(Q): the fixed-size compaction used when
    selection came from a *threshold* (hist/pallas impls) rather than an
    exact top-k. If the mask keeps more than k entries the surplus is
    truncated in index order (the hist threshold guarantees >= k, and the
    overshoot is at most one bin's worth); if fewer, the spare slots hold
    (value 0, index 0), which scatter-add treats as a no-op.
    """
    with jax.named_scope("sync.compact"):
        flat = x.reshape(-1)
        m = mask.reshape(-1)
        pos = jnp.cumsum(m.astype(jnp.int32)) - 1
        tgt = jnp.where(m & (pos < k), pos, k)  # k == out-of-bounds -> dropped
        iota = jnp.arange(flat.size, dtype=jnp.int32)
        idx = jnp.zeros((k,), jnp.int32).at[tgt].set(iota, mode="drop")
        vals = jnp.zeros((k,), flat.dtype).at[tgt].set(flat, mode="drop")
        return vals, idx


def pack_phi(x, phi: float, *, impl: str = "topk", bins: int = 64):
    """Fixed-size sparse payload of Ω(x, φ): (values [k], indices [k]).

    The exchange-side counterpart of ``omega``: k = keep_count(Q, φ) is
    static, so the payload can ride a fixed-shape all-gather. ``impl``:

      * ``topk``   -- exact ``lax.top_k`` (reference)
      * ``hist``   -- jnp histogram threshold + O(Q) compaction
      * ``pallas`` -- threshold from the Pallas DGC hist kernels
                      (``repro.kernels.dgc``) + O(Q) compaction
      * ``fused``  -- threshold select + compaction + finisher
                      (``repro.kernels.fused_sync``): selection
                      bit-identical to ``topk`` without its full sort

    The fused path selects and gathers its payload in one pass, all of it
    charged to ``sync.select``.
    """
    flat = x.reshape(-1)
    k = keep_count(flat.size, phi)
    if impl == "topk":
        return pack_topk(flat, k)
    with jax.named_scope("sync.select"):
        if impl == "fused":
            from repro.kernels.fused_sync import ops as _f

            return _f.fused_pack_phi(flat, phi, bins=bins)
        if impl == "hist":
            mask = threshold_mask(flat, phi, bins=bins)
        elif impl == "pallas":
            from repro.kernels.dgc import ops as _k

            th = _k.threshold_pallas(flat, phi, bins=bins)
            mask = mask_at_least_k(flat, th, k)
        else:
            raise ValueError(impl)
    return compact_mask(flat, mask, k)
