"""Gradient/model-difference sparsification (paper §IV; DGC, Lin et al. 2018).

``Ω(V, φ)`` keeps the top ``(1-φ)`` fraction of entries by magnitude and
zeroes the rest. Its selection implementations:

  * ``topk``  -- exact: ``lax.top_k``'s set, found by a counting radix
                 select over the bits of |x| (``topk_masks``) where a mask
                 is wanted, by ``lax.top_k`` where a payload is
  * ``hist``  -- histogram threshold estimation (TPU adaptation of DGC's
                 sampled radix-select; the Pallas kernel in
                 ``repro.kernels.dgc`` implements the same two-pass scheme)
  * ``fused`` -- exact top-k via threshold select + compaction + a small
                 finisher top-k (``repro.kernels.fused_sync``):
                 bit-identical selection to ``topk`` without the
                 whole-vector TopK sort

Functions operate on a single array (a leaf or a flat vector), the mask
forms of Ω on a list of 1-D rows; pytree orchestration lives in
``repro.core.hfl``.

The exchange-payload helpers put their ops under the sync's named scopes,
which the profiler's trace carries as metadata: ``sync.select`` (choosing
Ω), ``sync.compact`` (gathering the kept values and indices into the
payload) and ``sync.merge`` (scattering a payload back to dense).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def keep_count(size: int, phi: float) -> int:
    """Number of entries transmitted for sparsity parameter φ."""
    return max(1, int(round((1.0 - phi) * size)))


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


#: Bits of the k-th largest magnitude resolved per counting pass. A pass
#: compares every entry with ``2**RADIX_BITS - 1`` candidate keys in one
#: fused reduction; up to 7 candidates it stays a single read of the data
#: on a TPU (15 cost 1.7x a read, 31 split into two fusions: the TPU
#: compiler's estimates), so 3 bits a pass resolve the 31-bit key in the
#: fewest reads.
RADIX_BITS = 3
#: Longest run of entries the tie pass scans with a cumsum: the row of a
#: piece that holds the cut among the ties, or a block of a 1-D piece.
TIE_BLOCK = 8192


def magnitude_keys(x):
    """int32 keys in the order of |x|: the f32 bit pattern of a
    non-negative float is monotone in its value (+0 and -0 are 0,
    subnormals come next, inf last)."""
    return jax.lax.bitcast_convert_type(jnp.abs(x).astype(jnp.float32),
                                        jnp.int32)


def _per_row(v, piece):
    """[R] values broadcast against a piece [R, ...]."""
    return v.reshape((-1,) + (1,) * (piece.ndim - 1))


def _radix_threshold(pieces, k: int):
    """Per row: T, the largest key (``magnitude_keys``) with
    ``#(row >= T) >= k``, and ``#(row > T)``. ``pieces`` are arrays
    [R, ...] whose rows, concatenated, are the R rows searched.

    Each pass resolves ``RADIX_BITS`` bits of T from the top: it counts
    every row against the candidates ``T + j·2**shift`` for the bits below
    those already found (int32 counts: f32 is exact only below 2**24) and
    keeps the largest candidate whose count still reaches k: one read of
    every piece per pass. Keys of |x| have 31 bits, so the first pass's
    candidates above 2**31 - 1 are left out. The count at the candidate
    just above is carried along, so the last pass leaves ``#(row >= T +
    1)`` with no pass of its own."""
    R = pieces[0].shape[0]
    js = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.int32)
    passes = -(-31 // RADIX_BITS)

    def count_pass(i, carry):
        lo, above = carry  # #(row >= lo) >= k > #(row >= the top)
        shift = RADIX_BITS * (passes - 1 - i)
        room = jnp.right_shift(jnp.int32(2**31 - 1) - lo, shift)
        valid = js[None, :] <= room[:, None]
        cand = lo[:, None] + jnp.left_shift(js, shift)[None, :]  # [R, M]
        counts = sum(
            jnp.stack([jnp.sum(magnitude_keys(p) >= _per_row(cand[:, j], p),
                               axis=tuple(range(1, p.ndim)), dtype=jnp.int32)
                       for j in range(js.shape[0])], axis=1)
            for p in pieces)  # falling with the candidate
        ok = valid & (counts >= k)
        lo = lo + jnp.left_shift(jnp.sum(ok, axis=1, dtype=jnp.int32), shift)
        above = jnp.maximum(
            above, jnp.max(jnp.where(valid & ~ok, counts, 0), axis=1))
        return lo, above

    zeros = jnp.zeros((R,), jnp.int32)
    return jax.lax.fori_loop(0, passes, count_pass, (zeros, zeros))


def _row_views(piece):
    """A piece [R, ...] as (offset, [R, rows, C] view) pairs in index order,
    C at most ``TIE_BLOCK``: a piece's own last axis where it is short
    enough (a reshape that moves no data), else blocks of its flattened
    rows, aligned to the end so that a short block, if any, comes first."""
    R, n = piece.shape[0], piece.size // piece.shape[0]
    if piece.ndim >= 3 and piece.shape[-1] <= TIE_BLOCK:
        return [(0, piece.reshape(R, -1, piece.shape[-1]))]
    flat = piece.reshape(R, n)
    if n <= TIE_BLOCK:
        return [(0, flat.reshape(R, 1, n))]
    head = n % TIE_BLOCK
    views = [(0, flat[:, :head].reshape(R, 1, head))] if head else []
    return views + [(head, flat[:, head:].reshape(R, -1, TIE_BLOCK))]


def _tie_cuts(keys, t, need):
    """Per piece, the [R] index (within a row of the piece) of the last tie
    kept: ties are entries equal to T, kept by lowest index in the rows'
    concatenated order until ``need`` are kept; -1 where a piece keeps
    none. One reduction gives each view's per-row tie counts; a cumsum
    over them finds the row that holds the cut, and a cumsum inside that
    one row the cut."""
    R = t.shape[0]
    before = jnp.zeros((R,), jnp.int32)  # ties in the views already seen
    cuts = []
    for piece in keys:
        cut = jnp.full((R,), -1, jnp.int32)
        for off, view in _row_views(piece):
            rows, C = view.shape[1:]
            per_row = jnp.sum(view == t[:, None, None], axis=2,
                              dtype=jnp.int32)  # [R, rows]
            cum = jnp.cumsum(per_row, axis=1)
            left = need - before  # ties still to keep, from this view on
            pos = []
            for r in range(R):  # static unroll; R is small
                row = jnp.minimum(jnp.sum(cum[r] < left[r], dtype=jnp.int32),
                                  rows - 1)
                skipped = jnp.sum(jnp.where(jnp.arange(rows) < row,
                                            per_row[r], 0))
                ties = jax.lax.dynamic_slice(
                    view, (r, row, 0), (1, 1, C)).reshape(C) == t[r]
                col = jnp.sum(jnp.cumsum(ties, dtype=jnp.int32)
                              < left[r] - skipped, dtype=jnp.int32)
                pos.append(off + row * C + col)
            total = cum[:, -1]
            cut = jnp.where(left <= 0, cut,
                            jnp.where(left >= total, off + rows * C - 1,
                                      jnp.stack(pos)))
            before = before + total
        cuts.append(cut)
    return cuts


def _row_index(piece):
    """Index of each entry within its row of a piece [R, ...]."""
    shape, idx, stride = piece.shape, 0, 1
    for d in range(piece.ndim - 1, 0, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.int32, shape, d) * stride
        stride *= shape[d]
    return idx


@partial(jax.jit, static_argnames="k")
def topk_masks(pieces, k: int):
    """Masks of the k largest-|x| entries of each row, without a sort, a
    gather or a scatter. ``pieces`` are arrays [R, ...] with one leading
    row axis; row r is the concatenation of every piece's row r in index
    order (a model's leaves, or one [R, n] array). Masks come back in the
    pieces' shapes.

    The set is ``lax.top_k``'s over each concatenated row: every entry
    above T, the k-th largest magnitude, and the ties at T by lowest
    index. T comes from a counting radix select over ``magnitude_keys``
    (``_radix_threshold``), the cut among the ties from ``_tie_cuts``;
    then ``keys > T | (keys == T & index <= cut)`` keeps exactly k entries
    of each row, also where a row is all zeros."""
    t, above = _radix_threshold(pieces, k)
    keys = [magnitude_keys(p) for p in pieces]
    masks = []
    for key, cut in zip(keys, _tie_cuts(keys, t, k - above)):
        t_p, cut_p = _per_row(t, key), _per_row(cut, key)
        masks.append((key > t_p)
                     | ((key == t_p) & (_row_index(key) <= cut_p)))
    return masks


def topk_mask(x, k: int):
    """Boolean mask of the k largest-|x| entries. x any shape."""
    return topk_masks([x.reshape((1,) + (x.shape or (1,)))], k)[0].reshape(
        x.shape)


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


def omega_masks(pieces, phi: float, *, impl: str = "topk", bins: int = 64):
    """Masks of Ω(row, φ) for the rows of ``pieces`` (arrays [R, ...]; row
    r is every piece's row r concatenated, as in ``topk_masks``): the
    entries that ``pack_phi(row, φ, impl=impl)`` puts in its payload, so
    that a sync whose exchange is local applies Ω with ``where`` and never
    forms the payload. Masks come back in the pieces' shapes.

      * ``topk``          -- exactly k entries, ``lax.top_k``'s set
                             (``topk_masks``: every row in each pass)
      * ``hist``/``pallas`` -- the threshold mask of the concatenated row
                             truncated to its first k entries in index
                             order, as ``compact_mask`` keeps them
    """
    R = pieces[0].shape[0]
    k = keep_count(sum(p.size for p in pieces) // R, phi)
    if impl == "topk":
        return topk_masks(pieces, k)
    rows = jnp.concatenate([p.reshape(R, -1) for p in pieces], axis=1)
    masks = []
    for row in rows:
        if impl == "hist":
            m = threshold_mask(row, phi, bins=bins)
        elif impl == "pallas":
            from repro.kernels.dgc import ops as _k

            m = mask_at_least_k(row, _k.threshold_pallas(row, phi, bins=bins),
                                k)
        else:
            raise ValueError(impl)
        masks.append(m & (jnp.cumsum(m, dtype=jnp.int32) <= k))
    m = jnp.stack(masks)
    offsets = np.cumsum([0] + [p.size // R for p in pieces])
    return [m[:, a:b].reshape(p.shape)
            for p, a, b in zip(pieces, offsets[:-1], offsets[1:])]


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
