"""Exact whole-vector top-k without a whole-vector TopK sort.

The dataflow is DGC's threshold select (``kernels/dgc``), finished to
EXACT top-k semantics:

  1. *threshold estimate* — tail counts of ``|x|`` on a strided sample
     against linear edges (the jnp twin of the dgc ``tail_hist``
     kernel; same bin/pick semantics as ``dgc.ref.pick_threshold``),
     stepped down ``margin`` bins so sampling noise keeps the candidate
     count >= k.
  2. *mask + compact* — one pass emitting the candidates ``|x| >= th`` as
     (values, indices) in index order: cumsum + searchsorted, the same
     XLA ops on every platform.
  3. *exact-k finisher* — a SMALL top-k over the candidates picks
     the k winners. Candidates are emitted in index order and pad slots
     hold (0, n), so stable top-k tie-breaking matches whole-vector
     ``lax.top_k`` exactly: the returned indices are BIT-IDENTICAL to the
     ``topk`` impl, while the expensive sort shrinks from Q entries to
     the candidate buffer.
  4. *guaranteed-exact fallback* — if the threshold kept fewer than k or
     more than the candidate capacity (all-zero vectors, fewer-than-k
     nonzeros, adversarial ties), a ``lax.cond`` switches the whole batch
     to a stable argsort on the monotone |x| bit patterns: still exact,
     never silently approximate.

``select_topk_rows`` batches R independent selections (the N uplink hops
of one sync) through ONE finisher top-k — one launch per hop group
instead of one per cluster per leaf.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_TINY = np.float32(np.finfo(np.float32).tiny)
_BINS = 128  # linear edges; drift |x| mass concentrates low, so fine bins
_SAMPLE = 16384  # threshold-estimation sample size per row
_MARGIN = 2  # extra bins of threshold slack against sampling noise
# the TPU compiler takes minutes over a [R, n] cumsum whose n has a small
# odd factor, and twice the memory when n is no multiple of 2^18: the
# compaction pads its rank scan to this length
_SCAN_ALIGN = 1 << 18


def candidate_capacity(n: int, k: int) -> int:
    """Static candidate-buffer size: k plus threshold overshoot headroom
    (a few near-threshold bin masses, sampling noise, and a small floor —
    the fallback covers anything beyond)."""
    return int(min(n, k + k // 4 + max(n // 24, 128) + 2048))


def _row_threshold(A, k: int, *, bins: int, sample: int, margin: int):
    """|x| threshold per row keeping >= k entries w.h.p. A [R, n] = |S|.

    Tail counts on a strided sample against linear bin edges — the
    ``kernels/dgc`` ``tail_hist`` scheme (the Pallas kernel is its TPU
    analogue) — then ``pick_threshold`` stepped ``margin`` bins down.
    """
    n = A.shape[1]
    stride = max(1, n // sample)
    Sa = A[:, ::stride]
    ns = Sa.shape[1]
    hi = jnp.max(Sa, axis=1)  # [R]
    edges = jnp.linspace(0.0, 1.0, bins + 1)[:-1][None, :] * hi[:, None]
    counts = jnp.sum(
        (Sa[:, None, :] >= jnp.maximum(edges, _TINY)[:, :, None]).astype(
            jnp.float32
        ),
        axis=2,
    )  # [R, bins] tail counts, dgc tail_hist semantics
    ks = k * (ns / n)
    ok = (counts >= ks).astype(jnp.int32)
    j = jnp.maximum(jnp.sum(ok, axis=1) - 1 - margin, 0)
    th = jnp.take_along_axis(edges, j[:, None], axis=1)[:, 0]
    # all-zero row: hi == 0 collapses every edge to 0; the tiny floor then
    # yields zero candidates and the exact fallback takes over (preserving
    # the >= k contract on zero vectors, cf. PR 1's hist fix)
    return jnp.maximum(th, _TINY)


def _compact(S, th, cap: int):
    """Candidates of each row in index order: (vals [R, cap], idx [R, cap]
    with ``n`` as the pad slot, true counts m [R]).

    cumsum ranks + one vectorized searchsorted per row — O(Q) passes of
    plain XLA ops, where a scatter of Q targets would serialize.
    """
    R, n = S.shape
    A = jnp.abs(S)
    mask = A >= th[:, None]
    # f32 ranks are exact below 2^24 and measurably faster on CPU
    cdt = jnp.float32 if n < (1 << 24) else jnp.int32
    mask = jnp.pad(mask, ((0, 0), (0, -n % _SCAN_ALIGN)))
    c = jnp.cumsum(mask.astype(cdt), axis=1)
    m = c[:, -1].astype(jnp.int32)  # true candidate counts [R]
    if cdt == jnp.float32:
        q = jnp.arange(1, cap + 1, dtype=jnp.float32) - 0.5
    else:
        q = jnp.arange(1, cap + 1, dtype=jnp.int32)
    idx = jax.vmap(lambda row: jnp.searchsorted(row, q))(c)
    idx = jnp.minimum(idx, n - 1).astype(jnp.int32)
    valid = jnp.arange(cap)[None, :] < m[:, None]
    vals = jnp.where(valid, jnp.take_along_axis(S, idx, axis=1), 0.0)
    idx = jnp.where(valid, idx, n)
    return vals, idx, m


def _finish_topk(vals_c, idx_c, k: int):
    """Exact-k finisher: small stable top-k over the candidate buffers.

    Candidates are in index order and pads are (0, n), so ties resolve
    exactly as whole-vector ``lax.top_k`` would.
    """
    _, pos = jax.lax.top_k(jnp.abs(vals_c), k)
    return (
        jnp.take_along_axis(vals_c, pos, axis=1),
        jnp.take_along_axis(idx_c, pos, axis=1),
    )


def _exact_sort_rows(S, k: int):
    """Stable exact top-k via argsort on the monotone |x| bit patterns —
    the guaranteed fallback (and the k >= n degenerate path). Emits a
    ``sort``, not a ``top_k``, so hot-path launch counts stay honest."""
    keys = jax.lax.bitcast_convert_type(jnp.abs(S), jnp.int32)
    order = jnp.argsort(-keys, axis=1, stable=True)[:, :k]
    return jnp.take_along_axis(S, order, axis=1), order.astype(jnp.int32)


def select_topk_rows(
    S,
    k: int,
    *,
    bins: int = _BINS,
    sample: int = _SAMPLE,
    margin: int = _MARGIN,
):
    """Exact top-k of every row of ``S`` [R, n]: (vals [R, k], idx [R, k]).

    Bit-identical selection to per-row ``lax.top_k(|S|, k)`` (including
    tie-breaking and the all-zero/near-empty edge cases), computed by
    threshold select + compaction + small-top-k finisher, with a
    stable-sort fallback when the threshold misses the [k, capacity]
    window.
    """
    R, n = S.shape
    S = S.astype(jnp.float32)
    if k >= n:
        return _exact_sort_rows(S, k)
    cap = candidate_capacity(n, k)
    th = _row_threshold(jnp.abs(S), k, bins=bins, sample=sample, margin=margin)
    vals_c, idx_c, m = _compact(S, th, cap)
    vals, idx = _finish_topk(vals_c, idx_c, k)
    ok = jnp.all((m >= k) & (m <= cap))
    return jax.lax.cond(
        ok,
        lambda args: (args[1], args[2]),
        lambda args: _exact_sort_rows(args[0], k),
        (S, vals, idx),
    )


def fused_pack_phi(x, phi: float, **kw):
    """Single-vector Ω payload via the fused path: (values [k], indices
    [k] int32), k = ``keep_count(n, phi)`` — the ``omega_impl="fused"``
    twin of ``sparsify.pack_phi``."""
    from repro.core.sparsify import keep_count

    flat = x.reshape(-1)
    k = keep_count(flat.size, phi)
    vals, idx = select_topk_rows(flat[None, :], k, **kw)
    return vals[0], idx[0]


# ---------------------------------------------------------------------------
# Sharded stage-1 + merge (the ("data","model") flat-vector sharding)
# ---------------------------------------------------------------------------


def shard_capacity(n_local: int, k: int, num_shards: int) -> int:
    """Static per-shard candidate capacity for a k-of-(num_shards*n_local)
    selection: the per-shard share of k plus binomial spread, sampling
    noise and near-threshold bin-mass headroom (the exactness certificate
    catches anything beyond)."""
    k_s = -(-k // num_shards)
    spread = int(5 * np.sqrt(max(k_s, 1))) + k_s // 2
    return int(min(n_local, k_s + spread + max(n_local // 24, 128) + 1024))


def shard_select_candidates(
    S_loc,
    k: int,
    num_shards: int,
    *,
    bins: int = _BINS,
    sample: int = _SAMPLE,
    margin: int = _MARGIN,
):
    """Per-shard stage-1 of the sharded whole-vector Ω.

    ``S_loc`` [R, n_local] is this shard's slice of the flat vector(s).
    Returns (vals [R, cap_s], LOCAL idx [R, cap_s] int32 with ``n_local``
    as the pad slot, m [R] true counts, th [R]): the fixed-size compacted
    candidate payload that rides ONE all-gather; the merge
    (``merge_shard_candidates``) then finishes the exact global top-k.
    """
    R, n_loc = S_loc.shape
    S_loc = S_loc.astype(jnp.float32)
    cap_s = shard_capacity(n_loc, k, num_shards)
    k_target = min(-(-k // num_shards) + (-(-k // num_shards)) // 16, n_loc)
    th = _row_threshold(
        jnp.abs(S_loc), k_target, bins=bins, sample=sample, margin=margin
    )
    vals_c, idx_c, m = _compact(S_loc, th, cap_s)
    return vals_c, idx_c, m, th


def merge_shard_candidates(cand_vals, cand_idx, m, th, k: int):
    """Merge the all-gathered shard candidates into the final payload.

    ``cand_vals``/``cand_idx`` [R, total_cand] must be ordered shard-major
    (shard 0's candidates first) with GLOBAL indices; ``m``/``th``
    [R, num_shards]. Returns (vals [R, k], idx [R, k], exact [R] bool).
    ``exact`` certifies the result equals the unsharded whole-vector
    top-k: no shard overflowed its capacity, the union holds >= k
    candidates, and every shard's threshold sits at or below the merged
    k-th magnitude (so nothing above it was left behind). When the
    certificate fails the merged top-k of the union is still returned —
    deterministic and conservative, but possibly missing tail entries;
    the unsharded path instead falls back to the exact sort.
    """
    vals, idx = _finish_topk(cand_vals, cand_idx, k)
    th_k = jnp.abs(vals[:, -1])  # merged k-th magnitude per row
    caps = jnp.asarray(
        [cand_vals.shape[1] // m.shape[1]] * m.shape[1], jnp.int32
    )
    exact = (
        jnp.all(m <= caps[None, :], axis=1)
        & (jnp.sum(m, axis=1) >= k)
        & jnp.all(th <= th_k[:, None], axis=1)
    )
    return vals, idx, exact
