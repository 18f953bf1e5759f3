"""Hierarchical FL engine — the paper's protocol mapped onto a TPU mesh.

Mapping (see DESIGN.md §2): cluster -> pod, MUs -> data shards inside a pod.
Per-cluster models carry a leading ``[N]`` axis sharded over ``"pod"`` (GSPMD
"replicated" would wrongly assume identical values across clusters).

  * ``make_cluster_train_step``: one intra-cluster iteration (Alg. 3 l.4-8 /
    Alg. 5 "Computation and Uplink" + "Model Average"). The batch-mean
    gradient + the all-reduce GSPMD inserts over "data" IS the MU->SBS->MU
    aggregation; the optimizer step is the cluster model update.
  * ``make_sync_step``: the every-H inter-cluster consensus (Alg. 5 l.22-39).
    - ``dense``    : plain model averaging over the pod axis (the
                     hierarchical-local-SGD baseline the paper builds on).
    - ``sparse``   : the paper's contribution. DGC top-k of the model
                     difference, (values, indices) all-gather over "pod"
                     (2k << Q bytes on the slow cross-pod link),
                     scatter-add consensus, discounted error accumulation
                     (β_s at the SBS, β_m at the MBS).
    - ``quantized_sparse``: beyond-paper — sparse + bf16 values + int32 idx.

Two sparse *layouts* (``HFLConfig.sync_layout``):

  * ``flat`` (default): the paper-exact whole-model Ω. All pytrees are
    packed into ONE contiguous f32 vector (``repro.utils.flatten``, static
    leaf offsets), so each sync runs ONE top-k, ONE all-gather and ONE
    scatter-add regardless of how many leaves the architecture has.
  * ``leaf``: the legacy per-leaf adaptation (top-k per tensor, one
    collective per leaf), kept as the reference for equivalence tests.

The sparse sync runs inside a fully-manual ``shard_map``; because the
(data, model) shards are aligned across pods, each device exchanges only its
own shard's top-k with its peers in other pods — no intra-pod collectives at
all, and flat-vector positions mean the same model entry on every peer.
The Ω selection itself is pluggable (``HFLConfig.omega_impl``): exact
``lax.top_k`` or the DGC histogram-threshold path (jnp or Pallas kernels).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsify as sp
from repro.obs.metrics import current_registry
from repro.utils import flatten as fl
from repro.utils import jaxcompat


def _count_build(kind: str, **labels) -> None:
    """Build-time bookkeeping into the ambient metrics registry: which
    step builders ran, under which mode/layout/impl — the builders have no
    telemetry handle to thread, and build time is off the hot path."""
    reg = current_registry()
    if reg.enabled:
        reg.counter(f"hfl.{kind}_builds").inc(**labels)


class HFLState(NamedTuple):
    params: Any      # [N, ...] per-cluster models
    opt: Any         # [N, ...] per-cluster optimizer state
    w_ref: Any       # global reference model W̃ (no cluster axis)
    eps: Any         # [N, ...] SBS uplink error ε_n
    e: Any           # MBS downlink error (global)
    step: jnp.ndarray


def hfl_init(params_single, optimizer, hfl_cfg, *, buffer_dtype=jnp.float32):
    """Build HFLState by replicating a single model across N clusters.

    ``buffer_dtype``: dtype of the HFL error/reference buffers (w_ref, eps,
    e). f32 is the paper-faithful default; bf16 halves their footprint
    (3 model-sized buffers) at the cost of error-feedback resolution — a
    §Perf memory lever for the 100B+ archs.
    """
    N = hfl_cfg.num_clusters
    rep = jax.tree.map(lambda p: jnp.broadcast_to(p[None], (N,) + p.shape), params_single)
    opt = jax.vmap(optimizer.init)(rep)
    bd = jnp.dtype(buffer_dtype)
    return HFLState(
        params=rep,
        opt=opt,
        w_ref=jax.tree.map(lambda p: p.astype(bd), params_single),
        eps=jax.tree.map(lambda p: jnp.zeros((N,) + p.shape, bd), params_single),
        e=jax.tree.map(lambda p: jnp.zeros(p.shape, bd), params_single),
        step=jnp.zeros((), jnp.int32),
    )


def serving_params(state: HFLState):
    """Consensus model for serving (cluster 0 post-sync == all clusters)."""
    return jax.tree.map(lambda p: p[0], state.params)


# ---------------------------------------------------------------------------
# Intra-cluster train step
# ---------------------------------------------------------------------------

# The train step's ops sit under two named scopes, which change only the
# HLO metadata and so the names in the profiler's trace: ``train.grad``
# (forward, and under ``transpose(...)`` backward) and ``train.optimizer``.
# ``models.attention`` adds ``attention`` inside both passes.


def make_cluster_train_step(loss_fn: Callable, optimizer, lr_schedule):
    """loss_fn(params, batch) -> (loss, aux). batch leaves [N, localB, ...]."""
    _count_build("train_step", masked="no")

    def train_step(state: HFLState, batch):
        with jax.named_scope("train.optimizer"):
            lr = lr_schedule(state.step)

        def one_cluster(params, opt, cbatch):
            with jax.named_scope("train.grad"):
                (loss, _aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, cbatch)
            with jax.named_scope("train.optimizer"):
                new_params, new_opt = optimizer.update(grads, opt, params, lr)
            return new_params, new_opt, loss

        params, opt, losses = jax.vmap(one_cluster)(state.params, state.opt, batch)
        with jax.named_scope("train.optimizer"):
            step = state.step + 1
        return state._replace(params=params, opt=opt, step=step), losses

    return train_step


def make_masked_cluster_train_step(loss_fn: Callable, optimizer, lr_schedule):
    """One iteration of ONE cluster: grads/update for row ``n`` only.

    The vmapped step computes all N clusters even when the caller (the
    async / trace-replay disciplines) advances a single one — N-1 clusters
    of wasted forward+backward per launch. This step slices cluster ``n``
    out of the stacked state, trains just that model, and writes the row
    back in place (a dynamic-update-slice under donation), so its FLOPs
    are ~1/N of the vmapped step's (asserted via ``launch.hlo_cost`` in
    the tier-1 suite).

    ``batch_n`` leaves are a single cluster's rows ``[localB, ...]`` (no
    cluster axis); ``n`` is a traced int32 so one compiled program serves
    every cluster. Returns ``(state, loss)`` with ``loss`` a scalar.
    """
    _count_build("train_step", masked="yes")

    def train_step(state: HFLState, batch_n, n):
        # the row's slicing and write-back are the update's own bookkeeping
        with jax.named_scope("train.optimizer"):
            lr = lr_schedule(state.step)
            params_n = jax.tree.map(lambda p: p[n], state.params)
            opt_n = jax.tree.map(lambda o: o[n], state.opt)
        with jax.named_scope("train.grad"):
            (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params_n, batch_n)
        with jax.named_scope("train.optimizer"):
            new_p, new_o = optimizer.update(grads, opt_n, params_n, lr)
            params = jax.tree.map(lambda P, q: P.at[n].set(q), state.params,
                                  new_p)
            opt = jax.tree.map(lambda O, q: O.at[n].set(q), state.opt, new_o)
            step = state.step + 1
        return state._replace(params=params, opt=opt, step=step), loss

    return train_step


# ---------------------------------------------------------------------------
# Inter-cluster sync (every H steps)
# ---------------------------------------------------------------------------


def _wire_round(x, fmt: str):
    """Wire-format round-trip: what the receiver reconstructs from the
    transmitted values under ``HFLConfig.wire_format``.

      * ``bf16`` -- bfloat16 round-to-nearest-even (the historical
        ``quantized_sparse`` wire).
      * ``q8``   -- 8-bit linear quantization, scale = max|x|/127 carried
        as an f32 header on the wire. All arithmetic is f32 so this is
        bit-identical to the host codec (``repro.comm.codecs`` q8 formats),
        and the quantization error lands in the same ``eps``/``e`` error
        buffers as the sparsification error.

    On a 1-D payload this is the single-cluster case of
    ``_wire_round_rows`` (the last-axis q8 scale IS the whole-payload
    scale), so it simply delegates — one copy of the wire rule.
    """
    return _wire_round_rows(x, fmt)


def _wire_round_rows(x, fmt: str):
    """Row-batched wire rounding: each leading-axis row is one cluster's
    payload, so the q8 scale reduces over the LAST axis only —
    bit-identical to looping ``_wire_round`` over rows (the fused sync
    batches the N uplink hops). Wire rounding is part of ``sync.compact``."""
    with jax.named_scope("sync.compact"):
        if fmt == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        if fmt == "q8":
            amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
            scale = jnp.where(amax > 0, amax / jnp.float32(127.0),
                              jnp.float32(1.0))
            return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    raise ValueError(fmt)


# ---- flat layout: the paper's whole-model Ω, one launch per hop -----------
#
# Every op of a sync program sits under exactly one of four named scopes
# (HLO metadata only; the profiler's trace carries them): ``sync.select``
# builds the drift vectors and chooses Ω, ``sync.compact`` gathers the kept
# values and indices into the payload (wire rounding included),
# ``sync.exchange`` combines the payloads across clusters, and
# ``sync.merge`` scatters back to dense, updates the error buffers and the
# reference, and has the clusters adopt it. The helpers here and in
# ``core.sparsify`` open their own scope; the builders scope only their
# inline ops, so scopes never nest.


def _flat_sync_stats(wn, new_eps, new_e, new_wref, d, ul_idx, dl_idx):
    """In-jit learning-health statistics (``collect_stats=True``).

    Every input is an intermediate the sync already has live in HBM —
    the stats are a handful of extra norm reductions plus the Ω index
    arrays passed through as outputs, so collecting them costs no extra
    HBM round-trips and never touches the main dataflow (the sync's
    state outputs are bit-identical with stats on or off; tested).

      * ``drift``      [N]  — per-cluster consensus drift
                              ||w_n − w̄|| / ||w̄|| over PRE-sync models
      * ``eps_norm``   [N]  — post-sync SBS error-feedback residual norms
      * ``e_norm``     []   — post-sync MBS residual norm
      * ``wref_norm``  []   — new reference-model norm (ratio denominators)
      * ``update_norm`` []  — ||d||, the applied consensus update
      * ``ul_idx`` [N, k_ul] / ``dl_idx`` [k_dl] — Ω index sets; the
        host-side monitor diffs consecutive syncs for overlap fractions
    """
    wbar = jnp.mean(wn, axis=0)
    wnorm = jnp.maximum(jnp.linalg.norm(wbar), 1e-30)
    return {
        "drift": jnp.linalg.norm(wn - wbar[None, :], axis=1) / wnorm,
        "eps_norm": jnp.linalg.norm(new_eps, axis=1),
        "e_norm": jnp.linalg.norm(new_e),
        "wref_norm": jnp.linalg.norm(new_wref),
        "update_norm": jnp.linalg.norm(d),
        "ul_idx": ul_idx,
        "dl_idx": dl_idx,
    }


def _make_flat_local_sync(hfl_cfg, wire, collect_stats: bool = False):
    """Single-process whole-vector sync (mesh=None): the cluster axis is a
    leading array axis and the cross-pod exchange is a local mean."""
    impl = hfl_cfg.omega_impl

    def flat_sync(state: HFLState):
        N = hfl_cfg.num_clusters
        with jax.named_scope("sync.select"):
            wref, ref_spec = fl.pack(state.w_ref)
            e, _ = fl.pack(state.e)
        p_spec = fl.spec_of_stacked(state.params)
        Q = ref_spec.total

        # --- SBS side: drift + discounted error, whole-vector top-k uplink
        #     (Alg.5 l.24-27, Ω over V ∈ R^Q) ---
        s, eps_spec = _pack_drift(state, hfl_cfg.tiers[1].beta_up)  # [N, Q]
        sents, new_eps, ul_idx = [], [], []
        for n in range(N):  # static unroll; N is small
            with jax.named_scope("sync.select"):
                s_n = s[n]
            vals, idx = sp.pack_phi(s_n, hfl_cfg.tiers[1].phi_up, impl=impl)
            if wire:
                vals = _wire_round(vals, wire)
            sent = sp.unpack_topk(vals, idx, Q)
            sents.append(sent)
            with jax.named_scope("sync.merge"):
                new_eps.append(s[n] - sent)
            ul_idx.append(idx)

        # --- MBS side: consensus + discounted error + top-k downlink ---
        with jax.named_scope("sync.exchange"):
            mean = sum(sents) / N
        with jax.named_scope("sync.select"):
            delta = mean + hfl_cfg.tiers[1].beta_down * e
        dvals, didx = sp.pack_phi(delta, hfl_cfg.tiers[1].phi_down, impl=impl)
        if wire:
            dvals = _wire_round(dvals, wire)
        d = sp.unpack_topk(dvals, didx, Q)
        with jax.named_scope("sync.merge"):
            new_e = delta - d
            new_wref = wref + d

            # --- clusters adopt the new reference (Alg.5 l.33/43) ---
            new_wn = jnp.broadcast_to(new_wref[None], (N, Q))
            eps_stacked = jnp.stack(new_eps)
            new_state = state._replace(
                params=fl.unpack_stacked(new_wn, p_spec),
                w_ref=fl.unpack(new_wref, ref_spec),
                eps=fl.unpack_stacked(eps_stacked, eps_spec),
                e=fl.unpack(new_e, ref_spec),
            )
        if not collect_stats:
            return new_state
        wn, _ = fl.pack_stacked(state.params)
        return new_state, _flat_sync_stats(
            wn, eps_stacked, new_e, new_wref, d, jnp.stack(ul_idx), didx)

    return flat_sync


def _flat_shard_sync(params, w_ref, eps, e, *, hfl_cfg, wire):
    """shard_map body: whole-LOCAL-vector sync for this device's shards.

    params/eps leaves [C, *loc] (C = clusters hosted per pod, usually 1);
    w_ref/e leaves [*loc]. Packs the local shards into one flat vector —
    the layout is a trace-time constant and identical on every pod peer —
    then runs Alg.5 with ONE top-k per hop, ONE "pod" all-gather and ONE
    scatter-add for the whole model.
    """
    impl = hfl_cfg.omega_impl
    N = hfl_cfg.num_clusters
    with jax.named_scope("sync.select"):
        wref, ref_spec = fl.pack(w_ref)
        e_v, _ = fl.pack(e)
        wn, p_spec = fl.pack_stacked(params)  # [C, Qloc]
        eps_m, eps_spec = fl.pack_stacked(eps)
        C = wn.shape[0]
        Q = ref_spec.total

        # --- SBS side (Alg.5 l.24-27): one whole-vector Ω per cluster ---
        s = wn - wref[None, :] + hfl_cfg.tiers[1].beta_up * eps_m  # [C, Qloc]
    vals_l, idx_l, eps_rows = [], [], []
    for c in range(C):  # static; C == N // num_pods, normally 1
        with jax.named_scope("sync.select"):
            s_c = s[c]
        vals, idx = sp.pack_phi(s_c, hfl_cfg.tiers[1].phi_up, impl=impl)
        if wire:
            # quantize BEFORE accounting the residual: eps must buffer the
            # wire quantization error too, since receivers only ever see
            # the rounded value (keeps this path consistent with the local
            # flat/leaf paths and preserves exact drift conservation)
            vals = _wire_round(vals, wire)
        sent = sp.unpack_topk(vals, idx, Q)
        with jax.named_scope("sync.merge"):
            eps_rows.append(s[c] - sent)
        vals_l.append(vals)
        idx_l.append(idx)
    with jax.named_scope("sync.compact"):
        vals = jnp.stack(vals_l)  # [C, k]
        idx = jnp.stack(idx_l)

    # --- cross-pod exchange: 2·C·k values per hop instead of C·Q ---
    with jax.named_scope("sync.exchange"):
        if wire == "bf16":
            # lossless now (vals already round-tripped); the barriers pin
            # the bf16 cast to THIS side of the gather: XLA's algebraic
            # simplifier otherwise rewrites convert(all_gather(bf16)) into
            # all_gather(f32), putting f32 back on the wire. (q8 values
            # are already exact multiples of the scale; the gather stays
            # f32 as a simulation artifact — the byte-accurate stream is
            # the codec's.)
            vals = jax.lax.optimization_barrier(vals.astype(jnp.bfloat16))
        all_vals = jax.lax.all_gather(vals, "pod")  # [npod, C, k]
        if wire == "bf16":
            all_vals = jax.lax.optimization_barrier(all_vals)
        all_idx = jax.lax.all_gather(idx, "pod")
    with jax.named_scope("sync.merge"):
        summed = (
            jnp.zeros((Q,), jnp.float32)
            .at[all_idx.reshape(-1)]
            .add(all_vals.reshape(-1).astype(jnp.float32))
        )

    # --- MBS side: discounted error + whole-vector top-k downlink ---
    with jax.named_scope("sync.exchange"):
        mean = summed / N
    with jax.named_scope("sync.select"):
        delta = mean + hfl_cfg.tiers[1].beta_down * e_v
    dvals, didx = sp.pack_phi(delta, hfl_cfg.tiers[1].phi_down, impl=impl)
    if wire:
        dvals = _wire_round(dvals, wire)
    d = sp.unpack_topk(dvals, didx, Q)
    with jax.named_scope("sync.merge"):
        new_e = delta - d
        new_wref = wref + d

        # --- clusters adopt the new reference ---
        new_wn = jnp.broadcast_to(new_wref[None], (C, Q))
        return (
            fl.unpack_stacked(new_wn, p_spec),
            fl.unpack(new_wref, ref_spec),
            fl.unpack_stacked(jnp.stack(eps_rows), eps_spec),
            fl.unpack(new_e, ref_spec),
        )


# ---- fused flat layout: batched whole-model Ω via kernels/fused_sync ------


def _unpack_ref_outputs(new_wref, ref_spec, state: HFLState):
    """f32 flat reference -> (params, w_ref) trees WITHOUT routing params
    through the (possibly bf16) w_ref storage dtype: each leaf is cast
    straight f32 -> its own dtype, exactly like the unfused paths."""
    with jax.named_scope("sync.merge"):
        wref_leaves = [
            new_wref[ref_spec.leaf_slice(i)].reshape(ref_spec.shapes[i])
            for i in range(len(ref_spec.sizes))
        ]
        wref_tree_f32 = jax.tree.unflatten(ref_spec.treedef, wref_leaves)
        params = jax.tree.map(
            lambda w, p: jnp.broadcast_to(w.astype(p.dtype)[None], p.shape),
            wref_tree_f32,
            state.params,
        )
        w_ref = jax.tree.map(
            lambda w, r: w.astype(r.dtype), wref_tree_f32, state.w_ref
        )
        return params, w_ref


def _pack_drift(state: HFLState, beta_s: float, *, shards: int = 1):
    """[N, Q'] drift matrix s = wn - wref + β_s·eps built leaf-by-leaf in
    ONE concat — the packed params/eps matrices are never materialized
    separately, halving the [N, Q]-sized traffic of the sync prologue.
    Part of ``sync.select``."""
    N = jax.tree.leaves(state.params)[0].shape[0]
    p_leaves = jax.tree.leaves(state.params)
    wr_leaves = jax.tree.leaves(state.w_ref)
    eps_leaves = jax.tree.leaves(state.eps)
    with jax.named_scope("sync.select"):
        s = jnp.concatenate(
            [
                (p.reshape(N, -1).astype(jnp.float32)
                 - w.reshape(-1).astype(jnp.float32)[None, :])
                + beta_s * ep.reshape(N, -1).astype(jnp.float32)
                for p, w, ep in zip(p_leaves, wr_leaves, eps_leaves)
            ],
            axis=1,
        )
        # spec from eps: the unpacked drift residual must keep eps' storage
        # dtype (params may be a different dtype than the error buffers)
        spec = fl.spec_of_stacked(state.eps, shards=shards)
        if spec.pad:
            s = jnp.pad(s, ((0, 0), (0, spec.pad)))
        return s, spec


def _scatter_rows(idx, vals, L: int):
    """Dense [N, L] matrix with ``out[n, idx[n, j]] += vals[n, j]``, as
    ONE flat 1-D scatter (a 2-D scatter serializes on XLA-CPU). Pad/
    out-of-range entries carry vals == 0, so clipping them is a numeric
    no-op. Part of ``sync.merge``."""
    N = idx.shape[0]
    with jax.named_scope("sync.merge"):
        flat_idx = (jnp.minimum(idx, L - 1)
                    + (jnp.arange(N, dtype=jnp.int32) * L)[:, None]
                    ).reshape(-1)
        return (
            jnp.zeros((N * L,), jnp.float32)
            .at[flat_idx]
            .add(vals.reshape(-1))
            .reshape(N, L)
        )


def _make_flat_fused_local_sync(hfl_cfg, wire, collect_stats: bool = False):
    """Single-process whole-vector sync via the fused top-k select.

    Protocol-identical to ``_make_flat_local_sync`` (selection is
    bit-identical to ``omega_impl="topk"``), restructured for the fused
    path's batched shape: the N uplink Ωs run as ONE ``select_topk_rows``
    call (one finisher top-k for all clusters), all N sent rows
    materialize through a single flat scatter-add, and the error/
    consensus updates stay dense fusable arithmetic — so a sync traces
    2 top-k and 2 scatter-add launches regardless of N or the leaf
    count (vs one of each per leaf per hop on the legacy path).
    """
    from repro.kernels.fused_sync import ops as fops

    N = hfl_cfg.num_clusters

    def flat_sync(state: HFLState):
        with jax.named_scope("sync.select"):
            wref, ref_spec = fl.pack(state.w_ref)
            e, _ = fl.pack(state.e)
        Q = ref_spec.total
        s, eps_spec = _pack_drift(state, hfl_cfg.tiers[1].beta_up)

        # --- SBS side: batched whole-vector Ω uplinks (Alg.5 l.24-27);
        #     the fused selection gathers its payload itself ---
        k_ul = sp.keep_count(Q, hfl_cfg.tiers[1].phi_up)
        with jax.named_scope("sync.select"):
            vals, idx = fops.select_topk_rows(s, k_ul)  # [N, k]
        if wire:
            vals = _wire_round_rows(vals, wire)
        # ONE flat scatter materializes all N sent rows; the error update
        # and the consensus mean stay dense elementwise ops XLA fuses
        sents = _scatter_rows(idx, vals, Q)
        with jax.named_scope("sync.merge"):
            new_eps = s - sents

        # --- MBS side: consensus + discounted error + Ω downlink ---
        with jax.named_scope("sync.exchange"):
            mean = jnp.mean(sents, axis=0)
        k_dl = sp.keep_count(Q, hfl_cfg.tiers[1].phi_down)
        with jax.named_scope("sync.select"):
            delta = mean + hfl_cfg.tiers[1].beta_down * e
            dvals, didx = fops.select_topk_rows(delta[None, :], k_dl)
            dvals, didx = dvals[0], didx[0]
        if wire:
            dvals = _wire_round(dvals, wire)
        with jax.named_scope("sync.merge"):
            d = jnp.zeros((Q,), jnp.float32).at[didx].add(dvals)
            new_e = delta - d
            new_wref = wref + d
        # --- clusters adopt the new reference (Alg.5 l.33/43) ---
        params, w_ref = _unpack_ref_outputs(new_wref, ref_spec, state)
        with jax.named_scope("sync.merge"):
            new_state = state._replace(
                params=params,
                w_ref=w_ref,
                eps=fl.unpack_stacked(new_eps, eps_spec),
                e=fl.unpack(new_e, ref_spec),
            )
        if not collect_stats:
            return new_state
        # the fused prologue never materializes the stacked params matrix
        # (that is its point), so the drift statistic packs it here — an
        # extra read of buffers already resident, paid only when health
        # monitoring is on
        wn, _ = fl.pack_stacked(state.params)
        return new_state, _flat_sync_stats(
            wn, new_eps, new_e, new_wref, d, idx, didx)

    return flat_sync


# ---- sharded flat layout: the vector itself shards over (data, model) -----


def _sharded_select(s, k: int, S: int, L: int, size: int, *, gathered=None):
    """Shared stage-1+merge of the sharded whole-vector Ω.

    ``s`` [R, S*L] (local emulation) runs every shard's stage-1 locally;
    a mesh body instead passes ``gathered`` = (cand_vals, cand_idx, m,
    th) already stacked shard-major [S, R, ...] from its all-gather. The
    merge is identical either way, so the mesh execution and the local
    emulation are bit-identical. Returns (vals [R, k], idx [R, k], exact).
    Part of ``sync.select``: the selection gathers its payload itself.
    """
    from repro.kernels.fused_sync import ops as fops

    with jax.named_scope("sync.select"):
        if gathered is None:
            parts = []
            for sh in range(S):
                sl = s[:, sh * L:(sh + 1) * L]
                v, i, m, th = fops.shard_select_candidates(sl, k, S)
                gi = jnp.where(i < L, i + sh * L, size)
                parts.append((v, gi, m, th))
            cand_v = jnp.stack([p[0] for p in parts])  # [S, R, cap_s]
            cand_i = jnp.stack([p[1] for p in parts])
            m = jnp.stack([p[2] for p in parts])  # [S, R]
            th = jnp.stack([p[3] for p in parts])
        else:
            cand_v, cand_i, m, th = gathered
        R = cand_v.shape[1]
        cand_v = jnp.transpose(cand_v, (1, 0, 2)).reshape(R, -1)  # shard-major
        cand_i = jnp.transpose(cand_i, (1, 0, 2)).reshape(R, -1)
        return fops.merge_shard_candidates(
            cand_v, cand_i, jnp.transpose(m), jnp.transpose(th), k
        )


def _make_flat_sharded_local_sync(hfl_cfg, wire, shards: int):
    """Single-process emulation of the sharded flat sync: the padded flat
    vector is treated as ``shards`` contiguous pieces, stage-1 candidate
    selection runs per piece, and the merge finishes the whole-vector Ω —
    the exact dataflow of the mesh path (``_make_flat_sharded_sync``)
    with the all-gather replaced by a stack, so the two are bit-identical
    (the sharded-vs-unsharded equivalence tests run on this path).
    """
    N, S = hfl_cfg.num_clusters, shards

    def sharded_sync(state: HFLState):
        with jax.named_scope("sync.select"):
            wref, ref_spec = fl.pack(state.w_ref, shards=S)
            e, _ = fl.pack(state.e, shards=S)
        Q, Qp = ref_spec.total, ref_spec.padded_total
        L = ref_spec.local_size
        s, eps_spec = _pack_drift(state, hfl_cfg.tiers[1].beta_up, shards=S)

        k_ul = sp.keep_count(Q, hfl_cfg.tiers[1].phi_up)
        # the exactness certificate is intentionally advisory here: when a
        # shard overflows its candidate capacity the merged union top-k is
        # used as-is (deterministic, documented in merge_shard_candidates)
        # because the mesh body cannot fall back to a whole-vector sort —
        # and the emulation must stay bit-equivalent to the mesh
        vals, idx, _exact = _sharded_select(s, k_ul, S, L, Qp)
        if wire:
            vals = _wire_round_rows(vals, wire)
        sents = _scatter_rows(idx, vals, Qp)
        with jax.named_scope("sync.merge"):
            new_eps = s - sents
        with jax.named_scope("sync.exchange"):
            mean = jnp.mean(sents, axis=0)
        with jax.named_scope("sync.select"):
            delta = mean + hfl_cfg.tiers[1].beta_down * e

        k_dl = sp.keep_count(Q, hfl_cfg.tiers[1].phi_down)
        dvals, didx, _exact_d = _sharded_select(delta[None, :], k_dl, S, L, Qp)
        with jax.named_scope("sync.select"):
            dvals, didx = dvals[0], didx[0]
        if wire:
            dvals = _wire_round(dvals, wire)
        d = _scatter_rows(didx[None, :], dvals[None, :], Qp)
        with jax.named_scope("sync.merge"):
            d = d[0]
            new_e = delta - d
            new_wref = wref + d

        params, w_ref = _unpack_ref_outputs(new_wref, ref_spec, state)
        with jax.named_scope("sync.merge"):
            return state._replace(
                params=params,
                w_ref=w_ref,
                eps=fl.unpack_stacked(new_eps, eps_spec),
                e=fl.unpack(new_e, ref_spec),
            )

    return sharded_sync


def _make_flat_sharded_sync(hfl_cfg, wire, mesh):
    """Mesh path: the padded flat vector shards over the in-pod
    ("data", "model") axes inside a fully-manual shard_map.

    Each device holds ONE contiguous piece [N, L] of the drift matrix,
    runs the fused per-shard compaction on it, and exchanges only the
    compacted (values, indices) candidate payloads in a single
    all-gather (~1.3k entries, not Q) — the 100B-class configs never
    materialize the whole flat vector per device. The merge is
    replicated math over the gathered candidates, so every device
    computes identical payloads and scatters only its own slice.
    """
    N = hfl_cfg.num_clusters
    axes = tuple(
        a for a in ("data", "model")
        if a in mesh.axis_names and mesh.shape[a] > 1
    )
    S = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    assert S > 1, "sharded flat sync needs a >1 (data, model) mesh extent"
    P = jax.sharding.PartitionSpec
    from repro.kernels.fused_sync import ops as fops

    def gather_shard_major(t):
        # innermost axis first, so the stacked leading axis ends up
        # data-major — matching P(axes)'s contiguous shard order
        for a in reversed(axes):
            t = jax.lax.all_gather(t, a)
        return t.reshape((S,) + t.shape[len(axes):])

    def shard_offset(L):
        lin = jnp.int32(0)
        for a in axes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        return lin * L

    def body(s, wref, e, *, Q, Qp, L):
        # s [N, L]; wref/e [L] — this device's contiguous piece. The
        # candidates' all-gather inside a pod is part of the selection; the
        # clusters' payloads combine in the mean (sync.exchange).
        k_ul = sp.keep_count(Q, hfl_cfg.tiers[1].phi_up)
        with jax.named_scope("sync.select"):
            off = shard_offset(L)
            v, i, m, th = fops.shard_select_candidates(s, k_ul, S)
            gi = jnp.where(i < L, i + off, Qp)
            gathered = tuple(
                gather_shard_major(t) for t in (v, gi, m, th)
            )  # [S, N, cap_s] / [S, N]
        vals, idx, _exact = _sharded_select(
            None, k_ul, S, L, Qp, gathered=gathered
        )
        if wire:
            vals = _wire_round_rows(vals, wire)
        # scatter only the indices living on THIS shard (others no-op)
        with jax.named_scope("sync.merge"):
            loc = idx - off
            inb = (loc >= 0) & (loc < L)
            loc, vals = jnp.where(inb, loc, L - 1), jnp.where(inb, vals, 0.0)
        sents = _scatter_rows(loc, vals, L)
        with jax.named_scope("sync.merge"):
            new_eps = s - sents
        with jax.named_scope("sync.exchange"):
            mean = jnp.mean(sents, axis=0)

        k_dl = sp.keep_count(Q, hfl_cfg.tiers[1].phi_down)
        with jax.named_scope("sync.select"):
            delta = mean + hfl_cfg.tiers[1].beta_down * e
            dv, di, dm, dth = fops.shard_select_candidates(delta[None, :],
                                                           k_dl, S)
            dgi = jnp.where(di < L, di + off, Qp)
            dg = tuple(gather_shard_major(t) for t in (dv, dgi, dm, dth))
        dvals, didx, _exact_d = _sharded_select(
            None, k_dl, S, L, Qp, gathered=dg
        )
        with jax.named_scope("sync.select"):
            dvals, didx = dvals[0], didx[0]
        if wire:
            dvals = _wire_round(dvals, wire)
        with jax.named_scope("sync.merge"):
            dloc = didx - off
            dinb = (dloc >= 0) & (dloc < L)
            dloc = jnp.where(dinb, dloc, L - 1)[None, :]
            dvals = jnp.where(dinb, dvals, 0.0)[None, :]
        d = _scatter_rows(dloc, dvals, L)
        with jax.named_scope("sync.merge"):
            d = d[0]
            new_e = delta - d
            new_wref = wref + d
        return new_eps, new_wref, new_e

    def sharded_sync(state: HFLState):
        with jax.named_scope("sync.select"):
            wref, ref_spec = fl.pack(state.w_ref, shards=S)
            e, _ = fl.pack(state.e, shards=S)
        Q, Qp, L = ref_spec.total, ref_spec.padded_total, ref_spec.local_size
        s, eps_spec = _pack_drift(state, hfl_cfg.tiers[1].beta_up, shards=S)
        vec = P(axes if len(axes) > 1 else axes[0])
        mat = P(None, *vec)
        with jax.named_scope("sync.select"):
            s = jax.lax.with_sharding_constraint(
                s, jax.sharding.NamedSharding(mesh, mat))
        sm = jaxcompat.shard_map(
            partial(body, Q=Q, Qp=Qp, L=L),
            mesh=mesh,
            in_specs=(mat, vec, vec),
            out_specs=(mat, vec, vec),
        )
        new_eps, new_wref, new_e = sm(s, wref, e)
        params, w_ref = _unpack_ref_outputs(new_wref, ref_spec, state)
        with jax.named_scope("sync.merge"):
            return state._replace(
                params=params,
                w_ref=w_ref,
                eps=fl.unpack_stacked(new_eps, eps_spec),
                e=fl.unpack(new_e, ref_spec),
            )

    return sharded_sync


# ---- leaf layout: legacy per-tensor Ω, kept as the reference path ---------


def _leaf_sync_sparse(wn, wref, eps, e, *, hfl_cfg, axis, wire):
    """Local-shard sync for ONE leaf. wn/eps [1, *loc]; wref/e [*loc]."""
    N = hfl_cfg.num_clusters
    shape = wref.shape
    size = int(np.prod(shape)) if shape else 1
    wn0 = wn[0].astype(jnp.float32).reshape(-1)
    wref_f = wref.astype(jnp.float32).reshape(-1)
    eps_f = eps[0].reshape(-1)
    e_f = e.reshape(-1)

    # --- SBS side: drift + discounted error, top-k uplink (Alg.5 l.24-27) ---
    s = (wn0 - wref_f) + hfl_cfg.tiers[1].beta_up * eps_f
    k_ul = sp.keep_count(size, hfl_cfg.tiers[1].phi_up)
    vals, idx = sp.pack_topk(s, k_ul)
    if wire:
        vals = _wire_round(vals, wire)  # residual buffers the wire error too
    sent = sp.unpack_topk(vals, idx, size)
    new_eps = s - sent

    # --- cross-pod exchange: 2k values per hop instead of Q ---
    if wire == "bf16":
        vals = jax.lax.optimization_barrier(vals.astype(jnp.bfloat16))
    if axis is not None:
        all_vals = jax.lax.all_gather(vals, axis)  # [N, k]
        if wire == "bf16":
            all_vals = jax.lax.optimization_barrier(all_vals)
        all_idx = jax.lax.all_gather(idx, axis)
        delta = (
            jnp.zeros((size,), jnp.float32)
            .at[all_idx.reshape(-1)]
            .add(all_vals.reshape(-1).astype(jnp.float32))
            / N
        )
    else:  # single-cluster degenerate case
        delta = sent / N

    # --- MBS side: discounted error + top-k downlink (Alg.5 l.28-31) ---
    delta = delta + hfl_cfg.tiers[1].beta_down * e_f
    k_dl = sp.keep_count(size, hfl_cfg.tiers[1].phi_down)
    dvals, didx = sp.pack_topk(delta, k_dl)
    if wire:
        dvals = _wire_round(dvals, wire)
    d = sp.unpack_topk(dvals, didx, size)
    new_e = delta - d
    new_wref = wref_f + d

    # --- clusters adopt the new reference (Alg.5 l.33/43) ---
    new_wn = jnp.broadcast_to(new_wref[None], (1, size))
    return (
        new_wn.reshape((1,) + shape).astype(wn.dtype),
        new_wref.reshape(shape).astype(wref.dtype),
        new_eps.reshape((1,) + shape).astype(eps.dtype),
        new_e.reshape(shape).astype(e.dtype),
    )


def _make_leaf_local_sync(hfl_cfg, wire):
    """Single-process per-leaf sync (mesh=None): legacy reference path."""

    def local_sync(state: HFLState):
        def leaf(wn, wref, eps, e):
            N = hfl_cfg.num_clusters
            shape = wref.shape
            size = int(np.prod(shape)) if shape else 1
            wref_f = wref.astype(jnp.float32).reshape(-1)
            outs_eps, sents = [], []
            for n in range(N):  # static unroll; N is small
                s = (wn[n].astype(jnp.float32).reshape(-1) - wref_f) \
                    + hfl_cfg.tiers[1].beta_up * eps[n].reshape(-1)
                k_ul = sp.keep_count(size, hfl_cfg.tiers[1].phi_up)
                vals, idx = sp.pack_topk(s, k_ul)
                if wire:
                    vals = _wire_round(vals, wire)
                sent = sp.unpack_topk(vals, idx, size)
                outs_eps.append(s - sent)
                sents.append(sent)
            delta = sum(sents) / N + hfl_cfg.tiers[1].beta_down * e.reshape(-1)
            k_dl = sp.keep_count(size, hfl_cfg.tiers[1].phi_down)
            dvals, didx = sp.pack_topk(delta, k_dl)
            if wire:
                dvals = _wire_round(dvals, wire)
            d = sp.unpack_topk(dvals, didx, size)
            new_e = delta - d
            new_wref = wref_f + d
            new_wn = jnp.broadcast_to(new_wref[None], (N, size))
            return (
                new_wn.reshape((N,) + shape).astype(wn.dtype),
                new_wref.reshape(shape).astype(wref.dtype),
                jnp.stack(outs_eps).reshape((N,) + shape).astype(eps.dtype),
                new_e.reshape(shape).astype(e.dtype),
            )

        outs = jax.tree.map(
            leaf, state.params, state.w_ref, state.eps, state.e,
        )
        is_t = lambda t: isinstance(t, tuple)
        pick = lambda i: jax.tree.map(lambda t: t[i], outs, is_leaf=is_t)
        return state._replace(params=pick(0), w_ref=pick(1), eps=pick(2), e=pick(3))

    return local_sync


# ---- arbitrary-depth hierarchy: per-tier cascade over the flat buffer -----


class HierBufs(NamedTuple):
    """Flat f32 side buffers of the tiers between the clusters and the root
    (depth T >= 3; ``A_t = HFLConfig.agg_count(t)`` aggregators per tier).

      * ``refs[t-1]``  [A_t, Q]      tier-t reference models, t in 1..T-2
      * ``eps[t-2]``   [A_{t-1}, Q]  tier-t uplink errors,    t in 2..T-1
      * ``errs[t-1]``  [A_t, Q]      tier-t downlink errors,  t in 1..T-2

    Tier 1's uplink error is ``HFLState.eps`` and the root's reference /
    downlink error are ``HFLState.w_ref`` / ``HFLState.e`` — the depth-2
    state layout is untouched; the extra tiers ride OUTSIDE the state,
    threaded by the caller exactly like the async engine's ``e_dl``.
    """

    refs: tuple
    eps: tuple
    errs: tuple


def init_hier_bufs(state: HFLState, hfl_cfg) -> HierBufs:
    """Zero-error, reference-replicated buffers for ``HierSyncStep``."""
    T = len(hfl_cfg.tiers)
    wref, ref_spec = fl.pack(state.w_ref)
    Q = ref_spec.total
    refs = tuple(
        jnp.broadcast_to(wref[None], (hfl_cfg.agg_count(t), Q))
        for t in range(1, T - 1)
    )
    eps = tuple(
        jnp.zeros((hfl_cfg.agg_count(t - 1), Q), jnp.float32)
        for t in range(2, T)
    )
    errs = tuple(
        jnp.zeros((hfl_cfg.agg_count(t), Q), jnp.float32)
        for t in range(1, T - 1)
    )
    return HierBufs(refs=refs, eps=eps, errs=errs)


def hier_fire_top(tiers, round_idx: int) -> int:
    """Highest tier firing at (1-based) tier-1 round ``round_idx``.

    Tier 1 fires every round; tier t >= 2 fires every
    ``prod(tiers[2..t].period)`` tier-1 rounds (each tier's period counts
    rounds of the tier below it)."""
    top, stride = 1, 1
    for t in range(2, len(tiers)):
        stride *= tiers[t].period
        if round_idx % stride == 0:
            top = t
    return top


def _hier_cascade(state: HFLState, bufs: HierBufs, *, hfl_cfg, top: int,
                  wire):
    """One boundary of the tiered consensus: tiers 1..``top`` sync
    bottom-up, then every level below ``top`` adopts its (new) ancestor
    reference.

    Each tier runs the SAME drift/Ω/error-feedback protocol the two-level
    sync runs between SBS and MBS (Alg.5 l.24-31), with its own
    ``phi_up/phi_down/beta_up/beta_down``: children are grouped
    contiguously (child c of tier t-1 belongs to parent ``c // fanout_t``),
    the group mean + ``beta_down``-discounted error is Ω-sparsified on the
    downlink, and the parent reference absorbs the surviving delta. The
    depth-2 instance of this cascade is algebraically the flat local sync;
    the engine still routes depth-2 configs through the historical
    builders so that path stays bit-identical by construction.
    """
    tiers = hfl_cfg.tiers
    T = len(tiers)
    impl = hfl_cfg.omega_impl
    assert 1 <= top <= T - 1

    wn, p_spec = fl.pack_stacked(state.params)      # [N, Q]
    eps1, eps_spec = fl.pack_stacked(state.eps)     # [N, Q]
    wref, ref_spec = fl.pack(state.w_ref)           # [Q] root reference
    e_root, _ = fl.pack(state.e)
    Q = ref_spec.total

    refs = list(bufs.refs)                     # index t-1, t in 1..T-2
    epsu = [eps1] + list(bufs.eps)             # index t-1, t in 1..T-1
    errs = list(bufs.errs) + [e_root[None, :]]  # index t-1, t in 1..T-1

    child = wn  # current child models, level t-1, [A_{t-1}, Q]
    for t in range(1, top + 1):
        tc = tiers[t]
        A = hfl_cfg.agg_count(t)
        G = tc.fanout
        ref_t = refs[t - 1] if t <= T - 2 else wref[None, :]  # [A, Q]

        # --- uplink: per-child drift + discounted error, Ω(phi_up) ---
        s = child - jnp.repeat(ref_t, G, axis=0) + tc.beta_up * epsu[t - 1]
        sent_rows, eps_rows = [], []
        for r in range(A * G):  # static unroll; tier widths are small
            vals, idx = sp.pack_phi(s[r], tc.phi_up, impl=impl)
            if wire:
                vals = _wire_round(vals, wire)
            sent = sp.unpack_topk(vals, idx, Q)
            sent_rows.append(sent)
            eps_rows.append(s[r] - sent)
        sent = jnp.stack(sent_rows).reshape(A, G, Q)
        epsu[t - 1] = jnp.stack(eps_rows)

        # --- aggregator: group consensus + discounted error, Ω(phi_down) ---
        delta = sent.mean(axis=1) + tc.beta_down * errs[t - 1]  # [A, Q]
        d_rows, e_rows = [], []
        for a in range(A):
            dvals, didx = sp.pack_phi(delta[a], tc.phi_down, impl=impl)
            if wire:
                dvals = _wire_round(dvals, wire)
            d = sp.unpack_topk(dvals, didx, Q)
            d_rows.append(d)
            e_rows.append(delta[a] - d)
        new_ref = ref_t + jnp.stack(d_rows)
        errs[t - 1] = jnp.stack(e_rows)
        if t <= T - 2:
            refs[t - 1] = new_ref
        else:
            wref = new_ref[0]
        child = new_ref

    # --- downward adoption: every level below ``top`` adopts its new
    #     ancestor reference (Alg.5 l.33/43 applied per subtree) ---
    adopt = child  # [A_top, Q]
    for t in range(top, 0, -1):
        adopt = jnp.repeat(adopt, tiers[t].fanout, axis=0)  # -> [A_{t-1}, Q]
        if t - 1 >= 1:
            refs[t - 2] = adopt

    new_state = state._replace(
        params=fl.unpack_stacked(adopt, p_spec),
        eps=fl.unpack_stacked(epsu[0], eps_spec),
        w_ref=(fl.unpack(wref, ref_spec) if top == T - 1 else state.w_ref),
        e=(fl.unpack(errs[T - 2][0], ref_spec) if top == T - 1 else state.e),
    )
    new_bufs = HierBufs(
        refs=tuple(refs),
        eps=tuple(epsu[1:]),
        errs=tuple(errs[:T - 2]),
    )
    return new_state, new_bufs


def _subtree_width(tiers, lo: int, hi: int) -> int:
    """Tier-``lo`` rows under ONE tier-``hi`` aggregator:
    ``prod(fanout of tiers lo+1..hi)`` (1 when ``lo == hi``)."""
    out = 1
    for t in range(lo + 1, hi + 1):
        out *= tiers[t].fanout
    return out


def _hier_unit_sync(state: HFLState, bufs: HierBufs, *, hfl_cfg, cut: int,
                    u: int, utop: int, wire):
    """Within-unit consensus for mixed-discipline runs: boundaries
    ``1..utop`` of the subtree under unit ``u`` (one tier-``cut-1``
    aggregator, where ``cut`` is the lowest async boundary) sync bottom-up
    and adopt downward, while every other unit's state is untouched. The
    depth-3 ``cut=2`` instance is the historical per-edge tier-1 group
    sync; deeper trees cascade the same drift/Ω/error-feedback protocol
    over as many synchronous boundaries as fired this unit round."""
    tiers = hfl_cfg.tiers
    T = len(tiers)
    impl = hfl_cfg.omega_impl
    assert 1 <= utop <= cut - 1 <= T - 2

    wn, p_spec = fl.pack_stacked(state.params)
    eps1, eps_spec = fl.pack_stacked(state.eps)
    Q = wn.shape[1]

    refs = list(bufs.refs)                 # index t-1, t in 1..T-2
    epsu = [eps1] + list(bufs.eps)         # index t-1, t in 1..T-1
    errs = list(bufs.errs)                 # index t-1, t in 1..T-2

    child = wn
    child_rows = [u * _subtree_width(tiers, 0, cut - 1) + j
                  for j in range(_subtree_width(tiers, 0, cut - 1))]
    for t in range(1, utop + 1):
        tc = tiers[t]
        G = tc.fanout
        W = _subtree_width(tiers, t, cut - 1)  # tier-t parents in the unit
        rows = [u * W + a for a in range(W)]
        for a_i, a in enumerate(rows):
            sent_rows = []
            for j in range(G):
                c = child_rows[a_i * G + j]
                s = child[c] - refs[t - 1][a] + tc.beta_up * epsu[t - 1][c]
                vals, idx = sp.pack_phi(s, tc.phi_up, impl=impl)
                if wire:
                    vals = _wire_round(vals, wire)
                sent = sp.unpack_topk(vals, idx, Q)
                sent_rows.append(sent)
                epsu[t - 1] = epsu[t - 1].at[c].set(s - sent)
            delta = (jnp.stack(sent_rows).mean(axis=0)
                     + tc.beta_down * errs[t - 1][a])
            dvals, didx = sp.pack_phi(delta, tc.phi_down, impl=impl)
            if wire:
                dvals = _wire_round(dvals, wire)
            d = sp.unpack_topk(dvals, didx, Q)
            refs[t - 1] = refs[t - 1].at[a].set(refs[t - 1][a] + d)
            errs[t - 1] = errs[t - 1].at[a].set(delta - d)
        child = refs[t - 1]
        child_rows = rows

    # downward adoption within the unit: every level below ``utop`` adopts
    # its (new) ancestor reference, exactly like the global cascade
    Wt = _subtree_width(tiers, utop, cut - 1)
    adopt = refs[utop - 1][u * Wt:(u + 1) * Wt]
    for t in range(utop, 0, -1):
        adopt = jnp.repeat(adopt, tiers[t].fanout, axis=0)
        lo = u * _subtree_width(tiers, t - 1, cut - 1)
        if t - 1 >= 1:
            refs[t - 2] = refs[t - 2].at[lo:lo + adopt.shape[0]].set(adopt)
    wn = wn.at[lo:lo + adopt.shape[0]].set(adopt)

    state = state._replace(
        params=fl.unpack_stacked(wn, p_spec),
        eps=fl.unpack_stacked(epsu[0], eps_spec),
    )
    new_bufs = HierBufs(refs=tuple(refs), eps=tuple(epsu[1:]),
                        errs=tuple(errs))
    return state, new_bufs


def _hier_push(state: HFLState, bufs: HierBufs, weight, *, hfl_cfg, t: int,
               a: int, wire):
    """Staleness-weighted async push across boundary ``t``: tier-``t-1``
    aggregator ``a`` (a cluster when ``t == 1``) Ω(phi_up)-pushes its drift
    with its boundary-``t`` error buffer, the parent reference absorbs the
    ``weight``-discounted delta, and ``a``'s whole subtree densely adopts
    the fresh parent (the async engine's historical dense-DL contract,
    applied at whatever level the boundary sits). The depth-3 root push is
    the ``t = T-1`` instance."""
    tiers = hfl_cfg.tiers
    T = len(tiers)
    tc = tiers[t]
    impl = hfl_cfg.omega_impl
    p = a // tc.fanout

    wn, p_spec = fl.pack_stacked(state.params)
    eps1, eps_spec = fl.pack_stacked(state.eps)
    Q = wn.shape[1]
    refs = list(bufs.refs)
    epsu = [eps1] + list(bufs.eps)

    child_ref = wn[a] if t == 1 else refs[t - 2][a]
    if t == T - 1:
        wref, ref_spec = fl.pack(state.w_ref)
        parent_ref = wref
    else:
        parent_ref = refs[t - 1][p]

    s = child_ref - parent_ref + tc.beta_up * epsu[t - 1][a]
    vals, idx = sp.pack_phi(s, tc.phi_up, impl=impl)
    if wire:
        vals = _wire_round(vals, wire)
    sent = sp.unpack_topk(vals, idx, Q)
    new_pref = parent_ref + weight * sent
    epsu[t - 1] = epsu[t - 1].at[a].set(s - sent)
    if t < T - 1:
        refs[t - 1] = refs[t - 1].at[p].set(new_pref)

    # dense downward adoption of the fresh parent through a's subtree
    for tt in range(t - 1, 0, -1):
        W = _subtree_width(tiers, tt, t - 1)
        refs[tt - 1] = refs[tt - 1].at[a * W:(a + 1) * W].set(
            jnp.broadcast_to(new_pref, (W, Q)))
    W0 = _subtree_width(tiers, 0, t - 1)
    wn = wn.at[a * W0:(a + 1) * W0].set(jnp.broadcast_to(new_pref, (W0, Q)))

    state = state._replace(
        params=fl.unpack_stacked(wn, p_spec),
        eps=fl.unpack_stacked(epsu[0], eps_spec),
        w_ref=(fl.unpack(new_pref, ref_spec) if t == T - 1 else state.w_ref),
    )
    new_bufs = bufs._replace(refs=tuple(refs), eps=tuple(epsu[1:]))
    return state, new_bufs


class HierSyncStep:
    """Tiered consensus for depth > 2: ``(state, bufs, top=...) ->
    (state, bufs)``.

    One jitted program per distinct ``top`` boundary (there are at most
    depth-1 of them), each donating both the state and the tier buffers.
    Build the initial buffers with :meth:`init_bufs`; ``top`` defaults to
    a full root sync. The engine detects this object via the ``hier``
    attribute and threads the buffers through the run loop.
    """

    hier = True
    collect_stats = False

    def __init__(self, hfl_cfg):
        if hfl_cfg.sync_mode not in ("sparse", "quantized_sparse"):
            raise ValueError(
                "depth > 2 hierarchies run the sparse consensus only "
                f"(sync_mode={hfl_cfg.sync_mode!r})")
        if hfl_cfg.omega_impl == "fused":
            raise ValueError(
                "omega_impl='fused' is depth-2 only; use 'topk'/'hist' "
                "for deeper hierarchies")
        _count_build("sync_step", mode=hfl_cfg.sync_mode, layout="hier",
                     impl=hfl_cfg.omega_impl)
        self.cfg = hfl_cfg
        self._wire = wire_format_of(hfl_cfg)
        self._fns = {}
        self._unit_fns = ({}, {})

    def init_bufs(self, state: HFLState) -> HierBufs:
        return init_hier_bufs(state, self.cfg)

    def fire_top(self, round_idx: int) -> int:
        return hier_fire_top(self.cfg.tiers, round_idx)

    def __call__(self, state: HFLState, bufs: HierBufs, top: int = None):
        if top is None:
            top = len(self.cfg.tiers) - 1
        fn = self._fns.get(top)
        if fn is None:
            fn = jax.jit(
                partial(_hier_cascade, hfl_cfg=self.cfg, top=top,
                        wire=self._wire),
                donate_argnums=(0, 1),
            )
            self._fns[top] = fn
        return fn(state, bufs)

    def unit_ops(self, cut: int):
        """Mixed-discipline helpers for an async top suffix starting at
        boundary ``cut`` -> ``(unit_sync, push)``.

        ``unit_sync(state, bufs, u, utop)`` runs boundaries ``1..utop`` of
        the subtree under unit ``u`` (one tier-``cut-1`` aggregator) as a
        synchronous within-unit cascade; ``push(state, bufs, t, a, weight)``
        async-pushes tier-``t-1`` aggregator ``a`` across boundary ``t``
        with a staleness weight. One jitted donating program per distinct
        ``(u, utop)`` / ``(t, a)`` — unit and aggregator counts are small.
        The depth-3 async-root case is ``cut = 2``: per-edge tier-1 syncs
        plus ``t = 2`` root pushes."""
        if not 1 <= cut <= len(self.cfg.tiers) - 1:
            raise ValueError(f"cut={cut} out of range for depth "
                             f"{len(self.cfg.tiers)}")
        sync_fns, push_fns = self._unit_fns

        def unit_sync(state, bufs, u: int, utop: int = None):
            utop = cut - 1 if utop is None else int(utop)
            key = (int(u), utop)
            fn = sync_fns.get(key)
            if fn is None:
                fn = jax.jit(
                    partial(_hier_unit_sync, hfl_cfg=self.cfg, cut=cut,
                            u=int(u), utop=utop, wire=self._wire),
                    donate_argnums=(0, 1))
                sync_fns[key] = fn
            return fn(state, bufs)

        def push(state, bufs, t: int, a: int, weight: float):
            key = (int(t), int(a))
            fn = push_fns.get(key)
            if fn is None:
                fn = jax.jit(
                    partial(_hier_push, hfl_cfg=self.cfg, t=int(t),
                            a=int(a), wire=self._wire),
                    donate_argnums=(0, 1))
                push_fns[key] = fn
            return fn(state, bufs, jnp.float32(weight))
        return unit_sync, push


# ---- builder --------------------------------------------------------------


def wire_format_of(hfl_cfg) -> "str | None":
    """Wire value rounding of a config: ``None`` for exact-f32 modes, the
    configured ``wire_format`` (bf16 | q8) under ``quantized_sparse``."""
    if hfl_cfg.sync_mode != "quantized_sparse":
        return None
    return getattr(hfl_cfg, "wire_format", "bf16")


def jit_sync_step(sync_step):
    """Jit a sync step with the whole ``HFLState`` donated.

    Every sync consumes-and-replaces all six state buffers (params, opt,
    w_ref, eps, e, step), so the input state is dead the moment the call
    returns — donating it lets XLA reuse those buffers for the outputs and
    cuts the sync's peak memory by up to the full state footprint (3 extra
    model-sized error/reference buffers on top of params+opt). Callers must
    rebind: ``state = sync(state)``; touching the old state afterwards
    raises on deleted buffers.

    Inputs the sync does not read are kept (``keep_unused``): the dense
    sync reads ``w_ref`` only for its dtype, and were it pruned, JAX would
    hand its donated slot to the next buffer of the same shape, ``e``,
    which XLA then copies whole to return it unchanged.

    A sync built with ``collect_stats=True`` returns ``(state, stats)``;
    the flag is propagated onto the jitted callable so callers handed a
    pre-built step (the engine) can detect the return shape with
    ``getattr(sync, "collect_stats", False)``.

    A :class:`HierSyncStep` (depth > 2) manages its own per-boundary
    jitted programs (state AND tier buffers donated) and passes through
    unchanged, so the ``jit_sync_step(make_sync(...))`` idiom works at
    any depth.
    """
    if getattr(sync_step, "hier", False):
        return sync_step
    jitted = jax.jit(sync_step, donate_argnums=0, keep_unused=True)
    jitted.collect_stats = bool(getattr(sync_step, "collect_stats", False))
    return jitted


@dataclass(frozen=True)
class SyncPlan:
    """Resolved spec of ONE consensus step build — the single argument of
    :func:`make_sync`.

    ``make_sync_step``'s keyword surface grew one knob per subsystem
    (mesh, param_specs, layout override, collect_stats, …); a plan bundles
    them so call sites carry one object and new knobs stop rippling
    through every caller's signature. ``SyncPlan.from_config(hfl_cfg)``
    is the common case; everything else defaults.

      * ``hfl``           the :class:`HFLConfig` (tiers, mode, Ω impl, …)
      * ``mesh``          None -> single-process; a mesh with a "pod" axis
                          runs the per-device shard_map exchange
      * ``param_specs``   pytree of PartitionSpec (no leading cluster
                          axis); required for sparse modes on a pod mesh
      * ``layout``        overrides ``hfl.sync_layout`` ("flat" | "leaf")
      * ``collect_stats`` also return in-jit learning-health statistics
                          (local dense/flat-topk/flat-fused paths only)
    """

    hfl: Any
    mesh: Any = None
    param_specs: Any = None
    layout: Optional[str] = None
    collect_stats: bool = False

    @classmethod
    def from_config(cls, hfl_cfg, *, mesh=None, param_specs=None,
                    layout=None, collect_stats: bool = False) -> "SyncPlan":
        return cls(hfl=hfl_cfg, mesh=mesh, param_specs=param_specs,
                   layout=layout, collect_stats=collect_stats)


_make_sync_step_warned = False


def make_sync_step(hfl_cfg, mesh=None, param_specs=None, *, layout=None,
                   collect_stats: bool = False):
    """Deprecated keyword-surface wrapper: build a :class:`SyncPlan` and
    call :func:`make_sync` instead. Warns once per process; behaviour is
    unchanged (the plan carries exactly these arguments)."""
    global _make_sync_step_warned
    if not _make_sync_step_warned:
        _make_sync_step_warned = True
        warnings.warn(
            "make_sync_step(hfl_cfg, mesh=..., param_specs=..., "
            "layout=..., collect_stats=...) is deprecated; build a "
            "SyncPlan (SyncPlan.from_config) and call make_sync(plan)",
            DeprecationWarning, stacklevel=2)
    return make_sync(SyncPlan(hfl=hfl_cfg, mesh=mesh,
                              param_specs=param_specs, layout=layout,
                              collect_stats=collect_stats))


def make_sync(plan: SyncPlan):
    """Build the consensus step described by ``plan``.

    Depth-2 configs keep the historical two-level builders (bit-identical
    to the pre-tier code); depth > 2 returns a :class:`HierSyncStep`
    running the per-tier cascade (single-process flat layout only).

    ``mesh=None`` -> single-process (tests/CPU); the cluster axis is then
    a plain leading axis and the exchange is a concatenation instead of
    an all-gather. ``param_specs`` is required for sparse modes on a mesh
    with a "pod" axis.

    Flat-layout routing by Ω impl and mesh:

      * ``omega_impl="fused"`` + no mesh: the batched fused local sync
        (2 top-k + 2 scatter-add launches per sync, selection
        bit-identical to ``topk``). With ``hfl_cfg.flat_shards > 1`` the
        padded flat vector is processed as that many contiguous shards —
        the single-process emulation of the mesh-sharded path.
      * ``omega_impl="fused"`` + a pod-less mesh with >1 ("data",
        "model") extent: the flat vector itself shards over those axes
        (``_make_flat_sharded_sync``) — per-shard fused compaction, one
        all-gather of compacted candidates, no whole-vector
        materialization per device.
      * other impls keep their historical paths (local whole-vector, or
        the per-device "pod" shard_map on pod meshes).

    ``collect_stats=True`` makes the returned sync also return an in-jit
    learning-health statistics dict (``_flat_sync_stats``; the sync
    becomes ``state -> (state, stats)``). Supported on the local dense,
    flat-topk and flat-fused paths — the ones the simulator drives;
    sharded/mesh/leaf layouts raise.
    """
    hfl_cfg = plan.hfl
    mesh, param_specs = plan.mesh, plan.param_specs
    layout, collect_stats = plan.layout, plan.collect_stats
    if len(hfl_cfg.tiers) > 2:
        if mesh is not None:
            raise ValueError(
                "depth > 2 hierarchies are single-process only (mesh=None)")
        if collect_stats:
            raise ValueError(
                "collect_stats is not supported on the hierarchical "
                "cascade (depth-2 local flat paths only)")
        if (layout or getattr(hfl_cfg, "sync_layout", "flat")) != "flat":
            raise ValueError(
                "depth > 2 hierarchies run the flat layout only")
        return HierSyncStep(hfl_cfg)
    mode = hfl_cfg.sync_mode
    _count_build(
        "sync_step", mode=mode,
        layout=(layout or getattr(hfl_cfg, "sync_layout", "flat")),
        impl=hfl_cfg.omega_impl)
    if mode == "dense":
        N = hfl_cfg.num_clusters

        def dense_sync(state: HFLState):
            with jax.named_scope("sync.exchange"):
                w_mean = jax.tree.map(
                    lambda p: jnp.mean(p.astype(jnp.float32), axis=0),
                    state.params)
            with jax.named_scope("sync.merge"):
                new_params = jax.tree.map(
                    lambda m, p: jnp.broadcast_to(m[None].astype(p.dtype),
                                                  p.shape),
                    w_mean,
                    state.params,
                )
                # cast back to the buffer dtype chosen at hfl_init: writing
                # the f32 mean verbatim would flip a bf16 w_ref to f32 after
                # the first sync and retrace every jitted step each period
                new_wref = jax.tree.map(
                    lambda m, r: m.astype(r.dtype), w_mean, state.w_ref
                )
            new_state = state._replace(params=new_params, w_ref=new_wref)
            if not collect_stats:
                return new_state
            # dense averaging has no Ω or error feedback: drift and the
            # applied update are the meaningful signals, the residual
            # norms are identically zero (no index keys — the monitor
            # skips overlap when they are absent)
            wn, _ = fl.pack_stacked(state.params)
            wref_old, _ = fl.pack(state.w_ref)
            wbar = jnp.mean(wn, axis=0)
            wnorm = jnp.maximum(jnp.linalg.norm(wbar), 1e-30)
            stats = {
                "drift": jnp.linalg.norm(wn - wbar[None, :], axis=1) / wnorm,
                "eps_norm": jnp.zeros((N,), jnp.float32),
                "e_norm": jnp.zeros((), jnp.float32),
                "wref_norm": jnp.linalg.norm(wbar),
                "update_norm": jnp.linalg.norm(wbar - wref_old),
            }
            return new_state, stats

        dense_sync.collect_stats = collect_stats
        return dense_sync

    wire = wire_format_of(hfl_cfg)
    if mode not in ("sparse", "quantized_sparse"):
        raise ValueError(mode)
    layout = layout or getattr(hfl_cfg, "sync_layout", "flat")
    if layout not in ("flat", "leaf"):
        raise ValueError(layout)

    has_pod = mesh is not None and "pod" in mesh.axis_names

    def _no_stats(path: str):
        if collect_stats:
            raise ValueError(
                f"collect_stats is not supported on the {path} sync path "
                f"(local flat topk/fused and dense only)")

    if not has_pod:
        # Single-pod / CPU path: emulate the cluster axis locally. The
        # protocol still follows Alg.5 exactly; the "exchange" is a local sum.
        flat_shards = int(getattr(hfl_cfg, "flat_shards", 1))
        if layout == "flat":
            fused = hfl_cfg.omega_impl == "fused"
            if mesh is not None and fused:
                span = int(np.prod([
                    mesh.shape[a] for a in ("data", "model")
                    if a in mesh.axis_names
                ]))
                if span > 1:
                    _no_stats("mesh-sharded flat")
                    return _make_flat_sharded_sync(hfl_cfg, wire, mesh)
            if flat_shards > 1:
                if not fused:
                    raise ValueError(
                        "flat_shards > 1 requires omega_impl='fused' (the "
                        "sharded flat sync is built on the fused per-shard "
                        "compaction)")
                _no_stats("sharded flat")
                return _make_flat_sharded_local_sync(hfl_cfg, wire,
                                                     flat_shards)
            if fused:
                sync = _make_flat_fused_local_sync(hfl_cfg, wire,
                                                   collect_stats)
            else:
                sync = _make_flat_local_sync(hfl_cfg, wire, collect_stats)
            sync.collect_stats = collect_stats
            return sync
        _no_stats("leaf")
        return _make_leaf_local_sync(hfl_cfg, wire)

    # --- multi-pod: fully-manual shard_map, per-shard top-k, pod all-gather ---
    _no_stats("pod shard_map")
    assert param_specs is not None, "sparse sync on a pod mesh needs param_specs"
    P = jax.sharding.PartitionSpec

    def with_pod(spec):
        return P("pod", *spec)

    def no_pod(spec):
        return P(*spec)

    in_specs = (
        jax.tree.map(with_pod, param_specs),
        jax.tree.map(no_pod, param_specs),
        jax.tree.map(with_pod, param_specs),
        jax.tree.map(no_pod, param_specs),
    )
    out_specs = in_specs

    if layout == "flat":
        _sync_all = partial(_flat_shard_sync, hfl_cfg=hfl_cfg, wire=wire)
    else:

        def _sync_all(params, w_ref, eps, e):
            outs = jax.tree.map(
                partial(_leaf_sync_sparse, hfl_cfg=hfl_cfg, axis="pod", wire=wire),
                params, w_ref, eps, e,
            )
            is_t = lambda t: isinstance(t, tuple)
            pick = lambda i: jax.tree.map(lambda t: t[i], outs, is_leaf=is_t)
            return pick(0), pick(1), pick(2), pick(3)

    sync_sm = jaxcompat.shard_map(
        _sync_all, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )

    def sparse_sync(state: HFLState):
        params, w_ref, eps, e = sync_sm(state.params, state.w_ref, state.eps, state.e)
        return state._replace(params=params, w_ref=w_ref, eps=eps, e=e)

    return sparse_sync
