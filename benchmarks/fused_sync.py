"""Fused flat-buffer whole-model sync vs topk-flat vs leaf-wise reference.

Grid: {sparse, quantized_sparse} x {paper-fig5 fronthaul φ=0.9, headline
compression φ=0.99}, three sync paths each:

  * ``leaf``       — legacy per-leaf Ω (60 top-k / 60 scatter launches)
  * ``flat/topk``  — whole-model Ω applied as a mask, its exact top-k set
                     found by a counting radix select (no ``top_k``,
                     no scatter-add)
  * ``flat/fused`` — the ``kernels/fused_sync`` path: batched threshold →
                     compact → small-top-k finisher, bit-identical Ω
                     selection to ``topk`` at 2 top-k + 2 scatter-add
                     launches per sync regardless of N or leaf count

Measurements:

  1. LAUNCH COUNT — ``top_k`` / ``scatter-add`` primitives in the traced
     program. The hardware-relevant metric: on a pod mesh every such
     launch is a dispatch (and for the exchange, a collective) with a
     latency floor. Deterministic, gated in BENCH_fused.json.
  2. STEADY-STATE WALL-CLOCK — donated jit (``jit_sync_step``, the
     production configuration), round-robin across the three paths so
     host load drift hits them equally. CPU caveat (unchanged from
     PR 1): XLA-CPU TopK favors many small cache-resident buffers and
     the leaf path pays no flat pack/unpack, so leaf stays ahead on this
     backend — the fused path's win here is vs the flat/topk path it
     replaces; launch count is the TPU metric.
  3. BUILD TIME — trace + compile + first run.
  4. Ω FIDELITY — overlap of each path's uplink selection with the
     paper's whole-model top-k (flat paths exact by construction; fused
     verified bit-identical to topk).

  PYTHONPATH=src python -m benchmarks.fused_sync
"""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HFLConfig, ModelConfig
from repro.core import sparsify as sp
from repro.core.hfl import hfl_init, jit_sync_step, make_sync_step
from repro.models.transformer import init_model
from repro.optim import SGDM
from repro.utils import flatten as fl


def _bench_cfg():
    """Small but genuinely multi-leaf transformer (embeddings + blocks)."""
    return ModelConfig(name="bench", arch_type="dense", num_layers=4,
                       d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                       vocab_size=1024, dtype="float32", remat=False)


def _count_primitives(fn, state):
    txt = str(jax.make_jaxpr(fn)(state))
    return {
        "top_k": len(re.findall(r"\btop_k\[", txt)),
        "scatter_add": len(re.findall(r"\bscatter-add\[", txt)),
    }


def _fresh_state(hfl):
    params = init_model(jax.random.PRNGKey(0), _bench_cfg())
    state = hfl_init(params, SGDM(momentum=0.9), hfl)
    # desynchronise clusters so the sync has real work to do
    return state._replace(params=jax.tree.map(
        lambda p: p + 0.01 * jax.random.normal(
            jax.random.PRNGKey(p.ndim), p.shape).astype(p.dtype),
        state.params))


def _build(fn, hfl):
    """-> (donated jit fn, live state, build seconds). The timer covers
    trace + compile + first run only — state construction stays outside."""
    fresh = _fresh_state(hfl)
    jax.block_until_ready(fresh.params)
    t0 = time.perf_counter()
    jit_fn = jit_sync_step(fn)
    state = jit_fn(fresh)
    jax.block_until_ready(state.params)
    return jit_fn, state, time.perf_counter() - t0


def _steady_round_robin(entries, iters=8):
    """Interleave the paths' timed iterations so host-load drift is shared.

    ``entries``: dict name -> (jit_fn, state). Returns name -> ms/iter.
    """
    acc = {name: 0.0 for name in entries}
    states = {name: st for name, (_, st) in entries.items()}
    for _ in range(iters):
        for name, (jit_fn, _) in entries.items():
            t0 = time.perf_counter()
            states[name] = jit_fn(states[name])
            jax.block_until_ready(states[name].params)
            acc[name] += time.perf_counter() - t0
    return {name: acc[name] / iters * 1e3 for name in entries}


def _omega_fidelity(state, hfl):
    """Selection overlap with the paper's whole-model top-k Ω for cluster
    0's drift: (fused == topk exact-match flag, flat overlap, leaf
    overlap)."""
    wref, spec = fl.pack(state.w_ref)
    wn, _ = fl.pack_stacked(state.params)
    s0 = wn[0] - wref
    k = sp.keep_count(spec.total, hfl.tiers[1].phi_up)
    _, exact_idx = sp.pack_topk(s0, k)
    exact = set(np.asarray(exact_idx).tolist())
    _, fused_idx = sp.pack_phi(s0, hfl.tiers[1].phi_up, impl="fused")
    fused_identical = exact == set(np.asarray(fused_idx).tolist())
    leaf_sel = []
    for i in range(len(spec.sizes)):
        sl = spec.leaf_slice(i)
        kk = sp.keep_count(spec.sizes[i], hfl.tiers[1].phi_up)
        _, li = sp.pack_topk(s0[sl], kk)
        leaf_sel.extend((np.asarray(li) + sl.start).tolist())
    leaf = len(exact & set(leaf_sel)) / k
    return bool(fused_identical), leaf


def run(clusters: int = 4, iters: int = 8):
    params = init_model(jax.random.PRNGKey(0), _bench_cfg())
    num_leaves = len(jax.tree.leaves(params))
    rows = []
    for mode in ("sparse", "quantized_sparse"):
        for phi in (0.9, 0.99):
            mk = lambda impl: HFLConfig(
                num_clusters=clusters, mus_per_cluster=1, period=4,
                sync_mode=mode, omega_impl=impl,
                phi_sbs_ul=phi, phi_mbs_dl=phi)
            leaf_sync = make_sync_step(mk("topk"), mesh=None, layout="leaf")
            topk_sync = make_sync_step(mk("topk"), mesh=None, layout="flat")
            fused_sync = make_sync_step(mk("fused"), mesh=None, layout="flat")

            probe = _fresh_state(mk("topk"))
            launches = {
                name: _count_primitives(fn, probe)
                for name, fn in (("leaf", leaf_sync), ("topk", topk_sync),
                                 ("fused", fused_sync))
            }
            fused_exact, fid_leaf = _omega_fidelity(probe, mk("fused"))

            entries, builds = {}, {}
            for name, fn in (("leaf", leaf_sync), ("topk", topk_sync),
                             ("fused", fused_sync)):
                jit_fn, st, b = _build(fn, mk("fused" if name == "fused"
                                              else "topk"))
                entries[name] = (jit_fn, st)
                builds[name] = b
            steady = _steady_round_robin(entries, iters=iters)

            rows.append((
                f"{mode}/phi={phi}/N={clusters}/leaves={num_leaves}",
                dict(
                    leaf_topk_launches=launches["leaf"]["top_k"],
                    leaf_scatter_launches=launches["leaf"]["scatter_add"],
                    flat_topk_launches=launches["topk"]["top_k"],
                    flat_scatter_launches=launches["topk"]["scatter_add"],
                    fused_topk_launches=launches["fused"]["top_k"],
                    fused_scatter_launches=launches["fused"]["scatter_add"],
                    leaf_ms=steady["leaf"],
                    flat_topk_ms=steady["topk"],
                    fused_ms=steady["fused"],
                    fused_over_topk=steady["fused"] / steady["topk"],
                    fused_over_leaf=steady["fused"] / steady["leaf"],
                    leaf_build_s=builds["leaf"],
                    fused_build_s=builds["fused"],
                    fused_mask_identical=fused_exact,
                    fidelity_leaf=fid_leaf,
                ),
            ))
    return rows


def artifact(rows):
    """BENCH_fused.json tree. Gated (deterministic): the fused path's
    top-k/scatter launch counts. Informational: wall-clocks and their
    ratios (host-dependent — see the module docstring's CPU caveat)."""
    out = {}
    for tag, m in rows:
        out[tag] = {
            "fused_topk_launches": m["fused_topk_launches"],
            "fused_scatter_launches": m["fused_scatter_launches"],
            "flat_topk_launches": m["flat_topk_launches"],
            "leaf_topk_launches": m["leaf_topk_launches"],
            "fused_mask_identical": int(m["fused_mask_identical"]),
            "steady_ms": {
                "leaf": m["leaf_ms"],
                "flat_topk": m["flat_topk_ms"],
                "fused": m["fused_ms"],
            },
            "fused_over_topk": m["fused_over_topk"],
            "fused_over_leaf": m["fused_over_leaf"],
        }
    return out


def main():
    print("# fused flat-buffer sync vs topk-flat vs leaf-wise reference")
    print("# launches from the traced program; times are donated-jit CPU "
          "(see module docstring for the XLA-CPU TopK caveat)")
    for tag, m in run():
        print(
            f"sync/{tag},"
            f"topk_launches={m['leaf_topk_launches']}->"
            f"{m['flat_topk_launches']}->{m['fused_topk_launches']},"
            f"scatter={m['leaf_scatter_launches']}->"
            f"{m['flat_scatter_launches']}->{m['fused_scatter_launches']},"
            f"steady={m['leaf_ms']:.0f}/{m['flat_topk_ms']:.0f}/"
            f"{m['fused_ms']:.0f}ms(leaf/topk/fused),"
            f"fused_over_topk={m['fused_over_topk']:.2f},"
            f"fused_over_leaf={m['fused_over_leaf']:.2f},"
            f"build={m['leaf_build_s']:.2f}s->{m['fused_build_s']:.2f}s,"
            f"mask_identical={m['fused_mask_identical']},"
            f"fidelity_leaf={m['fidelity_leaf']:.4f}")


if __name__ == "__main__":
    main()
