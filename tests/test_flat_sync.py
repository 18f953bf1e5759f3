"""Flat-buffer whole-model sync engine: equivalence vs the leaf-wise
reference path, flatten round-trips, and regressions for the zero-vector
hist threshold and the dense-sync buffer-dtype drift."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HFLConfig, ModelConfig
from repro.core import sparsify as sp
from repro.core.hfl import (SyncPlan, _pack_drift, _wire_round, hfl_init,
                            make_sync, make_sync_step, wire_format_of)
from repro.models.transformer import init_model
from repro.obs.metrics import MetricsRegistry, use_registry
from repro.optim import SGDM
from repro.utils import flatten as fl


def _tiny_cfg():
    return ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=61,
                       dtype="float32", remat=False)


def _multi_leaf_state(hfl, seed=0, buffer_dtype=jnp.float32):
    params = init_model(jax.random.PRNGKey(seed), _tiny_cfg())
    state = hfl_init(params, SGDM(momentum=0.9), hfl, buffer_dtype=buffer_dtype)
    # desynchronise clusters and give the error buffers some history
    key = jax.random.PRNGKey(seed + 1)
    perturb = lambda p, k, s: p + s * jax.random.normal(k, p.shape).astype(p.dtype)
    keys = iter(jax.random.split(key, 3 * len(jax.tree.leaves(state.params))))
    state = state._replace(
        params=jax.tree.map(lambda p: perturb(p, next(keys), 0.1), state.params),
        eps=jax.tree.map(lambda p: perturb(p, next(keys), 0.01), state.eps),
        e=jax.tree.map(lambda p: perturb(p, next(keys), 0.01), state.e),
    )
    return state


# ---------------------------------------------------------------------------
# flatten.py round-trips
# ---------------------------------------------------------------------------


def test_flatten_roundtrip_mixed_dtypes():
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": jnp.ones((4,), jnp.bfloat16),
        "c": jnp.asarray(2.5, jnp.float32),  # scalar leaf
    }
    vec, spec = fl.pack(tree)
    assert vec.shape == (6 + 4 + 1,) and vec.dtype == jnp.float32
    assert spec.offsets == (0, 6, 10) and spec.total == 11
    out = jax.tree.map(lambda x: x, fl.unpack(vec, spec))
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        np.testing.assert_allclose(np.asarray(out[k], np.float32),
                                   np.asarray(tree[k], np.float32))


def test_flatten_stacked_roundtrip():
    n = 3
    tree = {"w": jnp.arange(n * 8, dtype=jnp.float32).reshape(n, 2, 4),
            "b": jnp.ones((n, 5), jnp.bfloat16)}
    mat, spec = fl.pack_stacked(tree)
    assert mat.shape == (n, 13)
    out = fl.unpack_stacked(mat, spec)
    assert out["w"].shape == (n, 2, 4) and out["b"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    # row layout matches the axis-free pack of one cluster's tree
    row0, spec0 = fl.pack(jax.tree.map(lambda x: x[0], tree))
    np.testing.assert_array_equal(np.asarray(mat[0]), np.asarray(row0))
    assert spec0.offsets == spec.offsets


# ---------------------------------------------------------------------------
# flat vs leaf-wise equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["sparse", "quantized_sparse"])
def test_flat_equals_leaf_on_single_leaf_model(mode):
    """With one leaf, whole-model Ω and per-leaf Ω are the same operator —
    the two layouts must agree to the bit."""
    N, Q = 3, 512
    hfl = HFLConfig(num_clusters=N, mus_per_cluster=1, period=1,
                    sync_mode=mode, phi_sbs_ul=0.9, phi_mbs_dl=0.8,
                    beta_s=0.5, beta_m=0.2)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (Q,))}
    state = hfl_init(params, SGDM(), hfl)
    state = state._replace(
        params=jax.tree.map(
            lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(1), p.shape),
            state.params),
        eps=jax.tree.map(
            lambda p: 0.01 * jax.random.normal(jax.random.PRNGKey(2), p.shape),
            state.eps),
        e=jax.tree.map(
            lambda p: 0.01 * jax.random.normal(jax.random.PRNGKey(3), p.shape),
            state.e),
    )
    out_leaf = make_sync_step(hfl, mesh=None, layout="leaf")(state)
    out_flat = make_sync_step(hfl, mesh=None, layout="flat")(state)
    for name in ("params", "w_ref", "eps", "e"):
        for a, b in zip(jax.tree.leaves(getattr(out_leaf, name)),
                        jax.tree.leaves(getattr(out_flat, name))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


def test_flat_equals_leaf_on_single_leaf_model_q8():
    """The q8 wire too: its scale is max|·| over the kept entries, whether
    they ride a payload (leaf) or stay in place under a mask (flat)."""
    N, Q = 3, 512
    hfl = HFLConfig(num_clusters=N, mus_per_cluster=1, period=1,
                    sync_mode="quantized_sparse", wire_format="q8",
                    phi_sbs_ul=0.9, phi_mbs_dl=0.8, beta_s=0.5, beta_m=0.2)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (Q,))}
    state = hfl_init(params, SGDM(), hfl)
    noise = lambda i, s: s * jax.random.normal(jax.random.PRNGKey(i), (N, Q))
    state = state._replace(params={"w": state.params["w"] + noise(1, 0.1)},
                           eps={"w": noise(2, 0.01)},
                           e={"w": noise(3, 0.01)[0]})
    out_leaf = make_sync_step(hfl, mesh=None, layout="leaf")(state)
    out_flat = make_sync_step(hfl, mesh=None, layout="flat")(state)
    for name in ("params", "w_ref", "eps", "e"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_leaf, name)["w"]),
            np.asarray(getattr(out_flat, name)["w"]), err_msg=name)


def _payload_sync(state, hfl):
    """The mesh-free flat sync with Ω as a payload, as it was built before
    the local exchange applied it as a mask: ``lax.top_k`` values and
    indices per hop (``pack_topk``), wire-rounded, scattered back
    (``unpack_topk``)."""
    wire, t1, N = wire_format_of(hfl), hfl.tiers[1], hfl.num_clusters
    wref, ref_spec = fl.pack(state.w_ref)
    e, _ = fl.pack(state.e)
    s, eps_spec = _pack_drift(state, t1.beta_up)
    Q = ref_spec.total
    sents = []
    for n in range(N):
        vals, idx = sp.pack_topk(s[n], sp.keep_count(Q, t1.phi_up))
        if wire:
            vals = _wire_round(vals, wire)
        sents.append(sp.unpack_topk(vals, idx, Q))
    delta = sum(sents) / N + t1.beta_down * e
    dvals, didx = sp.pack_topk(delta, sp.keep_count(Q, t1.phi_down))
    if wire:
        dvals = _wire_round(dvals, wire)
    d = sp.unpack_topk(dvals, didx, Q)
    new_wref = wref + d
    return state._replace(
        params=fl.unpack_stacked(jnp.broadcast_to(new_wref[None], (N, Q)),
                                 fl.spec_of_stacked(state.params)),
        w_ref=fl.unpack(new_wref, ref_spec),
        eps=fl.unpack_stacked(s - jnp.stack(sents), eps_spec),
        e=fl.unpack(delta - d, ref_spec))


@pytest.mark.parametrize("mode,wire", [("sparse", "bf16"),
                                       ("quantized_sparse", "bf16"),
                                       ("quantized_sparse", "q8")],
                         ids=["sparse", "bf16", "q8"])
def test_mask_sync_equals_payload_sync_on_bf16_ties(mode, wire):
    """A multi-leaf bf16 model at its first sync (eps = e = 0): the drift
    sits on bf16 grids, so many entries tie at the k-th magnitude. The
    mask-form sync keeps the same entries as ``lax.top_k`` and lands on the
    same state, to the bit, as the payload composition it replaced."""
    hfl = HFLConfig(num_clusters=3, mus_per_cluster=1, period=1,
                    sync_mode=mode, wire_format=wire, phi_sbs_ul=0.9,
                    phi_mbs_dl=0.9, beta_s=0.5, beta_m=0.2)
    cfg = dataclasses.replace(_tiny_cfg(), dtype="bfloat16")
    state = hfl_init(init_model(jax.random.PRNGKey(0), cfg),
                     SGDM(momentum=0.9), hfl)
    keys = iter(jax.random.split(jax.random.PRNGKey(1),
                                 len(jax.tree.leaves(state.params))))
    state = state._replace(params=jax.tree.map(
        lambda p: (p + 1e-3 * jax.random.normal(next(keys), p.shape)
                   ).astype(p.dtype), state.params))
    s, _ = _pack_drift(state, hfl.tiers[1].beta_up)
    k = sp.keep_count(s.shape[1], hfl.tiers[1].phi_up)
    a = np.abs(np.asarray(s))
    kth = np.sort(a, axis=1)[:, -k]
    assert ((a == kth[:, None]).sum(axis=1) > 50).all()  # ties at the cut
    # op by op, as the leaf-equivalence tests run: under one jit the CPU
    # compiler may fuse the q8 products into the mean's sums and round
    # them differently
    got = make_sync(SyncPlan.from_config(hfl))(state)
    want = _payload_sync(state, hfl)
    for name in ("params", "w_ref", "eps", "e"):
        for a, b in zip(jax.tree.leaves(getattr(want, name)),
                        jax.tree.leaves(getattr(got, name))):
            assert a.dtype == b.dtype, name
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32),
                                          err_msg=name)


@pytest.mark.parametrize("mode", ["sparse", "quantized_sparse"])
def test_local_topk_sync_has_no_sort_gather_or_scatter(mode):
    """The mesh-free topk sync applies Ω as a mask: no ``top_k`` (a full
    sort on the chip), no sort, no gather of a payload, no scatter back;
    and the build records that form in the metrics registry."""
    import re

    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode=mode, phi_sbs_ul=0.9, phi_mbs_dl=0.9)
    state = _multi_leaf_state(hfl)
    reg = MetricsRegistry()
    with use_registry(reg):
        sync = make_sync(SyncPlan.from_config(hfl))
    txt = str(jax.make_jaxpr(sync)(state))
    for prim in ("top_k", "sort", "gather", "scatter", "scatter-add"):
        assert not re.search(rf"\b{prim}\[", txt), prim
    series = reg.snapshot()["hfl.sync_step_builds"]["series"]
    assert [k for k in series if "omega=mask" in k] and len(series) == 1


def test_flat_and_leaf_phi0_equal_dense_mean_multi_leaf():
    """φ=0, β=0: both sparse layouts keep everything and must reproduce the
    dense averaging sync on a multi-leaf model (N>1) — the dense-mode
    equivalence anchor for the whole-vector engine."""
    hfl_sparse = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                           sync_mode="sparse", phi_sbs_ul=0.0, phi_mbs_dl=0.0,
                           beta_s=0.0, beta_m=0.0)
    hfl_dense = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                          sync_mode="dense")
    state = _multi_leaf_state(hfl_sparse)
    state = state._replace(  # dense ignores eps/e; zero them for parity
        eps=jax.tree.map(jnp.zeros_like, state.eps),
        e=jax.tree.map(jnp.zeros_like, state.e),
    )
    out_dense = make_sync_step(hfl_dense, mesh=None)(state)
    for layout in ("flat", "leaf"):
        out = make_sync_step(hfl_sparse, mesh=None, layout=layout)(state)
        for a, b in zip(jax.tree.leaves(out.params),
                        jax.tree.leaves(out_dense.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(out.w_ref),
                        jax.tree.leaves(out_dense.w_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", ["sparse", "quantized_sparse"])
def test_flat_multi_leaf_protocol_invariants(mode):
    """Whole-vector selection differs from per-leaf selection by design, so
    on a multi-leaf model we verify the protocol invariants the leaf path
    also satisfies: consensus, drift conservation, reference adoption."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode=mode, phi_sbs_ul=0.9, phi_mbs_dl=0.9,
                    beta_m=1.0, beta_s=1.0)  # undiscounted: exact conservation
    state = _multi_leaf_state(hfl)
    state = state._replace(eps=jax.tree.map(jnp.zeros_like, state.eps),
                           e=jax.tree.map(jnp.zeros_like, state.e))
    out = make_sync_step(hfl, mesh=None, layout="flat")(state)
    # 1) consensus: all clusters identical after sync
    for p in jax.tree.leaves(out.params):
        np.testing.assert_array_equal(np.asarray(p[0]), np.asarray(p[1]))
    # 2) clusters adopted the new reference
    for p, wr in zip(jax.tree.leaves(out.params), jax.tree.leaves(out.w_ref)):
        np.testing.assert_allclose(np.asarray(p[0], np.float32),
                                   np.asarray(wr, np.float32),
                                   rtol=1e-2 if mode == "quantized_sparse" else 1e-6,
                                   atol=1e-2 if mode == "quantized_sparse" else 1e-6)
    # 3) conservation: applied + residuals == mean drift (per entry)
    if mode == "sparse":  # bf16 wire format is deliberately lossy
        for p0, wr0, wr1, eps, e in zip(
            jax.tree.leaves(state.params), jax.tree.leaves(state.w_ref),
            jax.tree.leaves(out.w_ref), jax.tree.leaves(out.eps),
            jax.tree.leaves(out.e),
        ):
            drift = np.asarray(p0, np.float32).mean(0) - np.asarray(wr0, np.float32)
            applied = np.asarray(wr1, np.float32) - np.asarray(wr0, np.float32)
            buffered = np.asarray(eps, np.float32).mean(0) + np.asarray(e, np.float32)
            np.testing.assert_allclose(applied + buffered, drift,
                                       rtol=1e-4, atol=1e-5)


def test_flat_sync_selection_is_whole_model():
    """The defining behaviour change: a cluster whose drift lives entirely
    in ONE leaf gets the whole uplink budget there; per-leaf Ω would spend
    a quota on every leaf."""
    N = 2
    big = 4096
    hfl = HFLConfig(num_clusters=N, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.95, phi_mbs_dl=0.0,
                    beta_s=0.0, beta_m=0.0)
    params = {
        "hot": jnp.zeros((big,)),
        "cold": jnp.zeros((big,)),
    }
    state = hfl_init(params, SGDM(), hfl)
    # all drift in "hot"; "cold" drifts infinitesimally
    drift = {"hot": jax.random.normal(jax.random.PRNGKey(0), (N, big)),
             "cold": jnp.full((N, big), 1e-6)}
    state = state._replace(params=jax.tree.map(jnp.add, state.params, drift))
    out = make_sync_step(hfl, mesh=None, layout="flat")(state)
    k = sp.keep_count(2 * big, hfl.phi_sbs_ul)
    # with β=φ_dl=0 the w_ref update is exactly the mean of the sent top-k;
    # whole-model Ω must have spent the entire budget on "hot" (the union of
    # the N clusters' selections, minus birthday collisions)
    applied_hot = int((np.asarray(out.w_ref["hot"]) != 0).sum())
    applied_cold = int((np.asarray(out.w_ref["cold"]) != 0).sum())
    assert applied_hot >= 1.5 * k
    assert applied_cold == 0
    # the leaf-wise reference, by construction, spends half its budget on
    # the near-zero "cold" leaf
    out_leaf = make_sync_step(hfl, mesh=None, layout="leaf")(state)
    leaf_cold = int((np.asarray(out_leaf.w_ref["cold"]) != 0).sum())
    assert leaf_cold > 0


# ---------------------------------------------------------------------------
# Ω impl routing (hist / pallas payloads)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["topk", "hist"])
def test_pack_phi_payload_reconstructs(impl):
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    phi = 0.9
    k = sp.keep_count(x.size, phi)
    vals, idx = sp.pack_phi(x, phi, impl=impl)
    assert vals.shape == (k,) and idx.shape == (k,) and idx.dtype == jnp.int32
    sent = sp.unpack_topk(vals, idx, x.size)
    # the payload must carry the large-|x| mass (top 10% of a Gaussian holds
    # ~44% of the energy -> residual norm ~0.75 of the original)
    assert float(jnp.linalg.norm(x - sent)) < 0.8 * float(jnp.linalg.norm(x))
    # payload entries are genuine entries of x
    np.testing.assert_allclose(np.asarray(vals), np.asarray(x)[np.asarray(idx)],
                               rtol=0, atol=0)


def test_pack_phi_hist_overlaps_exact_topk():
    x = jax.random.normal(jax.random.PRNGKey(1), (8192,))
    phi = 0.95
    k = sp.keep_count(x.size, phi)
    _, exact = sp.pack_topk(x, k)
    _, approx = sp.pack_phi(x, phi, impl="hist")
    overlap = len(set(np.asarray(exact).tolist())
                  & set(np.asarray(approx).tolist())) / k
    assert overlap > 0.8  # hist threshold is approximate but close


def test_flat_sync_with_hist_impl_runs_and_converges_protocol():
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1,
                    sync_mode="sparse", phi_sbs_ul=0.9, phi_mbs_dl=0.9,
                    omega_impl="hist")
    state = _multi_leaf_state(hfl)
    out = make_sync_step(hfl, mesh=None)(state)
    for p in jax.tree.leaves(out.params):
        np.testing.assert_array_equal(np.asarray(p[0]), np.asarray(p[1]))


def test_pack_phi_pallas_impl():
    x = jax.random.normal(jax.random.PRNGKey(2), (4096,))
    phi = 0.9
    k = sp.keep_count(x.size, phi)
    vals, idx = sp.pack_phi(x, phi, impl="pallas")
    assert vals.shape == (k,)
    sent = sp.unpack_topk(vals, idx, x.size)
    assert float(jnp.linalg.norm(x - sent)) < 0.8 * float(jnp.linalg.norm(x))


# ---------------------------------------------------------------------------
# regressions: zero-vector hist threshold; dense-sync dtype drift
# ---------------------------------------------------------------------------


def test_zero_vector_hist_threshold_keeps_at_least_k():
    z = jnp.zeros((1000,))
    phi = 0.9
    k = sp.keep_count(z.size, phi)
    mask = sp.threshold_mask(z, phi)
    assert int(mask.sum()) >= k  # was 0: nothing survived the tiny floor
    _, m = sp.omega(z, phi, impl="hist")
    assert int(m.sum()) >= k
    vals, idx = sp.pack_phi(z, phi, impl="hist")
    assert vals.shape == (k,)
    np.testing.assert_array_equal(np.asarray(vals), np.zeros(k, np.float32))


def test_near_empty_vector_hist_keeps_at_least_k():
    """Fewer than k nonzeros: the tiny floor alone would keep only the
    nonzero entries, under-filling the fixed-size payload."""
    x = jnp.zeros((1000,)).at[0].set(1.0)
    phi = 0.9
    k = sp.keep_count(x.size, phi)
    mask = sp.threshold_mask(x, phi)
    assert int(mask.sum()) >= k
    assert bool(mask[0])  # the one real entry is always selected
    vals, idx = sp.pack_phi(x, phi, impl="hist")
    sent = sp.unpack_topk(vals, idx, x.size)
    assert float(sent[0]) == 1.0  # and it reaches the payload


def test_zero_vector_pallas_omega_keeps_at_least_k():
    from repro.kernels.dgc import ops

    z = jnp.zeros((2048,))
    phi = 0.9
    k = sp.keep_count(z.size, phi)
    sparse, mask = ops.omega_pallas(z, phi)
    assert int(np.asarray(mask).sum()) >= k
    np.testing.assert_array_equal(np.asarray(sparse), np.zeros(z.size, np.float32))
    ghat, u, v = ops.dgc_step_pallas(z, z, z, 0.9, phi)
    assert not np.any(np.isnan(np.asarray(ghat)))


@pytest.mark.parametrize("mode", ["dense", "sparse", "quantized_sparse"])
def test_sync_preserves_buffer_dtype(mode):
    """bf16 HFL buffers must stay bf16 across a sync — an f32 w_ref after
    the first sync retraced every jitted step each period."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1, sync_mode=mode)
    state = _multi_leaf_state(hfl, buffer_dtype=jnp.bfloat16)
    out = make_sync_step(hfl, mesh=None)(state)
    for name in ("w_ref", "eps", "e"):
        for a, b in zip(jax.tree.leaves(getattr(state, name)),
                        jax.tree.leaves(getattr(out, name))):
            assert b.dtype == a.dtype, (mode, name, a.dtype, b.dtype)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(out.params)):
        assert b.dtype == a.dtype


def test_dense_sync_no_retrace_across_periods():
    """End-to-end guard: two syncs through one jitted dense step must hit
    the same compiled program (dtype-stable state)."""
    hfl = HFLConfig(num_clusters=2, mus_per_cluster=1, period=1, sync_mode="dense")
    state = _multi_leaf_state(hfl, buffer_dtype=jnp.bfloat16)
    sync = jax.jit(make_sync_step(hfl, mesh=None))
    out1 = sync(state)
    out2 = sync(out1)  # would retrace (and on strict settings, fail) if the
    # state dtypes drifted after the first sync
    tr1 = jax.tree.structure(jax.tree.map(lambda x: x.dtype, out1._asdict()))
    assert jax.tree.structure(
        jax.tree.map(lambda x: x.dtype, out2._asdict())) == tr1
    for a, b in zip(jax.tree.leaves(out1._asdict()),
                    jax.tree.leaves(out2._asdict())):
        assert a.dtype == b.dtype
