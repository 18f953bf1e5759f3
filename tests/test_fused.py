"""Fused top-k/compaction sync: exact equivalence vs ``topk`` (masks,
payloads, whole syncs), sharded stage-1 + merge, and the engine-facing
routing (``omega_impl="fused"``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HFLConfig, ModelConfig
from repro.core import sparsify as sp
from repro.core.hfl import hfl_init, jit_sync_step, make_sync_step
from repro.kernels.fused_sync import ops
from repro.models.transformer import init_model
from repro.optim import SGDM


def _tiny_cfg():
    return ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=61,
                       dtype="float32", remat=False)


def _multi_leaf_state(hfl, seed=0):
    params = init_model(jax.random.PRNGKey(seed), _tiny_cfg())
    state = hfl_init(params, SGDM(momentum=0.9), hfl)
    key = jax.random.PRNGKey(seed + 1)
    perturb = lambda p, k, s: p + s * jax.random.normal(k, p.shape).astype(p.dtype)
    keys = iter(jax.random.split(key, 3 * len(jax.tree.leaves(state.params))))
    return state._replace(
        params=jax.tree.map(lambda p: perturb(p, next(keys), 0.1), state.params),
        eps=jax.tree.map(lambda p: perturb(p, next(keys), 0.01), state.eps),
        e=jax.tree.map(lambda p: perturb(p, next(keys), 0.01), state.e),
    )


# ---------------------------------------------------------------------------
# select_topk_rows / fused_pack_phi: bit-identical to lax.top_k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,frac", [(4096, 0.1), (65536, 0.01),
                                    (100001, 0.1), (8192, 0.5)])
def test_select_topk_rows_bit_identical(n, frac):
    S = jax.random.normal(jax.random.PRNGKey(n % 31), (3, n))
    k = max(1, int(frac * n))
    vals, idx = jax.jit(lambda S: ops.select_topk_rows(S, k))(S)
    for r in range(3):
        tv, ti = jax.lax.top_k(jnp.abs(S[r]), k)
        np.testing.assert_array_equal(np.asarray(idx[r]), np.asarray(ti))
        np.testing.assert_array_equal(
            np.asarray(jnp.abs(vals[r])), np.asarray(tv))


def test_select_topk_rows_zero_vector_matches_topk():
    """The >= k zero-vector edge from PR 1: selection must still emit k
    entries, identical to ``lax.top_k``'s tie-break (first k indices)."""
    Z = jnp.zeros((2, 1000))
    _, idx = ops.select_topk_rows(Z, 100)
    np.testing.assert_array_equal(np.asarray(idx[0]),
                                  np.arange(100, dtype=np.int32))


def test_select_topk_rows_near_empty_and_ties():
    E = jnp.zeros((1, 1000)).at[0, 7].set(3.0)
    _, idx = ops.select_topk_rows(E, 100)
    np.testing.assert_array_equal(
        np.asarray(idx[0]), np.asarray(jax.lax.top_k(jnp.abs(E[0]), 100)[1]))
    C = jnp.full((1, 2048), 2.5)  # all tied: stable index-order tie-break
    _, idx = ops.select_topk_rows(C, 200)
    np.testing.assert_array_equal(np.asarray(idx[0]),
                                  np.arange(200, dtype=np.int32))


def test_select_topk_rows_k_equals_n():
    F = jax.random.normal(jax.random.PRNGKey(9), (1, 512))
    _, idx = ops.select_topk_rows(F, 512)
    np.testing.assert_array_equal(
        np.asarray(idx[0]), np.asarray(jax.lax.top_k(jnp.abs(F[0]), 512)[1]))


@pytest.mark.parametrize("phi", [0.9, 0.99])
def test_fused_pack_phi_equals_pack_topk(phi):
    x = jax.random.normal(jax.random.PRNGKey(5), (40000,))
    k = sp.keep_count(x.size, phi)
    v, i = sp.pack_phi(x, phi, impl="fused")
    vt, it = sp.pack_topk(x, k)
    assert i.dtype == jnp.int32 and v.shape == (k,)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(it))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vt))


def test_omega_fused_mask_bit_identical_to_topk():
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 512))
    phi = 0.95
    _, m_fused = sp.omega(x, phi, impl="fused")
    m_topk = sp.topk_mask(x, sp.keep_count(x.size, phi))
    np.testing.assert_array_equal(np.asarray(m_fused), np.asarray(m_topk))
    assert int(m_fused.sum()) == sp.keep_count(x.size, phi)


# ---------------------------------------------------------------------------
# whole-sync equivalence: omega_impl="fused" vs "topk"
# ---------------------------------------------------------------------------


def _mk(impl, mode="sparse", **kw):
    base = dict(num_clusters=3, mus_per_cluster=1, period=1, sync_mode=mode,
                phi_sbs_ul=0.9, phi_mbs_dl=0.9, omega_impl=impl)
    base.update(kw)
    return HFLConfig(**base)


@pytest.mark.parametrize("mode", ["sparse", "quantized_sparse"])
def test_fused_sync_equals_topk_sync(mode):
    state = _multi_leaf_state(_mk("topk", mode))
    out_t = jax.jit(make_sync_step(_mk("topk", mode), mesh=None))(state)
    out_f = jax.jit(make_sync_step(_mk("fused", mode), mesh=None))(state)
    for name in ("params", "w_ref", "eps", "e"):
        for a, b in zip(jax.tree.leaves(getattr(out_t, name)),
                        jax.tree.leaves(getattr(out_f, name))):
            # selection is bit-identical; values may differ by summation
            # association (batched scatter-add vs per-cluster python sum)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-6, atol=1e-7, err_msg=f"{mode}/{name}")
    # consensus must be exact
    for p in jax.tree.leaves(out_f.params):
        np.testing.assert_array_equal(np.asarray(p[0]), np.asarray(p[1]))


def test_fused_sync_launch_counts():
    """The fused path's defining win: 2 top-k + 2 scatter-add per sync
    regardless of leaf count (leaf: one of each per leaf per hop)."""
    import re

    state = _multi_leaf_state(_mk("fused"))
    txt = str(jax.make_jaxpr(make_sync_step(_mk("fused"), mesh=None))(state))
    assert len(re.findall(r"\btop_k\[", txt)) <= 2
    assert len(re.findall(r"\bscatter-add\[", txt)) <= 2


def test_fused_sync_preserves_buffer_dtype():
    hfl = _mk("fused")
    params = init_model(jax.random.PRNGKey(0), _tiny_cfg())
    state = hfl_init(params, SGDM(momentum=0.9), hfl,
                     buffer_dtype=jnp.bfloat16)
    out = jit_sync_step(make_sync_step(hfl, mesh=None))(state)
    for name in ("w_ref", "eps", "e", "params"):
        for a, b in zip(jax.tree.leaves(getattr(state, name)),
                        jax.tree.leaves(getattr(out, name))):
            assert b.dtype == a.dtype, (name, a.dtype, b.dtype)


def test_fused_async_sync_and_probe():
    """The fused impl must ride the async per-cluster sync and the
    measured-accounting probe unchanged (single-vector pack_phi path)."""
    from repro.comm.accounting import make_sync_probe
    from repro.sim.engine import make_async_sync_step

    hfl = _mk("fused")
    state = _multi_leaf_state(hfl)
    sync_n = make_async_sync_step(hfl)
    out = sync_n(state, jnp.int32(1), jnp.float32(1.0 / 3))
    assert jnp.isfinite(jax.tree.leaves(out.params)[0]).all()
    hfl_t = _mk("topk")
    probe_f = make_sync_probe(hfl, "delta-varint")
    probe_t = make_sync_probe(hfl_t, "delta-varint")
    state2 = _multi_leaf_state(hfl)
    ul_f, dl_f = probe_f(state2)
    ul_t, dl_t = probe_t(state2)
    # identical selection => identical measured payload bits
    np.testing.assert_array_equal(np.asarray(ul_f), np.asarray(ul_t))
    assert float(dl_f) == float(dl_t)


# ---------------------------------------------------------------------------
# sharded stage-1 + merge
# ---------------------------------------------------------------------------


def test_shard_candidates_merge_exact():
    n, S = 65536, 4
    X = jax.random.normal(jax.random.PRNGKey(6), (2, n))
    k = 6000
    nloc = n // S
    cv, ci, cm, cth = [], [], [], []
    for s in range(S):
        sl = X[:, s * nloc:(s + 1) * nloc]
        v_, i_, m_, t_ = ops.shard_select_candidates(sl, k, S)
        cv.append(v_)
        ci.append(jnp.where(i_ < nloc, i_ + s * nloc, n))
        cm.append(m_)
        cth.append(t_)
    vals, idx, exact = ops.merge_shard_candidates(
        jnp.concatenate(cv, axis=1), jnp.concatenate(ci, axis=1),
        jnp.stack(cm, axis=1), jnp.stack(cth, axis=1), k)
    assert bool(exact.all())  # certificate holds on gaussian data
    for r in range(2):
        _, ti = jax.lax.top_k(jnp.abs(X[r]), k)
        np.testing.assert_array_equal(np.asarray(idx[r]), np.asarray(ti))


def test_flat_shards_requires_fused():
    with pytest.raises(ValueError, match="fused"):
        make_sync_step(_mk("topk", flat_shards=2), mesh=None)
