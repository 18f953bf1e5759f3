"""Pallas kernel tests: sweep shapes/dtypes, assert allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsify import keep_count
from repro.kernels.dgc import kernel as K
from repro.kernels.dgc import ops, ref


@pytest.mark.parametrize("n", [512, 1024, 262144, 300001, 1 << 20])
@pytest.mark.parametrize("phi", [0.9, 0.99])
def test_dgc_step_pallas_vs_ref(n, phi):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n % 97), 3)
    u = jax.random.normal(k1, (n,))
    v = jax.random.normal(k2, (n,))
    g = jax.random.normal(k3, (n,))
    outs_p = ops.dgc_step_pallas(u, v, g, 0.9, phi)
    outs_r = ref.dgc_step_ref(u, v, g, 0.9, phi)
    for p_, r_ in zip(outs_p, outs_r):
        np.testing.assert_allclose(np.asarray(p_), np.asarray(r_), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_omega_pallas_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)).astype(dtype)
    s, mask = ops.omega_pallas(x, 0.95)
    assert s.dtype == dtype
    assert int(mask.sum()) >= keep_count(4096, 0.95)
    # masked-out entries are exactly zero
    assert (np.asarray(s, np.float32)[~np.asarray(mask)] == 0).all()


@pytest.mark.parametrize("shape", [(2048,), (64, 1024), (8, 16, 512)])
def test_dgc_step_pallas_shapes(shape):
    k = jax.random.PRNGKey(1)
    u = jax.random.normal(k, shape)
    v = jnp.zeros(shape)
    g = jax.random.normal(jax.random.PRNGKey(2), shape)
    gp, up, vp = ops.dgc_step_pallas(u, v, g, 0.5, 0.9)
    gr, ur, vr = ref.dgc_step_ref(u, v, g, 0.5, 0.9)
    assert gp.shape == shape
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), rtol=1e-5, atol=1e-6)


def test_update_max_kernel_direct():
    R = K.BLOCK_ROWS * 2
    u = jax.random.normal(jax.random.PRNGKey(3), (R, K.BLOCK_COLS))
    v = jax.random.normal(jax.random.PRNGKey(4), (R, K.BLOCK_COLS))
    g = jax.random.normal(jax.random.PRNGKey(5), (R, K.BLOCK_COLS))
    u2, v2, bmax = K.update_max(u, v, g, 0.7, interpret=True)
    ur, vr, hi = ref.update_max_ref(u, v, g, 0.7)
    np.testing.assert_allclose(np.asarray(u2), np.asarray(ur), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(bmax.max()), float(hi), rtol=1e-5)


def test_tail_hist_kernel_direct():
    R = K.BLOCK_ROWS * 3
    v = jax.random.normal(jax.random.PRNGKey(6), (R, K.BLOCK_COLS))
    edges = jnp.linspace(1e-30, float(jnp.abs(v).max()), 32)
    counts = K.tail_hist(v, edges, interpret=True)
    counts_r = ref.tail_hist_ref(v, edges)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(counts_r))
