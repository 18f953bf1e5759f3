"""Unit + property tests for the DGC sparsification core (paper §IV)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sparsify as sp


def test_keep_count():
    assert sp.keep_count(1000, 0.99) == 10
    assert sp.keep_count(1000, 0.9) == 100
    assert sp.keep_count(10, 0.9999) == 1  # never zero


def test_omega_topk_exact():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05])
    s, mask = sp.omega(x, phi=0.6)  # keep 2
    assert int(mask.sum()) == 2
    np.testing.assert_array_equal(np.asarray(mask), [False, True, False, True, False])
    np.testing.assert_allclose(np.asarray(s), [0, -5.0, 0, 3.0, 0])


def test_omega_phi_zero_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,))
    s, mask = sp.omega(x, 0.0)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(x))
    assert bool(mask.all())


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(8, 2000),
    phi=st.floats(0.1, 0.995),
    seed=st.integers(0, 2**16),
)
def test_omega_properties(n, phi, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    s, mask = sp.omega(x, phi)
    k = sp.keep_count(n, phi)
    # exactly k kept (exact top-k impl)
    assert int(mask.sum()) == k
    # conservation: sent + residual == original
    np.testing.assert_allclose(
        np.asarray(s + x * (~mask)), np.asarray(x), rtol=1e-6, atol=1e-7
    )
    # kept entries dominate dropped entries in magnitude
    if k < n:
        kept_min = np.abs(np.asarray(x)[np.asarray(mask)]).min()
        drop_max = np.abs(np.asarray(x)[~np.asarray(mask)]).max()
        assert kept_min >= drop_max - 1e-6


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(64, 4000),
    phi=st.floats(0.5, 0.99),
    seed=st.integers(0, 2**16),
)
def test_hist_threshold_keeps_at_least_k(n, phi, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    s, mask = sp.omega(x, phi, impl="hist")
    assert int(mask.sum()) >= sp.keep_count(n, phi)
    np.testing.assert_allclose(
        np.asarray(s + x * (~mask)), np.asarray(x), rtol=1e-6, atol=1e-7
    )


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), sigma=st.floats(0.0, 0.99))
def test_dgc_step_invariants(seed, sigma):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = 256
    u = jax.random.normal(k1, (n,))
    v = jax.random.normal(k2, (n,))
    g = jax.random.normal(k3, (n,))
    ghat, u2, v2 = sp.dgc_step(u, v, g, sigma, 0.9)
    # total value conservation: what's sent + what's buffered == accumulated
    u_acc = sigma * u + g
    v_acc = v + u_acc
    np.testing.assert_allclose(np.asarray(ghat + v2), np.asarray(v_acc), rtol=1e-5, atol=1e-6)
    # momentum-factor masking: u zeroed exactly where transmitted
    sent = np.abs(np.asarray(ghat)) > 0
    assert (np.asarray(u2)[sent] == 0).all()
    assert (np.asarray(v2)[sent] == 0).all()


def test_pack_unpack_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(1), (512,))
    vals, idx = sp.pack_topk(x, 51)
    dense = sp.unpack_topk(vals, idx, 512)
    s, mask = sp.omega(x, 0.9)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(s), rtol=1e-6)


# ---------------------------------------------------------------------------
# topk_masks: lax.top_k's set by a counting radix select, no sort
# ---------------------------------------------------------------------------


def _grid(rng, shape, levels=20, step=2.0 ** -8):
    """bf16-representable values on a coarse grid: thousands of ties."""
    return rng.integers(-levels, levels + 1, shape).astype(np.float32) * step


def _normal(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


_TAIL = 3 * sp.TIE_BLOCK + 5  # a 1-D row that is not whole tie blocks

# case -> (pieces [R, ...] sharing the row axis, k)
_TOPK_CASES = {
    "random_normal": lambda: ([_normal((2, 3, 40, 50))], 600),
    "random_normal_pieces": lambda: (
        [_normal((2, 2, 64, 32), 1), _normal((2, 7), 2),
         _normal((2, 2, 1), 3), _normal((2, 9000), 4)], 1_325),
    "bf16_grid_ties": lambda: (
        [_grid(np.random.default_rng(5), (2, 4, 100, 250))], 10_000),
    "all_zero_rows": lambda: ([np.zeros((2, 30, 40), np.float32)], 400),
    "fewer_nonzeros_than_k": lambda: (
        [np.where(np.arange(3000) % 997 == 3, -1.5, 0.0).astype(np.float32)
         .reshape(1, 3000)], 100),
    "k_is_1": lambda: ([_normal((3, 5000), 6)], 1),
    "k_is_n": lambda: ([_grid(np.random.default_rng(7), (2, 5000))], 5000),
    "subnormals_and_negative_zero": lambda: (
        [np.resize(np.array([0.0, -0.0, 1e-45, -1e-45, 1e-40, -3e-39,
                             1e-38, -2.0], np.float32), (2, 4000))], 1_700),
    "length_not_whole_tie_blocks": lambda: (
        [_grid(np.random.default_rng(8), (2, _TAIL), levels=6)],
        _TAIL // 4),
}


@pytest.mark.parametrize("case", list(_TOPK_CASES))
def test_topk_masks_match_lax_top_k(case):
    """Exactly the set ``lax.top_k`` picks from each concatenated row (ties
    at the k-th magnitude by lowest index), in the pieces' shapes."""
    pieces, k = _TOPK_CASES[case]()
    R = pieces[0].shape[0]
    rows = np.concatenate([p.reshape(R, -1) for p in pieces], axis=1)
    masks = sp.topk_masks([jnp.asarray(p) for p in pieces], k)
    got = np.concatenate([np.asarray(m).reshape(R, -1) for m in masks], axis=1)
    for r in range(R):
        _, idx = jax.lax.top_k(jnp.abs(jnp.asarray(rows[r])), k)
        want = np.zeros(rows.shape[1], bool)
        want[np.asarray(idx)] = True
        np.testing.assert_array_equal(got[r], want, err_msg=f"row {r}")
    assert [m.shape for m in masks] == [p.shape for p in pieces]
    if case in ("bf16_grid_ties", "length_not_whole_tie_blocks"):
        kth = np.sort(np.abs(rows), axis=1)[:, -k]
        assert ((np.abs(rows) == kth[:, None]).sum(axis=1) > 1000).all()
