"""The reference's top-k, which counts instead of sorting, keeps exactly the
entries that the sorting definition keeps: the k largest magnitudes, ties
at the k-th going to the lower index."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference.dense_lm import top_k_part


def sorted_top_k(x, k):
    """The definition by sorting: the k-th largest magnitude is the
    threshold, then the lowest indices among the entries equal to it fill
    the rest."""
    a = jnp.abs(x)
    n = a.size
    t = jnp.sort(a)[n - k]
    gt = a > t
    eq = a == t
    need = k - jnp.sum(gt)
    iota = jnp.arange(n, dtype=jnp.int32)
    eq_idx = jnp.sort(jnp.where(eq, iota, n))
    cut = eq_idx[jnp.maximum(need - 1, 0)]
    keep = gt | (eq & (iota <= cut) & (need > 0))
    return jnp.where(keep, x, 0.0)


def tied_vector(seed, n, k):
    """Values on a coarse bf16 grid (many equal magnitudes, both signs,
    zeros), with extra copies of the k-th magnitude planted on both sides
    of the cut."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32) * 1e-3
    x = np.array(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    x[rng.random(n) < 0.05] = 0.0
    t = np.sort(np.abs(x))[n - k]
    spots = rng.choice(n, size=max(2, n // 50), replace=False)
    x[spots] = t * rng.choice([-1.0, 1.0], size=spots.size)
    return x


@pytest.mark.parametrize("seed", [0, 1, 2, 3_000_000_001])
@pytest.mark.parametrize("n,k", [(1000, 100), (4097, 1), (4097, 4097),
                                 (20_000, 2_000), (20_000, 19_000)])
def test_counting_top_k_equals_sorting_top_k(seed, n, k):
    x = jnp.asarray(tied_vector(seed, n, k))
    a = jnp.abs(x)
    t = jnp.sort(a)[n - k]
    assert int(jnp.sum(a == t)) > 1 or k == n  # ties at the cut
    got = np.asarray(jax.jit(top_k_part, static_argnums=1)(x, k))
    want = np.asarray(sorted_top_k(x, k))
    assert got.tobytes() == want.tobytes()
    assert int(np.count_nonzero(np.abs(got) > 0)) <= k


def test_all_equal_keeps_the_lowest_indices():
    x = jnp.full((37,), -2.5, jnp.float32)
    got = np.asarray(top_k_part(x, 5))
    assert np.array_equal(np.flatnonzero(got), np.arange(5))
