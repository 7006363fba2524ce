"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (the ops
dispatch through the backend registry: interpret on CPU CI, compiled on
TPU/GPU — see tests/test_dispatch.py for the registry itself)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.kmeans_assign.ops import kmeans_assign_chunked
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
from repro.kernels.gmm_estep import gmm_estep
from repro.kernels.gmm_estep.ref import gmm_estep_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,d,k", [
    (64, 2, 2), (1000, 4, 8), (1024, 3, 6), (777, 11, 10), (128, 130, 3),
    (2048, 4, 16), (31, 7, 5),
])
@pytest.mark.parametrize("dtype", [np.float32])
def test_kmeans_assign_sweep(n, d, k, dtype):
    x = jnp.asarray(RNG.normal(0, 10, (n, d)).astype(dtype))
    c = jnp.asarray(RNG.normal(0, 10, (k, d)).astype(dtype))
    l1, s1, n1, j1 = kmeans_assign(x, c)
    l2, s2, n2, j2 = kmeans_assign_ref(x, c)
    assert (l1 == l2).all()
    np.testing.assert_allclose(s1, s2, rtol=2e-5, atol=1e-2)
    np.testing.assert_allclose(n1, n2, rtol=0)
    np.testing.assert_allclose(j1, j2[0], rtol=2e-5)


@given(n=st.integers(8, 300), d=st.integers(1, 24), k=st.integers(2, 12))
@settings(max_examples=12, deadline=None)
def test_kmeans_assign_property(n, d, k):
    rng = np.random.default_rng(n * 31 + d * 7 + k)
    x = jnp.asarray(rng.normal(0, 5, (n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 5, (k, d)).astype(np.float32))
    labels, sums, counts, j = kmeans_assign(x, c)
    # invariants: counts sum to n; sums consistent with labels; J ≥ 0
    assert float(jnp.sum(counts)) == n
    assert float(j) >= 0
    ref_sums = np.zeros((k, d), np.float32)
    np.add.at(ref_sums, np.asarray(labels), np.asarray(x))
    np.testing.assert_allclose(sums, ref_sums, rtol=2e-4, atol=1e-2)


@pytest.mark.parametrize("n,d,k", [(64, 2, 2), (1000, 4, 8), (777, 11, 10),
                                   (2048, 3, 6)])
def test_gmm_estep_sweep(n, d, k):
    x = jnp.asarray(RNG.normal(0, 3, (n, d)).astype(np.float32))
    mu = jnp.asarray(RNG.normal(0, 3, (k, d)).astype(np.float32))
    var = jnp.asarray(RNG.uniform(0.5, 4, (k, d)).astype(np.float32))
    lw = jnp.log(jnp.full((k,), 1.0 / k, jnp.float32))
    o1 = gmm_estep(x, mu, var, lw)
    o2 = gmm_estep_ref(x, mu, var, lw)
    assert (o1[0] == o2[0]).all()
    np.testing.assert_allclose(o1[1], o2[1][0], rtol=1e-5)
    np.testing.assert_allclose(o1[2], o2[2], rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(o1[3], o2[3], rtol=2e-4, atol=2e-2)
    np.testing.assert_allclose(o1[4], o2[4], rtol=2e-4, atol=2e-1)


def test_gmm_estep_responsibilities_sum():
    n, d, k = 500, 4, 6
    x = jnp.asarray(RNG.normal(0, 2, (n, d)).astype(np.float32))
    mu = jnp.asarray(RNG.normal(0, 2, (k, d)).astype(np.float32))
    var = jnp.ones((k, d), jnp.float32)
    lw = jnp.log(jnp.full((k,), 1.0 / k))
    _, _, r_sum, _, _ = gmm_estep(x, mu, var, lw)
    assert float(jnp.sum(r_sum)) == pytest.approx(n, rel=1e-4)


def test_kmeans_assign_chunked_mask_slices_with_chunks():
    """The shared chunked driver slices the mask alongside the rows."""
    x = jnp.asarray(RNG.normal(0, 5, (300, 4)).astype(np.float32))
    c = jnp.asarray(RNG.normal(0, 5, (5, 4)).astype(np.float32))
    m = jnp.asarray((RNG.random(300) > 0.25).astype(np.float32))
    a = kmeans_assign(x, c, mask=m)
    b = kmeans_assign_chunked(x, c, chunks=4, mask=m)
    assert (a[0] == b[0]).all()
    np.testing.assert_allclose(a[1], b[1], rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(a[3], b[3], rtol=1e-5)
