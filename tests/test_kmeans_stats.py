"""The k-means sweep's per-cluster statistics (``core.kmeans.assign_and_stats``).

The jnp path forms ``sums``/``counts`` as a one-hot contraction; the
``kernels/kmeans_assign/ref.py`` oracle keeps the scatter-add.  Both are
checked against a float64 per-cluster sum on the host, and the engine's
one-chunk fit is checked to lower with no scatter at all.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kmeans as km
from repro.core.engine import EngineConfig, _fit, get_algorithm
from repro.kernels.kmeans_assign.ref import kmeans_assign_masked_ref
from repro.kernels.layout import chunk_points

EPS32 = 2.0 ** -24


def _points(n, d, seed=0):
    """Poker-like integer features plus noise: many rows per cluster."""
    rng = np.random.default_rng(seed)
    x = rng.integers(1, 14, (n, d)).astype(np.float64)
    return (x + rng.normal(0.0, 0.3, (n, d))).astype(np.float32)


def _host_stats(x, w, labels, k):
    """float64 per-cluster sums [K, D], counts [K] and |x|-sums [K, D] from
    the labels the device chose (rows labelled -1 add nothing)."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    w = np.asarray(w, np.float64).reshape(-1)
    labels = np.asarray(labels).reshape(-1)
    sums = np.zeros((k, x.shape[1]))
    abs_sums = np.zeros((k, x.shape[1]))
    counts = np.zeros(k)
    for c in range(k):
        sel = labels == c
        sums[c] = (x[sel] * w[sel, None]).sum(0)
        abs_sums[c] = np.abs(x[sel] * w[sel, None]).sum(0)
        counts[c] = w[sel].sum()
    return sums, counts, abs_sums


def _check(x, w, c, out):
    """``out`` = assign_and_stats(x, c, mask=w) for one restart: labels and
    j as the scatter oracle, counts exact, sums within float32 rounding of
    both the float64 host sum and the oracle's scatter-add."""
    labels, sums, counts, j = (np.asarray(a) for a in out)
    k, d = c.shape
    ref = kmeans_assign_masked_ref(jnp.asarray(x).reshape(-1, d),
                                   jnp.asarray(w).reshape(-1), c)
    np.testing.assert_array_equal(labels.reshape(-1), np.asarray(ref[0]))
    np.testing.assert_allclose(j, np.asarray(ref[3]), rtol=1e-6)
    want, want_counts, abs_sums = _host_stats(x, w, labels, k)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(np.asarray(ref[2]), want_counts)
    # float32 summation of n terms: |error| <= n * eps * sum |terms|; the
    # row count bounds n, and both forms sum far tighter than that
    tol = 64 * EPS32 * abs_sums + 1e-30
    assert np.all(np.abs(sums - want) <= tol)
    assert np.all(np.abs(np.asarray(ref[1], np.float64) - want) <= tol)
    return labels, sums, counts


CASES = ["unmasked", "masked_padding", "empty_cluster", "k1", "chunked",
         "vmap_restarts"]


@pytest.mark.parametrize("case", CASES)
def test_assign_and_stats_matches_float64_and_scatter_oracle(case):
    n, d, k = 3000, 11, 10
    x = _points(n, d)
    ones = np.ones(n, np.float32)
    c = jnp.asarray(x[:: n // k][:k])
    if case == "unmasked":
        _check(x, ones, c, km.assign_and_stats(jnp.asarray(x), c))
    elif case == "masked_padding":
        w = ones.copy()
        w[-700:] = 0.0                       # padding rows at the tail
        x[-700:] = 1e4                       # that would dominate any sum
        labels, _, _ = _check(x, w, c, km.assign_and_stats(
            jnp.asarray(x), c, mask=jnp.asarray(w)))
        assert np.all(labels[-700:] == -1) and np.all(labels[:-700] >= 0)
    elif case == "empty_cluster":
        c = c.at[3].set(1e3)                 # far from every point
        labels, sums, counts = _check(x, ones, c, km.assign_and_stats(
            jnp.asarray(x), c, mask=jnp.asarray(ones)))
        assert counts[3] == 0 and np.all(sums[3] == 0)
        new = np.asarray(km.update_centroids(c, jnp.asarray(sums),
                                             jnp.asarray(counts)))
        np.testing.assert_array_equal(new[3], np.asarray(c)[3])
        assert np.all(np.isfinite(new))
    elif case == "k1":
        c = c[:1]
        labels, sums, counts = _check(x, ones, c, km.assign_and_stats(
            jnp.asarray(x), c))
        assert np.all(labels == 0) and counts[0] == n
    elif case == "chunked":
        xc, mask = chunk_points(jnp.asarray(x[:2990]), 7)   # padded tail
        assert float(jnp.sum(mask)) == 2990 < mask.size
        labels, sums, counts = _check(np.asarray(xc), np.asarray(mask), c,
                                      km.assign_and_stats(xc, c, mask=mask))
        assert labels.shape == mask.shape
        assert np.all((labels == -1) == (np.asarray(mask) == 0))
        # the engine's streamed form: one call per [P, D] chunk, summed
        per = jax.lax.map(lambda a: km.assign_and_stats(a[0], c, mask=a[1]),
                          (xc, mask))
        np.testing.assert_array_equal(np.asarray(per[0]), labels)
        np.testing.assert_array_equal(np.asarray(per[2]).sum(0), counts)
        np.testing.assert_allclose(np.asarray(per[1]).sum(0), sums,
                                   rtol=1e-5, atol=1e-3)
    elif case == "vmap_restarts":
        r = 4
        cs = jnp.stack([jnp.asarray(x[i:: n // k][:k]) for i in range(r)])
        w = ones.copy()
        w[-100:] = 0.0
        out = jax.vmap(lambda cc: km.assign_and_stats(
            jnp.asarray(x), cc, mask=jnp.asarray(w)))(cs)
        for i in range(r):
            _check(x, w, cs[i], tuple(a[i] for a in out))


# (cell, N, D, K): the benchmark's one-chunk k-means sweeps at small N
SWEEP_SHAPES = [("poker", 2048, 11, 10), ("landuse", 2048, 3, 6)]


@pytest.mark.parametrize("name,n,d,k", SWEEP_SHAPES)
def test_one_chunk_fit_lowers_without_scatter(name, n, d, k):
    """The default k-means fit forms its statistics with no scatter, and
    every product in it runs at HIGHEST: bf16 would round the points."""
    del name
    cfg = EngineConfig(max_iters=30, patience=3, use_h_stop=True,
                       stop_when_frozen=True)
    f32 = jnp.float32
    text = _fit.lower(jax.ShapeDtypeStruct((n, d), f32),
                      jax.ShapeDtypeStruct((k, d), f32),
                      jax.ShapeDtypeStruct((), f32),
                      get_algorithm("kmeans"), cfg).as_text()
    assert "scatter" not in text
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert len(dots) >= 2                    # distances and statistics
    assert all("HIGHEST, HIGHEST" in ln for ln in dots)
