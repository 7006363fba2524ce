"""Property-based early-stop invariants (ISSUE 1): objective monotonicity,
change-rate scale invariance, LongTailModel persistence round-trip; plus
streamed k-means++ invariants (ISSUE 2): k distinct in-bounds picks under
any chunking, exact chunks=1 equivalence with the monolithic pass, and
the compiled seeding equal to its eager trace.

Runs under real hypothesis when installed, or under the seeded
mini-hypothesis shim in conftest.py on a bare JAX install.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import core
from repro.core import em_gmm
from repro.core.earlystop import change_rate


def _blobs(seed: int, n: int, k: int, d: int = 3):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 6.0, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.0, (n // k, d)) for c in centers])
    return jnp.asarray(x.astype(np.float32))


@given(seed=st.integers(0, 10_000), k=st.integers(2, 5))
@settings(max_examples=8, deadline=None)
def test_kmeans_objective_monotone_nonincreasing(seed, k):
    x = _blobs(seed, 240, k)
    c0 = core.random_init(jax.random.PRNGKey(seed), x, k)
    res = core.kmeans_fit_traced(x, c0, max_iters=40)
    js = np.asarray(res["objectives"], np.float64)
    rel = np.diff(js) / np.maximum(np.abs(js[:-1]), 1e-9)
    assert rel.max() <= 1e-5, \
        f"k-means J increased by {rel.max():.2e} (seed={seed}, k={k})"


@given(seed=st.integers(0, 10_000), k=st.integers(2, 5))
@settings(max_examples=8, deadline=None)
def test_em_loglik_monotone_nondecreasing(seed, k):
    x = _blobs(seed, 240, k)
    p0 = em_gmm.random_init(jax.random.PRNGKey(seed), x, k)
    res = em_gmm.em_fit_traced(x, p0, max_iters=30, tol=1e-12)
    js = np.asarray(res["objectives"], np.float64)
    rel = np.diff(js) / np.maximum(np.abs(js[:-1]), 1e-9)
    assert rel.min() >= -1e-5, \
        f"EM loglik decreased by {rel.min():.2e} (seed={seed}, k={k})"


@given(alpha=st.floats(1e-3, 1e3),
       j_prev=st.one_of(st.floats(-500.0, -0.5), st.floats(0.5, 500.0)),
       delta=st.floats(-10.0, 10.0))
@settings(max_examples=25, deadline=None)
def test_change_rate_scale_invariant(alpha, j_prev, delta):
    """h(αJ_i, αJ_{i-1}) == h(J_i, J_{i-1}): Eq. 7 is a *relative* rate, so
    the fitted h* transfers across objective scales (dataset sizes).
    Checked in f64 — in f32 the subtraction's cancellation noise would
    drown the property itself."""
    j_curr = j_prev + delta
    with jax.enable_x64():
        h1 = float(change_rate(jnp.float64(j_curr), jnp.float64(j_prev)))
        h2 = float(change_rate(jnp.float64(alpha * j_curr),
                               jnp.float64(alpha * j_prev)))
    assert h2 == pytest.approx(h1, rel=1e-9, abs=1e-15)


def _monolithic_kmeans_pp(key, x, k):
    """The historical flat k-means++ pass (resident [N] d², resident [N, D]
    difference temporaries) with the engine's key schedule: the reference
    the streamed implementation must reproduce bit-for-bit at chunks=1."""
    x = x.astype(jnp.float32)
    n = x.shape[0]
    key, sub = jax.random.split(key)
    first = x[jax.random.randint(sub, (), 0, n)]
    cent = [first]
    d2 = jnp.sum((x - first) ** 2, axis=-1)
    for _ in range(1, k):
        key, sub = jax.random.split(key)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        c = x[jax.random.choice(sub, n, p=probs)]
        cent.append(c)
        d2 = jnp.minimum(d2, jnp.sum((x - c) ** 2, axis=-1))
    return jnp.stack(cent)


@given(seed=st.integers(0, 10_000), k=st.integers(2, 6),
       n=st.integers(50, 400), chunks=st.integers(1, 13))
@settings(max_examples=12, deadline=None)
def test_streamed_kmeanspp_picks_k_distinct_inbounds_points(seed, k, n,
                                                            chunks):
    """For ANY chunking (dividing n or not, more chunks than needed or not)
    the streamed D² sampler returns k distinct rows of x — never a padding
    row, never a repeat (chosen points carry exactly zero d² mass)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 5.0, (n, 3)).astype(np.float32))
    c = core.kmeans_plus_plus_init(jax.random.PRNGKey(seed), x, k,
                                   chunks=chunks)
    got = np.asarray(c)
    rows = {tuple(r) for r in np.asarray(x)}
    assert all(tuple(r) in rows for r in got), "picked a non-data point"
    assert len({tuple(r) for r in got}) == k, "picked a duplicate"


@given(seed=st.integers(0, 10_000), k=st.integers(2, 6))
@settings(max_examples=12, deadline=None)
def test_streamed_kmeanspp_chunks1_equals_monolithic_exactly(seed, k):
    """chunks=1 must reduce the scan machinery to the flat pass bit-for-bit
    (same key schedule, same draws, same arithmetic) — the guard that lets
    every existing seed keep its value."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 5.0, (257, 4)).astype(np.float32))
    key = jax.random.PRNGKey(seed)
    a = _monolithic_kmeans_pp(key, x, k)
    b = core.kmeans_plus_plus_init(key, x, k, chunks=1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("chunks", [1, 3])
@given(seed=st.integers(0, 10_000), k=st.integers(2, 6))
@settings(max_examples=6, deadline=None)
def test_compiled_kmeanspp_equals_its_eager_trace(chunks, seed, k):
    """The seeding is one jitted program per shape; run op by op (its
    undecorated body with jit off, so the scan and the loop run in Python)
    it must pick the same points bit-for-bit, for a chunking that divides
    nothing as well as for the flat pass."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 5.0, (257, 4)).astype(np.float32))
    key = jax.random.PRNGKey(seed)
    compiled = core.kmeans_plus_plus_init(key, x, k, chunks=chunks)
    with jax.disable_jit():
        eager = core.kmeans_plus_plus_init.__wrapped__(key, x, k,
                                                       chunks=chunks)
    np.testing.assert_array_equal(np.asarray(compiled), np.asarray(eager))


@given(seed=st.integers(0, 99), a=st.floats(0.5, 3.0))
@settings(max_examples=10, deadline=None)
def test_longtail_model_json_roundtrip(seed, a):
    rng = np.random.default_rng(seed)
    r = rng.uniform(0.3, 1.0, 80)
    h = a * (1.0 - r) ** 2 * (1 + rng.normal(0, 0.02, r.size))
    m = core.fit_longtail([(r, np.abs(h))], algorithm="kmeans",
                          dataset=f"synthetic-{seed}", family="quadratic")
    m2 = core.LongTailModel.from_json(m.to_json())
    assert m2.algorithm == m.algorithm
    assert m2.dataset == m.dataset
    assert m2.n_train_groups == m.n_train_groups
    assert m2.regression.family == m.regression.family
    np.testing.assert_allclose(m2.regression.coeffs, m.regression.coeffs,
                               rtol=1e-12)
    for acc in (0.9, 0.95, 0.99):
        assert m2.threshold_for(acc) == pytest.approx(m.threshold_for(acc))


def test_longtail_roundtrip_with_comparison_table():
    """family=None stores the model-selection table; it must survive JSON."""
    rng = np.random.default_rng(0)
    r = rng.uniform(0.2, 1.0, 200)
    h = 1.8 * (1 - r) ** 2 + np.abs(rng.normal(0, 1e-3, r.size))
    m = core.fit_longtail([(r, h)], algorithm="em", dataset="synthetic",
                          family=None)
    m2 = core.LongTailModel.from_json(m.to_json())
    assert m2.comparison is not None
    assert set(m2.comparison) == set(m.comparison)
    assert m2.threshold_for(0.99) == pytest.approx(m.threshold_for(0.99))
