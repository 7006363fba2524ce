"""k-means + EM engines: convergence invariants, early stop, kernels parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import em_gmm
from repro.data import load


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(0)
    centers = np.array([[0, 0, 0], [8, 8, 8], [-8, 8, 0], [8, -8, 4]], float)
    x = np.concatenate([c + rng.normal(0, 1.0, (500, 3)) for c in centers])
    return x.astype(np.float32)


def test_kmeans_objective_monotone(blobs):
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), jnp.asarray(blobs), 4)
    res = core.kmeans_fit_traced(blobs, c0, max_iters=100)
    js = np.asarray(res["objectives"])
    assert np.all(np.diff(js) <= 1e-3 * np.abs(js[:-1]) + 1e-6), \
        "k-means J must be monotonically decreasing (Selim & Ismail 1984)"


def test_kmeans_earlystop_fewer_iters_and_accurate(blobs):
    x = jnp.asarray(blobs)
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(1), x, 4)
    res = core.kmeans_fit_traced(blobs, c0, max_iters=200)
    r, h = core.trace_to_rh(res, 4)
    model = core.fit_longtail([(np.asarray(r), np.asarray(h))],
                              algorithm="kmeans", dataset="blobs",
                              family="quadratic")
    h_star = model.threshold_for(0.95)
    _, labels, _, iters = core.kmeans_fit_earlystop(x, c0, h_star,
                                                    max_iters=200)
    assert int(iters) <= res["n_iters"]
    acc = float(core.rand_index(labels, res["labels"], 4, 4))
    assert acc >= 0.90          # close to the 95% desired accuracy


def test_kmeans_empty_cluster_keeps_centroid():
    x = jnp.asarray(np.array([[0.0, 0], [0.1, 0], [10, 10]], np.float32))
    # one centroid far away from everything → empty after assignment
    c0 = jnp.asarray([[0.0, 0.0], [100.0, 100.0]], jnp.float32)
    c1, labels, j = core.kmeans_step(x, c0)
    assert np.allclose(np.asarray(c1)[1], [100.0, 100.0])
    assert jnp.all(jnp.isfinite(c1))


def test_kmeans_full_equals_traced_final(blobs):
    x = jnp.asarray(blobs)
    c0 = core.random_init(jax.random.PRNGKey(2), x, 4)
    res = core.kmeans_fit_traced(blobs, c0, max_iters=300)
    _, labels, j, iters = core.kmeans_fit_full(x, c0, max_iters=300)
    assert float(core.rand_index(labels, res["labels"], 4, 4)) == \
        pytest.approx(1.0, abs=1e-6)


def test_kernel_path_matches_jnp_path(blobs):
    x = jnp.asarray(blobs[:512])
    c0 = core.random_init(jax.random.PRNGKey(3), x, 4)
    l1, s1, n1, j1 = core.assign_and_stats(x, c0, use_kernel=False)
    l2, s2, n2, j2 = core.assign_and_stats(x, c0, use_kernel=True)
    assert (l1 == l2).all()
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(j1, j2, rtol=1e-5)


def test_em_loglik_monotone(blobs):
    x = jnp.asarray(blobs)
    p0 = em_gmm.random_init(jax.random.PRNGKey(0), x, 4)
    res = em_gmm.em_fit_traced(blobs, p0, max_iters=60, tol=1e-12)
    js = np.asarray(res["objectives"])
    viol = np.diff(js) / np.maximum(np.abs(js[:-1]), 1e-9)
    assert viol.min() > -1e-5, \
        "EM log-likelihood must be non-decreasing up to f32 noise (Wu 1983)"


def test_em_recovers_separated_blobs(blobs):
    x = jnp.asarray(blobs)
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(4), x, 4)
    p0 = em_gmm.init_from_kmeans(x, c0)
    res = em_gmm.em_fit_traced(blobs, p0, max_iters=100, tol=1e-12)
    truth = np.repeat(np.arange(4), 500)
    acc = float(core.rand_index(res["labels"], jnp.asarray(truth), 4, 4))
    assert acc > 0.99


def test_em_kernel_path_matches(blobs):
    x = jnp.asarray(blobs[:512])
    p0 = em_gmm.random_init(jax.random.PRNGKey(5), x, 4)
    o1 = em_gmm.estep_stats(x, p0, use_kernel=False)
    o2 = em_gmm.estep_stats(x, p0, use_kernel=True)
    assert (o1[0] == o2[0]).all()
    np.testing.assert_allclose(o1[1], o2[1], rtol=1e-5)
    for a, b in zip(o1[2:], o2[2:]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-3)


def test_long_tail_exists_on_paper_like_data():
    """The core phenomenon (Fig. 5): high accuracy reached well before
    convergence on a realistic dataset."""
    x = load("road3d", n=6000, seed=7)
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(6), jnp.asarray(x), 8)
    res = core.kmeans_fit_traced(x, c0, max_iters=300)
    if res["n_iters"] < 10:
        pytest.skip("converged too fast to exhibit a tail")
    r = core.trace_accuracy(res["labels_history"], 8)
    # accuracy at 50% of iterations should already be ≥ 95%
    mid = res["n_iters"] // 2
    assert float(r[mid]) > 0.95


def _em_host_estep(x, w, params):
    """float64 E-step: log densities [N, K], weighted responsibilities
    [N, K], and their mass, first and second moments and log-likelihood."""
    x = np.asarray(x, np.float64)
    mu = np.asarray(params.means, np.float64)
    var = np.asarray(params.var, np.float64)
    log_w = np.asarray(params.log_w, np.float64)
    quad = (((x[:, None, :] - mu[None]) ** 2) / var[None]).sum(-1)
    lp = log_w[None] - 0.5 * (quad + np.log(var).sum(-1)[None]
                              + x.shape[1] * np.log(2 * np.pi))
    top = lp.max(1)
    lse = top + np.log(np.exp(lp - top[:, None]).sum(1))
    resp = np.exp(lp - lse[:, None]) * w[:, None]
    return lp, resp, resp.sum(0), resp.T @ x, resp.T @ (x * x), (lse * w).sum()


@pytest.mark.parametrize("d", [1, 3, 11])
@pytest.mark.parametrize("k", [1, 2, 6, 10])
def test_em_chunk_stats_match_float64(k, d):
    """The engine's masked EM chunk pass (``EMAlgorithm.chunk_stats``)
    against a float64 E-step on the host; rows with mask 0 add nothing."""
    from repro.core.engine import EMAlgorithm
    rng = np.random.default_rng(10 * k + d)
    n = 2000
    mu = rng.uniform(-3, 3, (k, d)).astype(np.float32)
    var = rng.uniform(0.5, 2.0, (k, d)).astype(np.float32)
    log_w = np.log(rng.dirichlet(np.ones(k))).astype(np.float32)
    z = rng.integers(0, k, n)
    x = (mu[z] + rng.normal(0, 1, (n, d)) * np.sqrt(var[z])).astype(np.float32)
    w = np.ones(n, np.float32)
    w[::5] = 0.0                             # masked rows inside the set
    w[-100:] = 0.0                           # and a padded tail
    x[-100:] = 1e3                           # that would dominate any sum
    params = em_gmm.GMMParams(jnp.asarray(mu), jnp.asarray(var),
                              jnp.asarray(log_w))
    labels, (r_sum, r_x, r_x2, loglik) = EMAlgorithm().chunk_stats(
        jnp.asarray(x), jnp.asarray(w), params)
    lp, resp, want_sum, want_x, want_x2, want_ll = _em_host_estep(x, w,
                                                                  params)
    labels = np.asarray(labels)
    assert np.all((labels == -1) == (w == 0))
    live = w > 0
    rows = np.flatnonzero(live)
    # the most responsible component; a float32 tie may take either one
    gap = lp[rows].max(1) - lp[rows, labels[live]]
    assert np.all(gap <= 1e-5 * (1.0 + np.abs(lp[rows].max(1))))
    # float32 against float64, relative to the sums of the terms' sizes
    # (largest reading 1.3e-6)
    rtol = 1e-5
    abs_x = resp.T @ np.abs(np.asarray(x, np.float64))
    assert np.all(np.abs(np.asarray(r_sum) - want_sum) <= rtol * want_sum)
    assert np.all(np.abs(np.asarray(r_x) - want_x) <= rtol * abs_x)
    assert np.all(np.abs(np.asarray(r_x2) - want_x2) <= rtol * want_x2)
    assert abs(float(loglik) - want_ll) <= rtol * abs(want_ll)
