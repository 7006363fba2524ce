"""Every engine regime against its own result contract.

Each case fits one seeded 3,001 × 3 set of overlapping blobs (8 to 60
sweeps a fit) under one regime: algorithm (k-means, EM) × point layout
(flat, 4 chunks, 7 chunks with ragged padding, minibatch 2 of 8 chunks) ×
restarts (one ``fit``, or the best of three from ``fit_restarts``).  Two
contracts hold in every regime:

- recording the per-iteration trace changes nothing: the stop model is
  harvested with ``trace=True`` and production runs with it off, so the
  two must reach the same fit bit for bit;
- the result describes itself: every label is the nearest centroid (the
  most responsible component) at the returned parameters, and the
  returned objective is the float64 objective of those labels and
  parameters.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import ClusteringEngine, EngineConfig

K = 4
H_STAR = 1e-4

LAYOUTS = {
    "flat": dict(chunks=1),
    "chunks4": dict(chunks=4),
    "chunks7": dict(chunks=7),
    "minibatch": dict(mode="minibatch", chunks=8, batch_chunks=2),
}
REGIMES = [(alg, layout, restarts) for alg in ("kmeans", "em")
           for layout in LAYOUTS for restarts in (1, 3)]
IDS = [f"{alg}-{layout}-r{restarts}" for alg, layout, restarts in REGIMES]


@functools.lru_cache(maxsize=None)
def _blobs():
    rng = np.random.default_rng(11)
    centers = np.array([[0, 0, 0], [8, 8, 8], [-8, 8, 0], [8, -8, 4]], float)
    x = np.concatenate([c + rng.normal(0, 4.0, (750, 3)) for c in centers])
    x = np.concatenate([x, rng.normal(0, 6.0, (1, 3))])     # N = 3,001
    return x[rng.permutation(len(x))].astype(np.float32)


@functools.lru_cache(maxsize=None)
def _fit(alg, layout, restarts, trace):
    cfg = EngineConfig(max_iters=60, patience=2, trace=trace,
                       stop_when_frozen=alg == "kmeans", **LAYOUTS[layout])
    eng = ClusteringEngine(alg, cfg)
    x = jnp.asarray(_blobs())
    key = jax.random.PRNGKey(5)
    if restarts == 1:
        return eng.fit(x, eng.init(key, x, K), h_star=H_STAR)
    return eng.fit_restarts(x, key=key, k=K, restarts=restarts,
                            h_star=H_STAR).best


@pytest.mark.parametrize("alg,layout,restarts", REGIMES, ids=IDS)
def test_trace_does_not_change_the_fit(alg, layout, restarts):
    off = _fit(alg, layout, restarts, False)
    on = _fit(alg, layout, restarts, True)
    assert int(on.n_iters) == int(off.n_iters)
    np.testing.assert_array_equal(np.asarray(on.labels),
                                  np.asarray(off.labels))
    for a, b in zip(jax.tree.leaves(on.params), jax.tree.leaves(off.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(on.objective) == float(off.objective)


def _kmeans_scores(x, centroids):
    """float64 squared distances [N, K]."""
    c = np.asarray(centroids, np.float64)
    return ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)


def _em_scores(x, params):
    """float64 log(w_k · N(x | μ_k, diag σ²_k)) [N, K]."""
    mu = np.asarray(params.means, np.float64)
    var = np.asarray(params.var, np.float64)
    log_w = np.asarray(params.log_w, np.float64)
    quad = (((x[:, None, :] - mu[None]) ** 2) / var[None]).sum(-1)
    log_det = np.log(var).sum(-1)
    return log_w[None] - 0.5 * (quad + log_det[None]
                                + x.shape[1] * np.log(2 * np.pi))


@pytest.mark.parametrize("alg,layout,restarts", REGIMES, ids=IDS)
def test_result_is_self_consistent(alg, layout, restarts):
    res = _fit(alg, layout, restarts, False)
    x = _blobs().astype(np.float64)
    labels = np.asarray(res.labels)
    assert labels.shape == (x.shape[0],)
    assert labels.min() >= 0 and labels.max() < K
    rows = np.arange(x.shape[0])
    if alg == "kmeans":
        d2 = _kmeans_scores(x, res.params)
        chosen, best = d2[rows, labels], d2.min(1)
        want = chosen.sum()
    else:
        lp = _em_scores(x, res.params)
        # the most responsible component has the largest log density
        chosen, best = -lp[rows, labels], -lp.max(1)
        top = lp.max(1)
        want = (top + np.log(np.exp(lp - top[:, None]).sum(1))).sum()
    # the label is the argmin/argmax; where two candidates tie within
    # float32 rounding of the score either one is the argmin
    tie = 1e-5 * (1.0 + np.abs(best))
    assert np.all(chosen - best <= tie), np.max(chosen - best)
    got = float(res.objective)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)
