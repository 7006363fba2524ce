"""Plain float64 reference of one clustering job, and the numbers compared.

A job is ``repro.launch.cluster.run_production`` on one data set: k-means++
seeding from ``PRNGKey(init_seed)``, Lloyd sweeps with the paper's Eq. 7
stop (h_i = |J_i - J_{i-1}| / |J_{i-1}| <= h* on ``patience`` consecutive
iterations from the third on, or centroids frozen bit for bit, or
``max_iters``), then one labels pass at the final centroids.  The
full-convergence run is the same fit with the h stop off and 3 x max_iters.

The reference imports nothing of the program.  It follows the documented
seeding semantics (one key split per draw; the first point by ``randint``;
each further point by inverse-CDF sampling of the D^2 weights, as
``jax.random.choice`` with ``p`` draws it), and takes only the random bits
from ``jax.random``.  The sweeps are plain ``jax.numpy`` in float64 on the
host's CPU, one jitted pass each.  Both stops lie on one trajectory from
the same seeding, so one replay gives both.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

_EPS = 1e-30


class Fit(NamedTuple):
    """The outputs of one fit that the comparison reads."""
    labels: np.ndarray      # [N] int
    objective: float        # J at the final centroids
    n_iters: int
    centroids: np.ndarray   # [K, D]


def kmeans_pp(init_seed: int, x: np.ndarray, k: int) -> np.ndarray:
    """k-means++ seeding of ``x`` [N, D] float64 from ``PRNGKey(init_seed)``."""
    import jax
    import jax.numpy as jnp
    with jax.default_device(_cpu()):
        return _kmeans_pp(jax, jnp, init_seed, x, k)


def _kmeans_pp(jax, jnp, init_seed, x, k):
    key = jax.random.PRNGKey(init_seed)
    n = x.shape[0]
    key, sub = jax.random.split(key)
    pick = int(jax.random.randint(sub, (), 0, n))
    c = np.zeros((k, x.shape[1]))
    c[0] = x[pick]
    d2 = np.sum((x - x[pick]) ** 2, axis=1)
    for i in range(1, k):
        key, sub = jax.random.split(key)
        u = float(jax.random.uniform(sub, (), jnp.float32))
        cum = np.cumsum(d2)
        pick = min(int(np.searchsorted(cum, cum[-1] * (1.0 - u))), n - 1)
        c[i] = x[pick]
        d2 = np.minimum(d2, np.sum((x - x[pick]) ** 2, axis=1))
    return c


def _cpu():
    """The host's CPU device, where the reference computes."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def distances(x, c):
    """[N, K] squared distances by the expansion |x|^2 - 2 x.c + |c|^2 (in
    float64 it is exact to ~1e-16 of |x|^2 + |c|^2 here)."""
    import jax
    import jax.numpy as jnp
    xc = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    return (jnp.sum(x * x, axis=1)[:, None] - 2.0 * xc
            + jnp.sum(c * c, axis=1)[None, :])


@functools.lru_cache(maxsize=None)
def _sweep_fn(dist):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sweep(x, c):
        d2 = dist(x, c)
        labels = jnp.argmin(d2, axis=1)
        j = jnp.sum(jnp.maximum(jnp.min(d2, axis=1), 0.0))
        onehot = jax.nn.one_hot(labels, c.shape[0], dtype=x.dtype)
        sums = jnp.matmul(onehot.T, x, precision=jax.lax.Precision.HIGHEST)
        return labels, sums, jnp.sum(onehot, axis=0), j

    return sweep


def lloyd(x: np.ndarray, c0: np.ndarray, *, h_star: float, patience: int,
          max_iters: int, full_max_iters: int,
          dist=distances) -> tuple[Fit, Fit]:
    """(early-stopped fit, full-convergence fit) from the seeding ``c0``,
    computed on the host's CPU in the dtype of ``x`` (float64 for the
    reference); ``dist`` gives the [N, K] squared distances."""
    import jax
    with jax.enable_x64(True):
        dev = _cpu()
        xd = jax.device_put(np.asarray(x), dev)
        sweep = _sweep_fn(dist)
        c = np.asarray(c0, x.dtype)
        j_prev, hits, it, moved = np.inf, 0, 0, True
        early_c, early_it = None, None

        def early_live():
            return it < max_iters and moved and (it < 2 or hits < patience)

        while it < full_max_iters and moved:
            if early_c is None and not early_live():
                early_c, early_it = c, it
            _, sums, counts, j = jax.device_get(
                sweep(xd, jax.device_put(c, dev)))
            j = float(j)
            new = np.where(counts[:, None] > 0,
                           sums / np.maximum(counts, 1.0)[:, None],
                           c).astype(x.dtype)
            h = abs(j - j_prev) / max(abs(j_prev), _EPS) \
                if np.isfinite(j_prev) else np.inf
            hits = hits + 1 if h <= h_star else 0
            moved = bool(np.any(new != c))
            c, j_prev, it = new, j, it + 1
        if early_c is None:
            early_c, early_it = c, it

        def final(cc, n_it):
            labels, _, _, j = jax.device_get(
                sweep(xd, jax.device_put(cc, dev)))
            return Fit(np.asarray(labels), float(j), n_it, cc)

        return final(early_c, early_it), final(c, it)


def rand_index(a, b, k: int) -> float:
    """Rand index of two labelings in [0, k): exact pair counts from an
    int64 contingency table and Python integers."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    n = int(a.shape[0])
    table = np.bincount(a * k + b, minlength=k * k).reshape(k, k)

    def pairs(v):
        return sum(int(t) * (int(t) - 1) // 2 for t in np.ravel(v))

    together = pairs(table)
    in_a = pairs(table.sum(axis=1))
    in_b = pairs(table.sum(axis=0))
    total = n * (n - 1) // 2
    if total == 0:
        return 1.0
    return (total + 2 * together - in_a - in_b) / total


def self_consistency(x: np.ndarray, fit: Fit) -> tuple[float, float]:
    """What the fit says of itself, judged in float64 at its own centroids:
    (worst excess of a label's distance over the nearest centroid's, as a
    share of |x|^2 + |c|^2; relative gap of its J to the J of its labels)."""
    import jax
    with jax.enable_x64(True):
        dev = _cpu()
        xd = jax.device_put(np.asarray(x, np.float64), dev)
        c = jax.device_put(np.asarray(fit.centroids, np.float64), dev)
        d2 = np.asarray(distances(xd, c))
    x = np.asarray(x, np.float64)
    cn = np.asarray(fit.centroids, np.float64)
    labels = np.asarray(fit.labels, np.int64)
    mine = np.take_along_axis(d2, labels[:, None], axis=1)[:, 0]
    scale = np.sum(x * x, axis=1) + np.sum(cn * cn, axis=1)[labels]
    excess = float(np.max((mine - d2.min(axis=1)) / np.maximum(scale, _EPS)))
    j_ref = float(np.sum(np.maximum(mine, 0.0)))
    return excess, abs(float(fit.objective) - j_ref) / max(j_ref, _EPS)


def fixed_point_gap(x: np.ndarray, fit: Fit) -> float:
    """How far the fit's centroids lie from the means of the points its
    labels give them (float64), over the data's largest magnitude: 0 up to
    rounding for a run that stopped on frozen centroids."""
    x = np.asarray(x, np.float64)
    labels = np.asarray(fit.labels, np.int64)
    c = np.asarray(fit.centroids, np.float64)
    k = c.shape[0]
    counts = np.bincount(labels, minlength=k)
    sums = np.stack([np.bincount(labels, x[:, q], minlength=k)
                     for q in range(x.shape[1])], axis=1)
    used = counts > 0
    means = sums[used] / counts[used][:, None]
    return float(np.max(np.abs(means - c[used])) / np.max(np.abs(x)))


def compare(x: np.ndarray, got: Fit, ref: Fit, full: bool = False) -> dict:
    """The numbers compared for one fit against its reference replay;
    ``full`` adds the fixed-point gap of a full-convergence fit."""
    excess, j_gap = self_consistency(x, got)
    out = {
        "iters_gap": abs(int(got.n_iters) - int(ref.n_iters)),
        "label_gap": float(np.mean(np.asarray(got.labels) != ref.labels)),
        "label_excess": excess,
        "objective_gap": j_gap,
    }
    if full:
        out["fixed_point_gap"] = fixed_point_gap(x, got)
    return out
