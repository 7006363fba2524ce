"""EM for diagonal-covariance Gaussian mixtures — the paper's second algorithm.

The E-step log-density is decomposed into three [N,D]×[D,K] matmuls
(x²·(1/σ²)ᵀ, x·(μ/σ²)ᵀ and constants), so the hot loop is MXU-shaped like the
k-means assignment (DESIGN.md §2); the fused Pallas version lives in
``repro.kernels.gmm_estep``.  Objective = total log-likelihood, monotonically
increasing (Wu 1983), so Eq. 7's change rate applies unchanged.

Diagonal covariance is a documented assumption (DESIGN.md §6): the paper does
not specify the covariance structure; diagonal is the standard big-data
choice and keeps the E-step matmul-friendly.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_LOG2PI = 1.8378770664093453


class GMMParams(NamedTuple):
    means: jnp.ndarray     # [K, D]
    var: jnp.ndarray       # [K, D] diagonal covariance
    log_w: jnp.ndarray     # [K] log mixture weights


VAR_FLOOR = 1e-6
# f32 matmuls: the TPU's default precision multiplies in bf16
_HI = jax.lax.Precision.HIGHEST


def log_prob(x, params: GMMParams):
    """[N,K] per-component log densities via the matmul decomposition."""
    x = x.astype(jnp.float32)
    inv_var = 1.0 / params.var                                   # [K,D]
    # Σ_d (x−μ)²/σ² = x²·(1/σ²) − 2·x·(μ/σ²) + Σ_d μ²/σ²
    quad = (jnp.matmul(x * x, inv_var.T, precision=_HI)
            - 2.0 * jnp.matmul(x, (params.means * inv_var).T, precision=_HI)
            + jnp.sum(params.means ** 2 * inv_var, axis=-1)[None, :])
    log_det = jnp.sum(jnp.log(params.var), axis=-1)              # [K]
    d = x.shape[-1]
    return (params.log_w[None, :]
            - 0.5 * (quad + log_det[None, :] + d * _LOG2PI))


def estep_stats(x, params: GMMParams, axis_name=None, use_kernel: bool = False,
                mask=None, kernel_backend: str | None = None):
    """Fused E-step: responsibilities → (labels, loglik, r_sum, r_x, r_x2).

    All M-step sufficient statistics come out of one pass over the points —
    the same contract as the ``gmm_estep`` kernel op.  ``use_kernel``
    routes through the kernel dispatch layer (``repro.kernels.dispatch``;
    ``kernel_backend`` forces a registry backend).  ``mask``: [N] f32 row
    weights (streaming-chunk padding) — honoured by both paths.
    """
    if use_kernel:
        from repro.kernels.gmm_estep import ops as _gops
        labels, loglik, r_sum, r_x, r_x2 = _gops.gmm_estep(
            x, params.means, params.var, params.log_w, mask=mask,
            backend=kernel_backend)
    else:
        lp = log_prob(x, params)                                 # [N,K]
        lse = jax.scipy.special.logsumexp(lp, axis=-1)           # [N]
        resp = jnp.exp(lp - lse[:, None])                        # [N,K]
        labels = jnp.argmax(lp, axis=-1).astype(jnp.int32)
        if mask is not None:
            mask = mask.astype(jnp.float32)
            resp = resp * mask[:, None]
            loglik = jnp.sum(lse * mask)
            # weight-0 rows are labelled -1 — the kernel ops' mask contract
            labels = jnp.where(mask > 0, labels, -1)
        else:
            loglik = jnp.sum(lse)
        r_sum = jnp.sum(resp, axis=0)                            # [K]
        xf = x.astype(jnp.float32)
        r_x = jnp.matmul(resp.T, xf, precision=_HI)              # [K,D]
        r_x2 = jnp.matmul(resp.T, xf * xf, precision=_HI)        # [K,D]
    if axis_name is not None:
        loglik = jax.lax.psum(loglik, axis_name)
        r_sum = jax.lax.psum(r_sum, axis_name)
        r_x = jax.lax.psum(r_x, axis_name)
        r_x2 = jax.lax.psum(r_x2, axis_name)
    return labels, loglik, r_sum, r_x, r_x2


def mstep(params: GMMParams, r_sum, r_x, r_x2, n_total) -> GMMParams:
    safe = jnp.maximum(r_sum, 1e-10)[:, None]
    means = r_x / safe
    var = jnp.maximum(r_x2 / safe - means ** 2, VAR_FLOOR)
    # Components with no support keep their old parameters (mirrors k-means
    # empty-cluster handling).
    alive = (r_sum > 1e-8)[:, None]
    means = jnp.where(alive, means, params.means)
    var = jnp.where(alive, var, params.var)
    log_w = jnp.log(jnp.maximum(r_sum / n_total, 1e-20))
    return GMMParams(means=means, var=var, log_w=log_w)


def minibatch_mstep(params: GMMParams, r_sum, r_x, r_x2, v, n_batch,
                    decay: float = 1.0):
    """Stepwise-EM M-step from subsampled responsibilities.

    Mirrors the k-means minibatch rule (see
    ``kmeans.minibatch_update_centroids``) with soft counts: ``v`` holds each
    component's cumulative responsibility mass, and the batch estimates are
    blended in with the per-component step size η_k = r_sum_k / v_k — the
    Robbins-Monro 1/t schedule of stepwise EM (Cappé & Moulines 2009), here
    annealed per component so rarely-responsible components are not dragged
    by large global steps.  ``decay`` < 1 forgets old mass exponentially;
    ``decay`` = 1 recovers the plain stochastic-approximation schedule.

    Sharded contract (shard_map): ``r_sum``/``r_x``/``r_x2`` and
    ``n_batch`` must arrive already psum'd over the data axes (the engine
    reduces shard-local E-step stats before the update), so ``v`` holds
    GLOBAL responsibility mass, η_k anneals on the global stream, the
    weight estimate ``r_sum / n_batch`` is the global batch fraction, and
    (params, v) stay replicated across shards with no extra collective.

    Returns (new_params, new_v).  Components with (numerically) zero batch
    responsibility keep their parameters, mirroring ``mstep``.
    """
    v_new = decay * v + r_sum
    eta = (r_sum / jnp.maximum(v_new, 1e-10))[:, None]           # [K, 1]
    safe = jnp.maximum(r_sum, 1e-10)[:, None]
    mu_b = r_x / safe
    var_b = jnp.maximum(r_x2 / safe - mu_b ** 2, VAR_FLOOR)
    alive = (r_sum > 1e-8)[:, None]
    means = jnp.where(alive, params.means + eta * (mu_b - params.means),
                      params.means)
    var = jnp.where(alive,
                    jnp.maximum(params.var + eta * (var_b - params.var),
                                VAR_FLOOR),
                    params.var)
    w_b = r_sum / jnp.maximum(n_batch, 1.0)                      # [K]
    w = jnp.exp(params.log_w)
    w = jnp.where(alive[:, 0], w + eta[:, 0] * (w_b - w), w)
    w = w / jnp.maximum(jnp.sum(w), 1e-20)
    return GMMParams(means=means, var=var,
                     log_w=jnp.log(jnp.maximum(w, 1e-20))), v_new


def em_step(x, params: GMMParams, n_total=None, axis_name=None,
            use_kernel: bool = False):
    """One EM iteration. Returns (new_params, labels, loglik)."""
    labels, loglik, r_sum, r_x, r_x2 = estep_stats(x, params, axis_name, use_kernel)
    if n_total is None:
        n_total = jnp.asarray(x.shape[0], jnp.float32)
        if axis_name is not None:
            n_total = jax.lax.psum(n_total, axis_name)
    return mstep(params, r_sum, r_x, r_x2, n_total), labels, loglik


def init_from_kmeans(x, centroids) -> GMMParams:
    """Means from k-means; shared isotropic variance; uniform weights."""
    k = centroids.shape[0]
    x = x.astype(jnp.float32)
    global_var = jnp.maximum(jnp.var(x, axis=0), VAR_FLOOR)
    return GMMParams(
        means=jnp.asarray(centroids, jnp.float32),
        var=jnp.broadcast_to(global_var, (k, x.shape[1])).astype(jnp.float32),
        log_w=jnp.full((k,), -jnp.log(k), jnp.float32),
    )


def random_init(key, x, k: int) -> GMMParams:
    from .kmeans import random_init as km_random
    return init_from_kmeans(x, km_random(key, x, k))


# --------------------------------------------------------------------------
# Drivers (mirror repro.core.kmeans)
# --------------------------------------------------------------------------

def em_fit_traced(x, params0: GMMParams, max_iters: int = 500,
                  tol: float = 0.0, use_kernel: bool = False,
                  chunks: int = 1):
    """Host loop recording (loglik_i, labels_i) — for training groups."""
    from .engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine("em", EngineConfig(use_kernel=use_kernel,
                                              chunks=chunks))
    params = params0
    x = jnp.asarray(x)
    labels_hist, js = [], []
    prev = None
    for _ in range(max_iters):
        params, labels, loglik = eng.step(x, params)
        labels_hist.append(labels)
        js.append(float(loglik))
        if prev is not None and abs(js[-1] - prev) <= tol * max(abs(prev), 1e-30):
            break
        prev = js[-1]
    return {
        "labels_history": jnp.stack(labels_hist),
        "objectives": jnp.asarray(js),
        "labels": labels_hist[-1],
        "params": params,
        "n_iters": len(js),
    }


def em_fit_earlystop(x, params0: GMMParams, h_star, max_iters: int = 500,
                     axis_name=None, use_kernel: bool = False,
                     patience: int = 1, chunks: int = 1):
    """Production driver: stop on device when h_i ≤ h* for ``patience``
    consecutive iterations (Eq. 7 on loglik; see kmeans_fit_earlystop)."""
    from .engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine("em", EngineConfig(
        max_iters=max_iters, patience=patience, chunks=chunks,
        axis_name=axis_name, use_kernel=use_kernel,
        use_h_stop=True, stop_when_frozen=False))
    res = eng.fit(x, params0, h_star=h_star)
    return res.params, res.labels, res.objective, res.n_iters


def em_fit_full(x, params0: GMMParams, max_iters: int = 1000, axis_name=None,
                use_kernel: bool = False, chunks: int = 1):
    """Reference run: converge to (near) machine-precision loglik stability."""
    return em_fit_earlystop(x, params0, h_star=1e-12, max_iters=max_iters,
                            axis_name=axis_name, use_kernel=use_kernel,
                            chunks=chunks)
