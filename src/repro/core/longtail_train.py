"""Configuration-matched long-tail training (the paper's §4–§5.4 pipeline,
harvested under the production engine regime).

The paper's contribution is the *training phase*: run sample groups to
convergence, harvest (accuracy r_i, change-rate h_i) pairs, fit h = f(r)
(Eq. 8 family comparison) and reuse h* = f(r*) forever.  The original
repo fitted that regression only from full-batch traces replayed host-side
(``kmeans_fit_traced`` step loops) and *transferred* h* to minibatch /
kernel / sharded production runs via the paired Eq. 7 stop.  That works —
the pairing keeps the h scale compatible — but the ROADMAP (and the
cost-aware cloud tooling in PAPERS.md: D-SPACE4Cloud, DV-ARPA) is explicit
that a performance model should be trained under the configuration it will
serve.  This module is that trainer:

  · ``harvest_traces`` runs each training group through the engine's fit
    drivers with ``EngineConfig(trace=True)`` — full, minibatch, restarts,
    sharded, with or without ``use_kernel`` — so the recorded h sequence is
    the *exact* statistic the production stop will compare against h*
    (paired same-subsample rate in minibatch mode, psum'd stats under
    shard_map, kernel fp32 accumulation order under ``use_kernel``).
    Accuracy r_i is then read off the recorded parameter trajectory: one
    batched assignment pass per trace (``lax.map`` over the [T, ...]
    params history) labels every iteration's partition, and r_i is the
    Rand index against the *full-batch reference partition* — the
    paper's §3.2 definition (accuracy relative to the converged result).
    Full-mode harvests already end at that partition, so they
    self-reference; minibatch harvests run one cheap offline full-batch
    fit per training group (``reference_partition``) so the fit target
    aligns exactly with the validation metric.

  · ``fit_for_config`` pools those traces, runs the Eq. 8 family
    comparison (or a pinned family) and stamps the harvest regime into
    ``LongTailModel.engine_config`` — ``EngineConfig.from_longtail``
    compares that provenance against the production config and warns
    loudly on a mismatch.

``BENCH_longtail_matched.json`` (benchmarks/run.py ``longtail_matched``)
tracks the payoff: the matched fit's achieved-accuracy spread vs the
transferred full-batch h* on the same held-out groups.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans

from . import em_gmm as _em
from . import kmeans as _km
from .earlystop import LongTailModel, fit_longtail
from .engine import ClusteringEngine, EngineConfig, Trace, get_algorithm
from .rand_index import contingency_table, rand_index_from_contingency

# EM full-batch harvest stop: relative log-likelihood change below the
# legacy em_fit_traced tolerance counts as converged.
_EM_TOL = 1e-12


def config_fingerprint(config: EngineConfig, devices: int = 1) -> dict:
    """The regime a harvest ran under, as JSON-stampable provenance.

    ``devices`` records the mesh size for the record only —
    ``EngineConfig.from_longtail`` does not warn on it, because the sharded
    drivers reproduce the single-device trajectory up to fp32 reduction
    order (chunk-global layout + replicated draws, regression-tested), so
    a model fitted on 1 device serves an 8-device mesh mode-matched.
    """
    d = config.matched_fingerprint()
    d["devices"] = int(devices)
    return d


def harvest_config(production: EngineConfig, algorithm: str, *,
                   max_iters: int | None = None,
                   seed: int | None = None) -> EngineConfig:
    """Derive the trace-harvest config from the production config.

    Everything regime-defining (mode, chunk layout, batch_chunks, decay,
    ema, kernel routing) is kept; only the stop is re-aimed at *full
    convergence* so the trace covers the whole tail the regression must
    see: k-means full mode stops on frozen centroids (an h-based stop at
    h*=0 quits on fp32 J plateaus before the Lloyd fixed point), EM full
    mode stops at the legacy ``em_fit_traced`` tolerance, and minibatch
    mode runs until the paired rate sits at exactly 0 with patience (or
    ``max_iters`` — learning-rate updates have no frozen fixed point).
    """
    kw: dict = dict(trace=True, h_star=0.0)
    if max_iters is not None:
        kw["max_iters"] = max_iters
    if production.mode == "minibatch":
        kw.update(use_h_stop=True, stop_when_frozen=False,
                  patience=max(production.patience, 3))
        if seed is not None:
            kw["seed"] = seed
    elif algorithm == "kmeans":
        kw.update(use_h_stop=False, stop_when_frozen=True)
    else:
        kw.update(use_h_stop=True, h_star=_EM_TOL, patience=1,
                  stop_when_frozen=False)
    return dataclasses.replace(production, **kw)


@dataclasses.dataclass(frozen=True)
class TrainingPlan:
    """What to harvest and fit: algorithm, k, and — the point of this
    module — the production :class:`EngineConfig` the traces must be
    recorded under.  ``restarts`` > 1 harvests every restart's trace from
    one vmapped fleet per group (R traces per group for the price of one
    batched program); ``max_iters`` overrides the harvest iteration budget
    without touching the production config; ``family=None`` runs the
    Eq. 8 model-selection comparison and keeps the winner."""
    algorithm: str = "kmeans"
    k: int = 2
    config: EngineConfig = EngineConfig()
    family: str | None = "quadratic"
    balanced: bool = False
    restarts: int = 1
    max_iters: int | None = None
    seed: int = 0
    dataset: str = "train"


def _group_init(algorithm: str, key, x, k: int, chunks: int):
    """Per-group seeding, matching the production CLI's convention:
    streamed k-means++ for k-means, k-means++-seeded GMMs for EM."""
    c0 = _km.kmeans_plus_plus_init(key, x, k, chunks=chunks)
    if algorithm == "kmeans":
        return c0
    return _em.init_from_kmeans(x, c0)


@functools.partial(jax.jit, static_argnames=("algorithm",))
def _trace_labels(x, params_hist, algorithm: str):
    """[T, N] labels: one full assignment pass per recorded iteration,
    sequential over the trace axis (``lax.map``) so the per-step [N, K]
    intermediate never batches up."""
    alg = get_algorithm(algorithm)
    ones = jnp.ones((x.shape[0],), jnp.float32)

    def one(p):
        labels, _ = alg.chunk_stats(x, ones, p)
        return labels

    return jax.lax.map(one, params_hist)


@functools.partial(jax.jit, static_argnames=("k",))
def _trace_rand(labels_hist, ref_labels, k: int):
    """[T] Rand(P_t, P_ref) — the paper's accuracy metric per iteration."""
    def one(lab):
        return rand_index_from_contingency(
            contingency_table(lab, ref_labels, k, k))

    return jax.lax.map(one, labels_hist)


def engine_trace_to_rh(trace: Trace, x, *, algorithm: str, k: int,
                       ref_labels=None) -> tuple[np.ndarray, np.ndarray]:
    """(r_i, h_i) pairs from one engine trace (§3.2 accuracy + Eq. 7 rate).

    Distinct name from the legacy ``core.trace_to_rh`` (which consumes a
    ``kmeans_fit_traced`` result dict) — this one consumes the engine's
    :class:`Trace`.  ``ref_labels`` is the reference partition accuracy is
    measured against; ``None`` falls back to the trace's own final
    recorded state (the legacy semantics — exact for full-mode harvests,
    which run to the converged partition anyway).  ``harvest_traces``
    passes the *full-batch* reference partition for minibatch harvests,
    where the trace's own endpoint is a subsample approximation and
    self-reference would inflate every r_i (the ROADMAP carry-over this
    fixes): the fit target then aligns exactly with the validation metric.
    Rows with no iteration behind them (mask 0) or an undefined rate
    (h = inf at index 0 of a full-mode trace) are dropped.
    """
    mask = np.asarray(trace.mask)
    h = np.asarray(trace.h, np.float64)
    n_it = int(mask.sum())
    if n_it == 0:
        return np.zeros((0,)), np.zeros((0,))
    # the buffers are [max_iters]-padded; label only the recorded prefix,
    # rounded up to a bucket so differently-deep traces share jit caches
    m = min(mask.shape[0], -(-n_it // 64) * 64)
    params = jax.tree.map(lambda a: a[:m], trace.params)
    labels_hist = _trace_labels(jnp.asarray(x, jnp.float32), params,
                                algorithm)
    ref = (labels_hist[n_it - 1] if ref_labels is None
           else jnp.asarray(ref_labels, jnp.int32))
    r = np.asarray(_trace_rand(labels_hist, ref, k), np.float64)
    valid = (np.arange(m) < n_it) & np.isfinite(h[:m])
    return r[valid], h[:m][valid]


def reference_config(production: EngineConfig, algorithm: str,
                     max_iters: int | None = None) -> EngineConfig:
    """The full-batch reference regime for a production config: same
    memory layout and kernel routing, minibatch knobs reset, stop re-aimed
    at full convergence (frozen centroids / EM tolerance), no trace."""
    full = dataclasses.replace(
        production, mode="full", batch_chunks=0, decay=1.0, seed=0,
        ema=0.0, patience=1)
    cfg = harvest_config(full, algorithm, max_iters=max_iters)
    return dataclasses.replace(cfg, trace=False)


def reference_partition(plan: TrainingPlan, x, params0) -> jnp.ndarray:
    """One cheap offline full-batch fit → the [N] reference labels the
    matched harvest measures accuracy against."""
    cfg = reference_config(plan.config, plan.algorithm,
                           max_iters=plan.max_iters)
    eng = ClusteringEngine(plan.algorithm, cfg)
    return eng.fit(x, params0).labels


@spans.span("stop.harvest")
def harvest_traces(plan: TrainingPlan, groups,
                   mesh=None) -> list[tuple[np.ndarray, np.ndarray]]:
    """Run every training group under the plan's (harvest-adjusted)
    production config and return its (r, h) trace(s).

    ``mesh`` routes each fit through the engine's sharded drivers
    (``fit_sharded`` / ``fit_restarts_sharded``) — the trace is computed
    from psum'd stats, so it comes back replicated and identical to the
    single-device harvest up to fp32 reduction order.

    Minibatch harvests measure r against the group's *full-batch
    reference partition* (one cheap offline full-batch fit per group,
    seeded from the same init) — the trace's own subsample endpoint is
    not the partition production accuracy is validated against, and
    self-reference systematically inflated r (ROADMAP carry-over).
    Full-mode harvests run to the converged partition already, so their
    self-reference IS the full-batch reference and no extra fit runs.
    """
    out: list[tuple[np.ndarray, np.ndarray]] = []
    for gi in range(len(groups)):
        x = jnp.asarray(groups[gi], jnp.float32)
        cfg = harvest_config(
            plan.config, plan.algorithm, max_iters=plan.max_iters,
            seed=(plan.seed + gi
                  if plan.config.mode == "minibatch" else None))
        eng = ClusteringEngine(plan.algorithm, cfg)
        key = jax.random.PRNGKey(plan.seed + gi)
        needs_ref = plan.config.mode == "minibatch"
        if plan.restarts > 1:
            keys = jax.random.split(key, plan.restarts)
            inits = [_group_init(plan.algorithm, kk, x, plan.k, cfg.chunks)
                     for kk in keys]
            params0 = jax.tree.map(lambda *ls: jnp.stack(ls), *inits)
            ref = (reference_partition(plan, x, inits[0])
                   if needs_ref else None)
            rr = (eng.fit_restarts_sharded(x, params0, mesh)
                  if mesh is not None else eng.fit_restarts(x, params0))
            for ri in range(plan.restarts):
                tr = jax.tree.map(lambda a, ri=ri: a[ri], rr.traces)
                out.append(engine_trace_to_rh(
                    tr, x, algorithm=plan.algorithm, k=plan.k,
                    ref_labels=ref))
        else:
            params0 = _group_init(plan.algorithm, key, x, plan.k, cfg.chunks)
            ref = (reference_partition(plan, x, params0)
                   if needs_ref else None)
            res = (eng.fit_sharded(x, params0, mesh)
                   if mesh is not None else eng.fit(x, params0))
            out.append(engine_trace_to_rh(
                res.trace, x, algorithm=plan.algorithm, k=plan.k,
                ref_labels=ref))
    return out


def fit_for_config(plan: TrainingPlan, groups, mesh=None,
                   traces: Sequence[tuple[np.ndarray, np.ndarray]]
                   | None = None) -> LongTailModel:
    """Harvest (unless ``traces`` is supplied) and fit h = f(r) for the
    plan's engine configuration, stamping the regime into the model's
    provenance so ``EngineConfig.from_longtail`` can police the match."""
    if traces is None:
        traces = harvest_traces(plan, groups, mesh=mesh)
    with spans.span("stop.regression"):
        return fit_longtail(
            traces, algorithm=plan.algorithm, dataset=plan.dataset,
            family=plan.family, balanced=plan.balanced,
            engine_config=config_fingerprint(
                plan.config, devices=(mesh.size if mesh is not None else 1)))
