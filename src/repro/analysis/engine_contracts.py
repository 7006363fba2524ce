"""The config-matrix harness: trace every engine cell, run the rules.

This is the linter's driver.  For every ``(mode, use_kernel,
stats_compression, prefetch)`` combination × algorithm it builds the
*production* shard_map'd fit programs through
``ClusteringEngine.sharded_fit_callable`` / ``sharded_restarts_callable``
(the same code path ``fit_sharded`` runs), traces them with
``jax.make_jaxpr`` — tracing never executes the fit — and walks the
jaxprs with the :mod:`repro.analysis.graph_rules` passes (GC001–GC004).
Two checks need more than a trace:

  GC005  lowers + compiles ONE stats reduction (``_stats_reducer``'s
         ``reduce_stats`` under shard_map — a sub-second compile, no
         fit execution) and cross-checks the collective bytes in the
         optimized HLO against ``stats_wire_bytes``'s analytic account;
  GC006  hashes every ``EngineConfig`` field (static jit cache key) and
         traces the fit at two ``h_star`` values — identical jaxprs
         prove the sweep axis is traced, not baked in.

Params come from ``jax.eval_shape`` over the real initialisers, so even
k-means++ init never runs — the whole lint is trace/compile only.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp

from repro.analysis import graph_rules
from repro.analysis.report import Finding, Report

GRAPH_RULES = ("GC001", "GC002", "GC003", "GC004", "GC005", "GC006")
ALGORITHMS = ("kmeans", "em")

_N_POINTS, _DIM, _K = 64, 3, 3


def _data(n_points: int = _N_POINTS, dim: int = _DIM):
    # deterministic, RNG-free: the lint only reads shapes and structure
    return (jnp.arange(n_points * dim, dtype=jnp.float32)
            .reshape(n_points, dim) % 17.0)


def default_mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


def config_matrix(matrix: str = "full"):
    """Every fit-relevant static-config combination (16 cells), or the
    4-cell ``quick`` diagonal that still covers each option at least
    once."""
    from repro.core.engine import EngineConfig
    cells = []
    for mode, kern, comp, pref in itertools.product(
            ("full", "minibatch"), (False, True),
            ("none", "int8_ef"), (False, True)):
        cells.append(EngineConfig(
            max_iters=4, chunks=4, mode=mode,
            batch_chunks=2 if mode == "minibatch" else 0,
            use_kernel=kern, stats_compression=comp, prefetch=pref))
    if matrix == "quick":
        picks = {("full", False, "none", False),
                 ("full", True, "int8_ef", True),
                 ("minibatch", True, "none", True),
                 ("minibatch", False, "int8_ef", False)}
        cells = [c for c in cells
                 if (c.mode, c.use_kernel, c.stats_compression,
                     c.prefetch) in picks]
    return cells


def cell_desc(alg: str, cfg) -> str:
    return (f"{alg}|mode={cfg.mode}|kernel={int(cfg.use_kernel)}"
            f"|comp={cfg.stats_compression}|prefetch={int(cfg.prefetch)}")


def _zero_params(eng, x, k: int, restarts: int | None = None):
    """Concrete zero-filled params with the initialiser's exact pytree
    structure — via eval_shape, so init itself never executes."""
    key = jax.random.key(0)
    if restarts is None:
        shapes = jax.eval_shape(lambda kk: eng.init(kk, x, k), key)
    else:
        shapes = jax.eval_shape(
            lambda kk: eng.init_restarts(kk, x, k, restarts), key)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


_JAXPR_CHECKS = {
    "GC001": graph_rules.check_collective_uniformity,
    "GC002": graph_rules.check_host_transfers,
    "GC003": graph_rules.check_fp64,
    "GC004": graph_rules.check_stop_stats_precision,
}


def check_cell(alg: str, cfg, mesh, rules, *,
               include_restarts: bool = True) -> list[Finding]:
    """Trace one engine cell's fit (and restarts) drivers, run the
    jaxpr rules."""
    from repro.core.engine import ClusteringEngine
    desc = cell_desc(alg, cfg)
    eng = ClusteringEngine(alg, cfg)
    x = _data()
    findings: list[Finding] = []
    progs = [("fit_sharded",
              eng.sharded_fit_callable(x, _zero_params(eng, x, _K), mesh))]
    if include_restarts:
        progs.append((
            "fit_restarts_sharded",
            eng.sharded_restarts_callable(
                x, _zero_params(eng, x, _K, restarts=2), mesh)))
    for name, prog in progs:
        jaxpr = jax.make_jaxpr(prog.fn)(*prog.args)
        for rule in rules:
            check = _JAXPR_CHECKS.get(rule)
            if check is not None:
                findings += check(jaxpr, name, config=desc)
    return findings


# ------------------------------------------------------------------ GC005

def check_wire_bytes(mesh, algorithms=ALGORITHMS,
                     compressions=("none", "int8_ef"),
                     analytic_fn=None) -> list[Finding]:
    """GC005 — compile one stats reduction per (algorithm, compression),
    count its HLO collective bytes, compare with the analytic account.

    ``analytic_fn(stats_like, axis_size, compression)`` defaults to
    ``core.engine.stats_wire_bytes`` (injectable so the mismatch path is
    testable)."""
    import math

    from jax.sharding import PartitionSpec as P
    from repro.core.engine import (ClusteringEngine, EngineConfig,
                                   _stats_reducer, stats_wire_bytes)
    from repro.distribution.compression import ring_wire_bytes
    analytic_fn = analytic_fn or stats_wire_bytes
    n = mesh.devices.size
    findings = []
    # probe shapes are larger than the trace matrix's (and axis-aligned)
    # so the real byte counts dwarf the ring-padding slack below
    probe_k, probe_dim = max(8, n), 32
    for alg_name, comp in itertools.product(algorithms, compressions):
        cfg = EngineConfig(stats_compression=comp, axis_name="data",
                           stats_axis_size=n if comp != "none" else 0)
        eng = ClusteringEngine(alg_name, cfg)
        x = _data(dim=probe_dim)
        params = _zero_params(eng, x, probe_k)
        stats = eng.algorithm.zero_stats(params)
        init_ef, reduce_stats = _stats_reducer(eng.algorithm, cfg)

        def one_reduction(stats, params):
            out, _ = reduce_stats(stats, init_ef(stats), params)
            return out

        rep_s = jax.tree.map(lambda a: P(*(None,) * jnp.ndim(a)), stats)
        rep_p = jax.tree.map(lambda a: P(*(None,) * jnp.ndim(a)), params)
        fn = jax.shard_map(one_reduction, mesh=mesh,
                           in_specs=(rep_s, rep_p), out_specs=rep_s,
                           check_vma=False)
        hlo = jax.jit(fn).lower(stats, params).compile().as_text()
        hlo_per_family = graph_rules.hlo_wire_bytes(hlo, n)
        measured = sum(hlo_per_family.values())
        expected = analytic_fn(stats, n, comp)
        # principled slack: the int8 ring pads each leaf's per-hop chunk
        # to ceil(numel/N), and XLA may leave the shared-scale pmax
        # unmerged with the reduction — both bounded per leaf; an
        # account/dtype error produces a ~4× mismatch, far outside it
        slack = 64.0 + 0.02 * expected
        if comp == "int8_ef":
            for a in jax.tree.leaves(stats):
                numel = math.prod(jnp.shape(a))
                if jnp.ndim(a) >= 2:
                    slack += (2 * (n - 1) * math.ceil(numel / n)
                              - ring_wire_bytes(numel, n))
                    slack += ring_wire_bytes(4 * jnp.shape(a)[0], n)
        if abs(measured - expected) > slack:
            fam = ", ".join(f"{k}={v:.0f}"
                            for k, v in sorted(hlo_per_family.items()))
            findings.append(Finding(
                "GC005", f"stats_reduction[{alg_name}]",
                f"compiled HLO moves {measured:.0f} wire bytes/device "
                f"({fam}) but stats_wire_bytes accounts {expected} "
                f"(tolerance {slack:.0f}) — the analytic cost model has "
                "drifted from the compiled graph",
                config=f"{alg_name}|comp={comp}"))
    return findings


# ------------------------------------------------------------------ GC006

def check_config_static(cfg=None) -> list[Finding]:
    """GC006 (static half) — every EngineConfig field must hash: the
    config is a static jit argument, and one unhashable field turns every
    fit call into a TypeError (or, with a custom __hash__ that skips the
    field, into silent cache collisions)."""
    from repro.core.engine import EngineConfig
    cfg = cfg if cfg is not None else EngineConfig()
    findings = []
    for field in dataclasses.fields(cfg):
        try:
            hash(getattr(cfg, field.name))
        except TypeError:
            findings.append(Finding(
                "GC006", f"EngineConfig.{field.name}",
                f"field value {getattr(cfg, field.name)!r} is unhashable "
                "— EngineConfig is a static jit argument and every field "
                "must be part of the cache key"))
    try:
        hash(cfg)
    except TypeError:
        findings.append(Finding(
            "GC006", "EngineConfig",
            "config instance is unhashable — cannot be a static jit "
            "argument"))
    return findings


def check_h_star_traced(mesh, alg: str = "kmeans") -> list[Finding]:
    """GC006 (sweep half) — tracing the fit at two h* values must yield
    the *identical* jaxpr: h* is the paper's sweep axis, and a config
    that bakes it into the graph recompiles once per swept value."""
    from repro.core.engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine(alg, EngineConfig(max_iters=4, chunks=4))
    x = _data()
    p0 = _zero_params(eng, x, _K)
    texts = []
    for hs in (0.01, 0.02):
        prog = eng.sharded_fit_callable(x, p0, mesh, h_star=hs)
        texts.append(str(jax.make_jaxpr(prog.fn)(*prog.args)))
    if texts[0] != texts[1]:
        return [Finding(
            "GC006", "fit_sharded(h_star)",
            "sweeping h_star changes the traced graph — the stopping "
            "threshold is baked in as a constant instead of riding as a "
            "traced argument, so every swept value pays a full "
            "recompile", config=f"{alg}")]
    return []


# ------------------------------------------------------------------ driver

def run_graph_lint(mesh=None, matrix: str = "full", rules=None,
                   algorithms=ALGORITHMS, *,
                   include_restarts: bool = True) -> Report:
    """Trace the full engine config matrix and run every requested
    graph-contract rule; returns the populated :class:`Report`."""
    mesh = mesh if mesh is not None else default_mesh()
    rules = tuple(rules) if rules else GRAPH_RULES
    report = Report(rules_run=[r for r in GRAPH_RULES if r in rules])
    if any(r in _JAXPR_CHECKS for r in rules):
        for cfg in config_matrix(matrix):
            for alg in algorithms:
                report.configs.append(cell_desc(alg, cfg))
                report.extend(check_cell(alg, cfg, mesh, rules,
                                         include_restarts=include_restarts))
    if "GC005" in rules:
        report.extend(check_wire_bytes(mesh, algorithms))
    if "GC006" in rules:
        report.extend(check_config_static())
        report.extend(check_h_star_traced(mesh))
    return report
