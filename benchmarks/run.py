"""Benchmark harness — one function per paper table/figure + kernel
microbenches.

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run --only fig7,table2

Output: CSV rows to stdout (name,metric,value,…) and benchmarks/out/*.csv.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.timing import time_callable

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
_REGISTRY = {}


def bench(name):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def _emit(name: str, rows: list[dict]):
    os.makedirs(OUT_DIR, exist_ok=True)
    if not rows:
        print(f"# {name}: no rows")
        return
    cols = list(rows[0])
    path = os.path.join(OUT_DIR, f"{name}.csv")
    with open(path, "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")
    print(f"\n== {name} ({path})")
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))


# --------------------------------------------------------------------------
# Fig. 2/3/5 — the long tail
# --------------------------------------------------------------------------

@bench("fig5_longtail")
def fig5_longtail():
    """Clustering accuracy over iterations: iterations to reach 95/99/100%
    of accuracy for both algorithms (the long-tail phenomenon)."""
    from benchmarks.paper_experiments import run_group, load_groups
    rows = []
    for algorithm in ("kmeans", "em"):
        groups, k = load_groups("3D_Road/4")
        g = run_group(groups[0], k, algorithm, seed=1)
        r = g.accuracies
        def first_at(th):
            idx = np.where(r >= th)[0]
            return int(idx[0] + 1) if idx.size else g.n_iters
        rows.append({
            "algorithm": algorithm, "total_iters": g.n_iters,
            "iters_to_95": first_at(0.95), "iters_to_99": first_at(0.99),
            "frac_iters_for_last_1pct":
                round(1 - first_at(0.99) / g.n_iters, 3),
        })
    return rows


# --------------------------------------------------------------------------
# Fig. 6 — the regression model (3D Road Network k=4)
# --------------------------------------------------------------------------

@bench("fig6_regression")
def fig6_regression():
    """h(r) regression per algorithm; paper: h = 1.83r² − 3.66r + 1.83
    (k-means, 3D Road k=4).  Coefficients are data-scale dependent — the
    claim validated here is the *form*: quadratic, h(1)≈0, R² high."""
    from benchmarks.paper_experiments import experiment
    rows = []
    for algorithm in ("kmeans", "em"):
        model, *_ = experiment("3D_Road/4", algorithm)
        c = model.regression.coeffs
        rows.append({
            "algorithm": algorithm, "family": model.regression.family,
            "b0": round(c[0], 6), "b1": round(c[1], 6),
            "b2": round(c[2], 6) if len(c) > 2 else "",
            "r2": round(model.regression.metrics.r2, 4),
            "h_at_r1": round(float(model.regression.predict(1.0)), 8),
        })
    return rows


@bench("model_selection")
def model_selection():
    """§4/§5.5-internal: quadratic vs linear/cubic/exp/lasso by adj-R²."""
    from benchmarks.paper_experiments import experiment, fit_model
    from repro.core import select_model, pool_traces, rh_from_objectives
    rows = []
    for algorithm in ("kmeans", "em"):
        model, train_runs, _, _ = experiment("3D_Road/4", algorithm)
        traces = [(g.accuracies[1:], rh_from_objectives(g.objectives))
                  for g in train_runs]
        r, h = pool_traces(traces)
        _, table = select_model(r, h)
        for fam, m in table.items():
            rows.append({"algorithm": algorithm, "family": fam,
                         "adj_r2": round(m.adj_r2, 4),
                         "rmse": f"{m.rmse:.3e}"})
    return rows


# --------------------------------------------------------------------------
# Table 2 — desired accuracy → h* threshold
# --------------------------------------------------------------------------

@bench("table2_thresholds")
def table2_thresholds():
    from benchmarks.paper_experiments import experiment, ACCURACIES
    rows = []
    for algorithm in ("kmeans", "em"):
        model, *_ = experiment("3D_Road/4", algorithm)
        row = {"algorithm": algorithm}
        for a in ACCURACIES:
            row[f"h_at_{a}"] = f"{model.threshold_for(a):.3e}"
        rows.append(row)
    return rows


# --------------------------------------------------------------------------
# Tables 3 & 4 — achieved accuracy per dataset × desired accuracy
# --------------------------------------------------------------------------

def _achieved(algorithm: str, family="quadratic", balanced=False):
    from benchmarks.paper_experiments import (experiment, ACCURACIES,
                                              DATASETS)
    rows = []
    means = {a: [] for a in ACCURACIES}
    for name in DATASETS:
        model, _, val_runs, k = experiment(name, algorithm, family=family,
                                           balanced=balanced)
        row = {"dataset": name}
        for a in ACCURACIES:
            h_star = model.threshold_for(a)
            achieved = [g.accuracies[g.stop_index(h_star)] for g in val_runs]
            row[f"acc_{a}"] = round(float(np.mean(achieved)), 4)
            row[f"std_{a}"] = round(float(np.std(achieved)), 4)
            means[a].append(float(np.mean(achieved)))
        rows.append(row)
    avg = {"dataset": "Average"}
    for a in ACCURACIES:
        avg[f"acc_{a}"] = round(float(np.mean(means[a])), 4)
        avg[f"std_{a}"] = ""
    rows.append(avg)
    return rows


@bench("table3_achieved_kmeans")
def table3_achieved_kmeans():
    """Paper-faithful: raw cloud, quadratic (Eq. 8)."""
    return _achieved("kmeans")


@bench("table4_achieved_em")
def table4_achieved_em():
    return _achieved("em")


@bench("table3b_kmeans_balanced_auto")
def table3b_kmeans_balanced_auto():
    """Beyond-paper: balanced cloud + model auto-selection (incl. log-quad)."""
    return _achieved("kmeans", family=None, balanced=True)


@bench("table4b_em_balanced_auto")
def table4b_em_balanced_auto():
    return _achieved("em", family=None, balanced=True)


# --------------------------------------------------------------------------
# Fig. 7 — cost-effectiveness (% of full computation time)
# --------------------------------------------------------------------------

@bench("fig7_cost_effectiveness")
def fig7_cost_effectiveness():
    from benchmarks.paper_experiments import (experiment, ACCURACIES,
                                              DATASETS)
    rows = []
    for algorithm in ("kmeans", "em"):
        fracs = {a: [] for a in ACCURACIES}
        for name in DATASETS:
            model, _, val_runs, k = experiment(name, algorithm)
            for a in ACCURACIES:
                h_star = model.threshold_for(a)
                for g in val_runs:
                    # iteration count as the time proxy (§3.3: time ∝ cost;
                    # per-iteration cost is constant for fixed n, k)
                    fracs[a].append((g.stop_index(h_star) + 1) / g.n_iters)
        row = {"algorithm": algorithm}
        for a in ACCURACIES:
            row[f"time_frac_{a}"] = round(float(np.mean(fracs[a])), 4)
        rows.append(row)
    return rows


# --------------------------------------------------------------------------
# §5.4 — the land-use case study (cloud cost)
# --------------------------------------------------------------------------

@bench("case_study_landuse")
def case_study_landuse():
    import jax.numpy as jnp
    import jax
    from repro import core
    from repro.core import landuse_case_study
    from repro.data import spacenet_pixels
    from repro.core.cost_model import US_AREA_KM2, CALIFORNIA_AREA_KM2

    # measure per-image full-convergence time on THIS machine (reduced res,
    # scaled up quadratically to 438×406 ≈ 177,828 px)
    pix = spacenet_pixels(n_images=2, k_true=6, seed=0, shape=(72, 72, 3))
    x = jnp.asarray(pix[0])
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), x, 6)
    core.kmeans_fit_full(x, c0, max_iters=200)[1].block_until_ready()  # warm
    t0 = time.time()
    _, _, _, iters_full = core.kmeans_fit_full(x, c0, max_iters=200)
    t_full_small = time.time() - t0
    scale = (438 * 406) / (72 * 72)
    t_full_image = t_full_small * scale

    res = core.kmeans_fit_traced(x, c0, max_iters=200)
    r, h = core.trace_to_rh(res, 6)
    model = core.fit_longtail([(np.asarray(r), np.asarray(h))],
                              algorithm="kmeans", dataset="spacenet",
                              family="quadratic")
    hh = core.rh_from_objectives(res["objectives"])
    idx = np.where(hh <= model.threshold_for(0.99))[0]
    frac = (int(idx[0]) + 2) / res["n_iters"] if idx.size else 1.0

    rows = []
    for area, label in ((CALIFORNIA_AREA_KM2, "california"),
                        (US_AREA_KM2, "united_states")):
        rep = landuse_case_study(t_full_image, frac, area_km2=area)
        rows.append({
            "region": label, "cost_effectiveness": round(frac, 4),
            "t_full_per_image_s": round(t_full_image, 3),
            "cost_full_usd": round(rep.cost_full_usd, 2),
            "savings_usd": round(rep.savings_usd, 2),
            "train_cost_usd": round(rep.cost_train_usd, 4),
        })
    return rows


# --------------------------------------------------------------------------
# Kernel microbenches (CSV: name,us_per_call,derived)
# --------------------------------------------------------------------------

@bench("kernels")
def kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
    from repro.kernels.gmm_estep.ref import gmm_estep_ref

    rng = np.random.default_rng(0)
    rows = []

    def timeit(fn, *args, n=5):
        # shared methodology (benchmarks.timing): warmup + block_until_ready
        return time_callable(fn, *args, reps=n, warmup=1,
                             reduce="mean") * 1e6

    x = jnp.asarray(rng.normal(0, 5, (100_000, 4)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 5, (8, 4)).astype(np.float32))
    us = timeit(jax.jit(kmeans_assign_ref), x, c)
    flops = 2 * 100_000 * 8 * 4 * 2
    rows.append({"name": "kmeans_assign_jnp_100k_d4_k8",
                 "us_per_call": round(us, 1),
                 "derived": f"{flops / us * 1e-3:.2f}GFLOPs"})

    mu = jnp.asarray(rng.normal(0, 2, (8, 4)).astype(np.float32))
    var = jnp.ones((8, 4), jnp.float32)
    lw = jnp.log(jnp.full((8,), 0.125, jnp.float32))
    us = timeit(jax.jit(gmm_estep_ref), x, mu, var, lw)
    rows.append({"name": "gmm_estep_jnp_100k_d4_k8",
                 "us_per_call": round(us, 1),
                 "derived": f"{3 * flops / us * 1e-3:.2f}GFLOPs"})

    return rows


# --------------------------------------------------------------------------
# Unified engine: streaming chunk sweep + vmapped multi-restart
# --------------------------------------------------------------------------

@bench("engine_scaling")
def engine_scaling():
    """Streaming sweep cost vs chunk count (peak [N,K] intermediate shrinks
    by C) and vmapped multi-restart vs R sequential fits."""
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core.engine import ClusteringEngine, EngineConfig

    rng = np.random.default_rng(0)
    n, d, k = 200_000, 8, 16
    x = jnp.asarray(rng.normal(0, 5, (n, d)).astype(np.float32))
    c0 = core.random_init(jax.random.PRNGKey(0), x, k)
    rows = []

    def timed(fn, *args, reps=3):
        return time_callable(fn, *args, reps=reps, warmup=1, reduce="mean")

    for chunks in (1, 8, 32):
        eng = ClusteringEngine("kmeans", EngineConfig(
            max_iters=10, chunks=chunks, use_h_stop=False,
            stop_when_frozen=True))
        s = timed(lambda: eng.fit(x, c0))
        rows.append({"name": f"kmeans_stream_c{chunks}_n200k_k16",
                     "s_per_fit": round(s, 4),
                     "derived": f"peak_NK={n // max(chunks, 1) * k}"})

    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=10, use_h_stop=False, stop_when_frozen=True))
    key = jax.random.PRNGKey(1)
    r = 4
    inits = eng.init_restarts(key, x, k, r)
    s_batch = timed(lambda: eng.fit_restarts(x, inits).best.labels)
    s_seq = timed(lambda: [eng.fit(x, jax.tree.map(lambda a: a[i], inits))
                           .labels for i in range(r)])
    rows.append({"name": f"kmeans_restarts_vmap_r{r}",
                 "s_per_fit": round(s_batch, 4),
                 "derived": f"{s_seq / max(s_batch, 1e-9):.2f}x_vs_sequential"})
    # s_seq times the whole r-fit loop; report it per fit like the others
    rows.append({"name": f"kmeans_restarts_seq_r{r}",
                 "s_per_fit": round(s_seq / r, 4),
                 "derived": f"baseline_total_{round(s_seq, 4)}s"})
    return rows


@bench("minibatch_scaling")
def minibatch_scaling():
    """Minibatch mode on a 2^18-point blob set: fraction of points touched
    per iteration vs accuracy (paper's r metric — Rand index against the
    full-batch partition).  The acceptance bar: ≥ 99% of full-batch accuracy
    while touching ≤ 25% of the points per iteration.

    ``points_per_iter_frac`` counts *distinct data touched* (B/C — the HBM
    streaming bound); ``sweep_equiv_compute_frac`` counts distance-pass
    compute, which is 2·B/C because the paired Eq. 7 stop evaluates the
    same subsample at the old and the new parameters each iteration."""
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core.engine import ClusteringEngine, EngineConfig

    rng = np.random.default_rng(0)
    n, d, k, chunks = 1 << 18, 4, 8, 64
    centers = rng.normal(0, 6.0, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.5, (n // k, d)) for c in centers])
    x = jnp.asarray(x[rng.permutation(n)].astype(np.float32))  # unbias chunks
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), x, k, chunks=chunks)

    full = ClusteringEngine("kmeans", EngineConfig(
        max_iters=300, chunks=chunks, use_h_stop=False, stop_when_frozen=True))
    t0 = time.time()
    rf = full.fit(x, c0)
    jax.block_until_ready(rf.labels)
    t_full = time.time() - t0
    rows = [{"name": f"full_n{n}_k{k}", "iters": int(rf.n_iters),
             "points_per_iter_frac": 1.0, "sweep_equiv_compute_frac": 1.0,
             "j": round(float(rf.objective), 1),
             "rand_vs_full": 1.0, "fit_s": round(t_full, 3),
             "ge_99pct_at_le_25pct_touch": ""}]
    # 25% touch with mild forgetting (larger late steps), 12.5% with pure
    # 1/t annealing — both stop via the paired h, not max_iters
    for b, decay in ((16, 0.95), (8, 1.0)):
        mb = ClusteringEngine("kmeans", EngineConfig(
            mode="minibatch", chunks=chunks, batch_chunks=b, patience=5,
            max_iters=600, decay=decay, stop_when_frozen=True))
        t0 = time.time()
        rm = mb.fit(x, c0, h_star=1e-5)
        jax.block_until_ready(rm.labels)
        t_mb = time.time() - t0
        r = float(core.rand_index(rm.labels, rf.labels, k, k))
        frac = b / chunks
        rows.append({
            "name": f"minibatch_b{b}of{chunks}_n{n}_k{k}",
            "iters": int(rm.n_iters),
            "points_per_iter_frac": round(frac, 4),
            "sweep_equiv_compute_frac": round(2 * frac, 4),
            "j": round(float(rm.objective), 1),
            "rand_vs_full": round(r, 4), "fit_s": round(t_mb, 3),
            "ge_99pct_at_le_25pct_touch": bool(r >= 0.99 and frac <= 0.25),
        })
    return rows


@bench("minibatch_shard")
def minibatch_shard():
    """Sharded minibatch clustering across device counts (submeshes of the
    host platform): rand index vs the full-batch partition, sweep-equivalent
    compute fraction, and wall time per device count.

    Persists ``BENCH_minibatch_shard.json`` at the repo root — the
    perf-trajectory artifact the repo's history tracks (the CSVs under
    ``benchmarks/out/`` are per-run scratch).  Wall times on the forced
    host-platform device counts measure the collective + partitioning
    overhead of the composed path, not accelerator speedups.
    """
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core.engine import ClusteringEngine, EngineConfig

    rng = np.random.default_rng(0)
    n, d, k, chunks, b = 1 << 18, 4, 8, 64, 16   # = minibatch_scaling's set
    centers = rng.normal(0, 6.0, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.5, (n // k, d)) for c in centers])
    x = jnp.asarray(x[rng.permutation(n)].astype(np.float32))
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), x, k,
                                    chunks=chunks)

    full = ClusteringEngine("kmeans", EngineConfig(
        max_iters=300, chunks=chunks, use_h_stop=False,
        stop_when_frozen=True))
    rf = full.fit(x, c0)
    jax.block_until_ready(rf.labels)

    # decay 0.95 = the minibatch_scaling 25%-touch recipe (mild forgetting
    # keeps late steps large enough to land ≥99% of full-batch accuracy)
    eng = ClusteringEngine("kmeans", EngineConfig(
        mode="minibatch", chunks=chunks, batch_chunks=b, patience=5,
        max_iters=600, decay=0.95, stop_when_frozen=True))
    devs = jax.devices()
    counts = [m for m in (1, 2, 4, 8) if m <= len(devs)]
    skipped = [m for m in (1, 2, 4, 8) if m > len(devs)]
    rows = []
    for m in counts:
        mesh = jax.make_mesh((m,), ("data",), devices=devs[:m],
                             axis_types=(jax.sharding.AxisType.Auto,))
        res = eng.fit_sharded(x, c0, mesh, h_star=1e-5)   # compile + warm
        jax.block_until_ready(res.labels)
        wall = time_callable(
            lambda: eng.fit_sharded(x, c0, mesh, h_star=1e-5).labels,
            reps=1, warmup=0)
        r = float(core.rand_index(res.labels, rf.labels, k, k))
        rows.append({
            "name": f"minibatch_shard_d{m}", "devices": m,
            "iters": int(res.n_iters),
            "rand_vs_full": round(r, 4),
            "sweep_equiv_compute_frac": round(2 * b / chunks, 4),
            "wall_s_fit": round(wall, 3),
        })

    if skipped:
        # never silently overwrite the tracked multi-device trajectory with
        # a partial sweep — say what's missing and keep the old artifact
        print(f"# minibatch_shard: only {len(devs)} device(s) visible, "
              f"skipped counts {skipped}; NOT writing "
              "BENCH_minibatch_shard.json (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 for the full sweep)")
        return rows
    payload = {
        "benchmark": "minibatch_shard",
        "n": n, "d": d, "k": k, "chunks": chunks, "batch_chunks": b,
        "decay": 0.95,
        "note": "device counts are XLA host-platform emulation "
                "(--xla_force_host_platform_device_count); wall times "
                "measure collective/partitioning overhead on CPU, not "
                "accelerator scaling",
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_minibatch_shard.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


@bench("sharded_overlap")
def sharded_overlap():
    """ISSUE 7: compressed, latency-hidden sharded sweeps — int8-EF ring
    stats reduction vs fp32 psum, and the latency-hiding toggle (XLA flags
    + double-buffered chunk prefetch) vs the synchronous baseline.

    XLA reads ``XLA_FLAGS`` once per process, so each flag leg runs in a
    fresh worker subprocess (``benchmarks.sharded_overlap_worker``) whose
    environment ``repro.launch.mesh.overlap_env`` builds; the overlap leg
    also turns on ``EngineConfig(prefetch=True)`` (bit-identical math).

    Persists ``BENCH_sharded_overlap.json`` at the repo root (tracked
    artifact).  Tracked claims (the CI ``longtail-artifacts`` gate):

      · parity — int8-EF stop iterations match the fp32 psum stop to
        ≤ 1 iteration at every device count, in both legs (the centred
        compression basis + error feedback keep the Eq. 7 h trajectory on
        the fp32 one);
      · ≥ 3× collective-byte reduction vs fp32 at every multi-device
        count (analytic ``stats_wire_bytes``; the ring factor cancels);
      · overlap wall-clock per sweep no worse than the synchronous
        baseline, summed over the sweep grid (1.15× tolerance — CPU
        host-emulation timing noise, not a perf regression bar).
    """
    import subprocess
    import sys
    import tempfile
    from repro.launch.mesh import overlap_env

    # this process stays off JAX: on an accelerator the device belongs to
    # the process that touches it first, and every worker needs it
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    legs = {}
    for leg, enable in (("sync", False), ("overlap", True)):
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
            out = tf.name
        cmd = [sys.executable, "-m", "benchmarks.sharded_overlap_worker",
               "--out", out, "--leg", leg] + (["--prefetch"] if enable
                                              else [])
        # the device counts are host-platform emulation: pin the CPU
        subprocess.run(cmd, check=True, cwd=root,
                       env=overlap_env({**os.environ, "JAX_PLATFORMS": "cpu"},
                                       enable=enable))
        with open(out) as f:
            legs[leg] = json.load(f)
        os.unlink(out)
        if legs[leg]["visible_devices"] < 8:
            print("# sharded_overlap: needs 8 devices (set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8); skipping — "
                  "NOT writing BENCH_sharded_overlap.json")
            return []

    rows = [r for leg in ("sync", "overlap") for r in legs[leg]["rows"]]
    cell = {(r["leg"], r["devices"], r["compression"]): r for r in rows}
    counts = sorted({r["devices"] for r in rows})
    parity = {f"{leg}_d{m}": abs(cell[(leg, m, "int8_ef")]["iters"]
                                 - cell[(leg, m, "none")]["iters"])
              for leg in ("sync", "overlap") for m in counts}
    byte_ratio = {f"d{m}": round(
        cell[("sync", m, "none")]["wire_bytes_per_reduction"]
        / cell[("sync", m, "int8_ef")]["wire_bytes_per_reduction"], 3)
        for m in counts if m > 1}
    wall = {leg: round(sum(r["wall_s"] for r in legs[leg]["rows"]), 3)
            for leg in ("sync", "overlap")}
    payload = {
        "benchmark": "sharded_overlap",
        **{k: legs["sync"][k] for k in ("n", "d", "k", "chunks",
                                        "batch_chunks", "h_star",
                                        "timed_iters")},
        "overlap_leg": {"xla_flags": "latency_hiding_xla_flags",
                        "prefetch": True},
        "parity_iters_delta": parity,
        "wire_byte_ratio_fp32_over_int8": byte_ratio,
        "timed_wall_s_total": wall,
        "claims": {
            "int8_parity_delta_le_1": bool(max(parity.values()) <= 1),
            # int8 payload + one f32 scale per cluster row + exact
            # counts: ~(d+1)·4 / (d+8) of the fp32 bytes, 2.2x at d=8
            "wire_byte_reduction_ge_2x":
                bool(min(byte_ratio.values()) >= 2.0),
            "overlap_wall_no_worse_1p15x":
                bool(wall["overlap"] <= wall["sync"] * 1.15),
        },
        "note": "device counts are XLA host-platform emulation on CPU; "
                "wall columns measure collective/partitioning overhead, "
                "not accelerator scaling.  Parity and byte-ratio columns "
                "are host-independent (the tracked claims); the wall "
                "claim carries a 1.15x noise tolerance",
        "rows": rows,
    }
    path = os.path.join(root, "BENCH_sharded_overlap.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


@bench("kernel_backends")
def kernel_backends():
    """ISSUE 4: the kernel dispatch layer across engine modes and device
    counts — full vs minibatch sweeps, dispatched kernel (interpret on this
    host; the same code compiles on TPU/GPU) vs the XLA reference backend,
    single-device and sharded.

    Persists ``BENCH_kernel_backends.json`` at the repo root (tracked
    perf-trajectory artifact, like ``BENCH_minibatch_shard.json``).  Wall
    times on a CPU host measure the interpreter + partitioning overhead of
    the composed path, not accelerator speedups — the artifact's tracked
    claims are the parity columns (identical stop iterations and matching
    objectives across backends), which hold on any host.
    """
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core.engine import ClusteringEngine, EngineConfig

    rng = np.random.default_rng(0)
    n, d, k, chunks, b = 1 << 15, 4, 8, 16, 4     # 25% touch in minibatch
    centers = rng.normal(0, 6.0, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.5, (n // k, d)) for c in centers])
    x = jnp.asarray(x[rng.permutation(n)].astype(np.float32))
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), x, k,
                                    chunks=chunks)
    devs = jax.devices()
    counts = [m for m in (1, 2, 4, 8) if m <= len(devs)]

    def cfg(mode, backend):
        kw = dict(max_iters=300, chunks=chunks, stop_when_frozen=True,
                  use_kernel=True, kernel_backend=backend)
        if mode == "minibatch":
            kw.update(mode="minibatch", batch_chunks=b, patience=5,
                      max_iters=600, decay=0.95)
            return EngineConfig(**kw)
        kw.update(use_h_stop=False)
        return EngineConfig(**kw)

    def fit(engine, mesh=None):
        # 1e-4 trips the paired minibatch stop well before max_iters (~130
        # iterations here), so the parity column compares real early-stop
        # decisions, not a trivial run-to-max; full mode stops on frozen
        # centroids (use_h_stop=False) and ignores the threshold
        run = (lambda: engine.fit(x, c0, h_star=1e-4)) if mesh is None else \
            (lambda: engine.fit_sharded(x, c0, mesh, h_star=1e-4))
        res = run()                                   # compile + warm
        jax.block_until_ready(res.labels)
        return res, time_callable(lambda: run().labels, reps=1, warmup=0)

    rows = []
    baselines = {}
    host_backend = "interpret" if jax.default_backend() == "cpu" \
        else jax.default_backend()
    for mode in ("full", "minibatch"):
        for backend in (host_backend, "xla"):
            eng = ClusteringEngine("kmeans", cfg(mode, backend))
            for m in counts:
                mesh = None if m == 1 else jax.make_mesh(
                    (m,), ("data",), devices=devs[:m],
                    axis_types=(jax.sharding.AxisType.Auto,))
                res, wall = fit(eng, mesh)
                key = (mode, m)
                base = baselines.setdefault(key, res)
                rows.append({
                    "name": f"{mode}_{backend}_d{m}",
                    "mode": mode, "backend": backend, "devices": m,
                    "iters": int(res.n_iters),
                    "j": round(float(res.objective), 1),
                    "stop_matches_first_backend":
                        bool(int(res.n_iters) == int(base.n_iters)),
                    "wall_s_fit": round(wall, 3),
                })

    skipped = [m for m in (1, 2, 4, 8) if m > len(devs)]
    if skipped:
        print(f"# kernel_backends: only {len(devs)} device(s) visible, "
              f"skipped counts {skipped}; NOT writing "
              "BENCH_kernel_backends.json (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 for the full "
              "sweep)")
        return rows
    payload = {
        "benchmark": "kernel_backends",
        "n": n, "d": d, "k": k, "chunks": chunks, "batch_chunks": b,
        "host_pallas_backend": host_backend,
        "note": "device counts are XLA host-platform emulation; wall "
                "times on CPU measure interpreter/partitioning overhead, "
                "not accelerator scaling — the tracked claim is backend "
                "parity (stop_matches_first_backend) per mode × device "
                "count",
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_kernel_backends.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


@bench("longtail_matched")
def longtail_matched():
    """ISSUE 5: mode-matched vs transferred h(r) fits on the skin config.

    Both models are fitted on the SAME training groups through the
    engine-trace pipeline (``repro.core.longtail_train``) — one harvested
    under the minibatch production config (matched), one under full-batch
    sweeps (the legacy transfer regime) — then both serve the SAME
    minibatch production runs on held-out groups at r* ∈ {0.95, 0.99}.
    Achieved accuracy = Rand index vs the group's full-convergence
    partition from the same init (the paper's §5.3 validation).

    Persists ``BENCH_longtail_matched.json`` at the repo root (tracked
    artifact).  Tracked claims: the matched fit's achieved-accuracy
    spread (max − min across held-out groups) at r* = 0.99 is ≤ the
    transferred fit's, and its mean achieved accuracy at r* = 0.95 clears
    0.95 (the CI ``longtail-artifacts`` gate).
    """
    import warnings

    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core.engine import ClusteringEngine, EngineConfig
    from repro.core.longtail_train import TrainingPlan, fit_for_config
    from repro.data import load

    k, chunks, b, decay = 2, 8, 2, 0.95
    data = load("skin", n=60_000, seed=0)
    groups = core.random_groups(data, 6_000, max_groups=8)
    train_g, prod_g = groups[:4], groups[4:]

    # decay 0.95 = the documented 25%-touch production recipe
    # (minibatch_scaling); both fits use the balanced r-binned cloud so the
    # transition region the thresholds live in is equally weighted — the
    # raw skin cloud puts almost all mass at r ≈ 1 and under-constrains
    # both regressions.
    prod_cfg = EngineConfig(mode="minibatch", chunks=chunks, batch_chunks=b,
                            decay=decay, patience=5, max_iters=400,
                            stop_when_frozen=True)
    models = {
        "matched": fit_for_config(TrainingPlan(
            algorithm="kmeans", k=k, config=prod_cfg, family="quadratic",
            balanced=True), train_g),
        "transferred": fit_for_config(TrainingPlan(
            algorithm="kmeans", k=k, config=EngineConfig(max_iters=400),
            family="quadratic", balanced=True), train_g),
    }

    # full-convergence reference partition per held-out group (same init)
    full = ClusteringEngine("kmeans", EngineConfig(
        max_iters=1200, chunks=chunks, use_h_stop=False,
        stop_when_frozen=True))
    inits, refs = [], []
    for gi, g in enumerate(prod_g):
        x = jnp.asarray(g)
        c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(100 + gi), x, k,
                                        chunks=chunks)
        inits.append(c0)
        refs.append(full.fit(x, c0).labels)

    prod_kw = dict(mode="minibatch", chunks=chunks, batch_chunks=b,
                   decay=decay, patience=5, max_iters=400,
                   stop_when_frozen=True)
    rows = []
    spreads = {}
    for r_star in (0.95, 0.99):
        for name, model in models.items():
            accs, iters = [], []
            for gi, g in enumerate(prod_g):
                with warnings.catch_warnings():
                    # the transferred model mismatches by design
                    warnings.simplefilter("ignore")
                    cfg = EngineConfig.from_longtail(
                        model, r_star, seed=100 + gi, **prod_kw)
                res = ClusteringEngine("kmeans", cfg).fit(
                    jnp.asarray(g), inits[gi])
                accs.append(float(core.rand_index(res.labels, refs[gi],
                                                  k, k)))
                iters.append(int(res.n_iters))
            spread = max(accs) - min(accs)
            spreads[(r_star, name)] = spread
            rows.append({
                "name": f"{name}_rstar{r_star}", "fit": name,
                "r_star": r_star,
                "h_star": f"{model.threshold_for(r_star):.3e}",
                "acc_mean": round(float(np.mean(accs)), 4),
                "acc_min": round(min(accs), 4),
                "acc_max": round(max(accs), 4),
                "spread": round(spread, 4),
                "mean_iters": round(float(np.mean(iters)), 1),
                "per_group_acc": "|".join(f"{a:.4f}" for a in accs),
            })

    payload = {
        "benchmark": "longtail_matched",
        "dataset": "skin", "k": k, "n": 60_000, "group_size": 6_000,
        "train_groups": 4, "prod_groups": len(prod_g),
        "production_config": prod_cfg.matched_fingerprint(),
        "matched_provenance": models["matched"].engine_config,
        "claims": {
            "matched_spread_le_transferred_at_0.99":
                bool(spreads[(0.99, "matched")]
                     <= spreads[(0.99, "transferred")]),
            "matched_acc_mean_at_0.95_ge_0.95":
                bool(next(r for r in rows
                          if r["name"] == "matched_rstar0.95")["acc_mean"]
                     >= 0.95),
        },
        "note": "achieved accuracy = Rand vs the full-convergence "
                "partition of the same held-out group and init; spread = "
                "max - min across held-out groups; both fits share "
                "training groups and differ only in harvest regime",
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_longtail_matched.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


# --------------------------------------------------------------------------
# Cost-aware provisioning planner: predicted vs actual (ISSUE 10 tentpole)
# --------------------------------------------------------------------------

@bench("plan")
def plan_bench():
    """ISSUE 10: planner predicted-vs-actual on the small skin config.

    Fits per-mode h(r) + iteration models from harvested traces, runs the
    planner at r* = 0.99 over the default price table and the committed
    throughput benches, then executes the chosen plan through the real fit
    drivers on a held-out group (``repro.launch.plan.validate_plan`` —
    warm walls, so Eq. 10 compares steady-state compute, plus the
    StragglerMonitor step-loop report).

    Persists ``BENCH_plan.json`` at the repo root (tracked artifact).
    Tracked claims (CI ``longtail-artifacts`` gate):
      · ``iters_within_tolerance`` — actual stop iterations within
        ±max(50%, 5) of predicted (host-independent, hard-gated);
      · ``actual_cost_below_full_convergence`` — the validated run's
        Eq. 6 cost at r* = 0.99 is strictly below the full-convergence
        reference on the same host (the paper's §5.4 claim, executable;
        warm same-host walls so host noise largely cancels);
      · ``predicted_cost_fraction_below_1`` — the planner already
        predicts that saving before running anything;
      · ``straggler_report_present`` — the monitored step-loop evidence
        landed (ISSUE 10 satellite: StragglerMonitor wired through
        --validate).
    Wall-seconds agreement is recorded but advisory: the throughput
    points were measured on a different host class than CI.
    """
    import jax.numpy as jnp
    from repro import core
    from repro.core.cost_model import PriceTable
    from repro.core.planner import PlanSpec, ThroughputModel
    from repro.core.planner import plan as run_plan
    from repro.data import load
    from repro.launch.plan import TOLERANCE, fit_models, validate_plan

    k, chunks, b, decay, max_iters, r_star = 2, 16, 4, 0.95, 200, 0.99
    data = load("skin", n=24_000, seed=0)
    groups = core.random_groups(data, 6_000, max_groups=3)
    train_g, val = groups[:2], jnp.asarray(groups[2], jnp.float32)

    models, ims = fit_models(train_g, algorithm="kmeans", k=k,
                             chunks=chunks, batch_chunks=b, decay=decay,
                             max_iters=max_iters, seed=0)
    prices = PriceTable.default()
    throughput = ThroughputModel.from_bench_dir()
    spec = PlanSpec(n=24_000, d=int(data.shape[1]), k=k, target_r=r_star,
                    deadline_s=3600.0, prices=prices, max_iters=max_iters,
                    chunks=chunks, batch_chunks=b, decay=decay)
    report = run_plan(spec, models=models, iteration_models=ims,
                      throughput=throughput)
    record = validate_plan(report, val, algorithm="kmeans", k=k,
                           models=models, throughput=throughput,
                           prices=prices, target_r=r_star,
                           max_iters=max_iters)

    chosen = report.chosen
    claims = {
        "iters_within_tolerance": bool(record["iters_within_tolerance"]),
        "actual_cost_below_full_convergence":
            bool(record["cost_fraction_actual"] < 1.0),
        "predicted_cost_fraction_below_1":
            bool(report.cost_fraction < 1.0),
        "straggler_report_present":
            bool(record["straggler"].get("steps", 0) > 0),
    }
    rows = [{
        "name": "plan_rstar0.99", "chosen": chosen.describe(),
        "predicted_iters": record["predicted"]["iters"],
        "actual_iters": record["actual"]["iters"],
        "predicted_cost_usd": f"{record['predicted']['cost_usd']:.3e}",
        "actual_cost_usd": f"{record['actual']['cost_usd']:.3e}",
        "cost_fraction_predicted": round(report.cost_fraction, 4),
        "cost_fraction_actual": round(record["cost_fraction_actual"], 4),
        "accuracy": round(record["actual"]["accuracy"], 4),
        "straggler_flagged": record["straggler"].get("flagged", 0),
    }]
    payload = {
        "benchmark": "plan",
        "dataset": "skin", "k": k, "n": 24_000, "group_size": 6_000,
        "train_groups": 2,
        "target_r": r_star, "deadline_s": 3600.0,
        "engine": {"chunks": chunks, "batch_chunks": b, "decay": decay,
                   "max_iters": max_iters},
        "price_table": [p.name for p in prices.prices],
        "h_star_by_mode": report.h_star_by_mode,
        "chosen": {
            "candidate": chosen.describe(),
            "engine_kwargs": chosen.engine_kwargs(),
            "predicted_iters": chosen.predicted_iters,
            "predicted_wall_s": chosen.predicted_wall_s,
            "predicted_cost_usd": chosen.predicted_cost_usd,
        },
        "cost_fraction_predicted": report.cost_fraction,
        "full_reference": report.full_reference,
        "tolerance": TOLERANCE,
        "validation": record,
        "claims": claims,
        "note": "validation walls are warm (second call of an identical "
                "jit program) so Eq. 10 compares steady-state compute; "
                "wall-seconds agreement with the cross-host throughput "
                "points is advisory, iteration and same-host cost-"
                "fraction claims are the CI gate",
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_plan.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


# --------------------------------------------------------------------------
# Clustering-as-a-service: the assignment server (ISSUE 6 tentpole)
# --------------------------------------------------------------------------

@bench("serve_cluster")
def serve_cluster():
    """Continuous-batching assignment server: per-model latency,
    throughput, QPS and the recompile-count claim.

    Two artifacts with real harvest provenance (minibatch k-means +
    full-batch EM, ``launch.serve_cluster.demo_artifacts``) serve a mixed
    stream of assignment batches in several drain waves, plus incremental
    fit jobs.  Persists ``BENCH_serve_cluster.json`` at the repo root.
    Tracked claims (the CI ``longtail-artifacts`` gate):

      · one compiled program per (model, bucket) — the assign jit cache
        never exceeds the bucket count, no matter how many distinct batch
        sizes arrive;
      · served labels match ``ClusteringEngine`` batch assignment
        bit-for-bit (padding never leaks into results).
    """
    import jax
    import numpy as np
    from repro.core.engine import ClusteringEngine
    from repro.launch.serve_cluster import demo_artifacts
    from repro.serving import AssignRequest, ClusterServer, FitRequest, \
        ModelRegistry

    buckets = (256, 1024, 4096)
    registry = ModelRegistry(devices=len(jax.devices()), fit_steps=20)
    artifacts = demo_artifacts(seed=0)
    keys = {a.name: registry.register(a) for a in artifacts}
    server = ClusterServer(registry, buckets=buckets)
    for key in keys.values():
        server.warmup(key)              # steady-state latencies only

    rng = np.random.default_rng(0)
    d = artifacts[0].d
    names = list(keys)
    rid = 0
    labels_match = True
    parity_checks = 0
    for wave in range(6):
        wave_reqs = []
        for _ in range(12):
            name = names[rng.integers(0, len(names))]
            n = int(rng.integers(20, 3000))
            wave_reqs.append(AssignRequest(
                x=rng.normal(0, 4, (n, d)).astype(np.float32),
                model_key=keys[name], rid=rid))
            rid += 1
        if wave % 3 == 2:               # fits are rare — the paper's premise
            name = names[rng.integers(0, len(names))]
            wave_reqs.append(FitRequest(
                x=rng.normal(0, 4, (512, d)).astype(np.float32),
                model_key=keys[name], rid=rid))
            rid += 1
        for r in wave_reqs:
            server.submit(r)
        out = server.drain()
        # spot-check label parity against the engine's batch assignment
        for r in wave_reqs[:2]:
            if not isinstance(r, AssignRequest):
                continue
            entry = server.registry[r.model_key]
            eng = ClusteringEngine(entry.artifact.algorithm, entry.config)
            _, ref, _ = eng.step(r.x, entry.params)
            labels_match &= bool(np.array_equal(out[r.rid], np.asarray(ref)))
            parity_checks += 1

    compiled = server.compiled_programs()
    one_per_bucket = all(c["assign"] <= len(buckets)
                         for c in compiled.values())
    rows = []
    for a in artifacts:
        key = keys[a.name]
        m = server.metrics.summary()[key]
        fit_m = server.metrics.summary().get(f"{key}#fit")
        rows.append({
            "model": a.name, "algorithm": a.algorithm,
            "requests": m["requests"], "batches": m["batches"],
            "points": m["points"],
            "p50_latency_ms": round(m["p50_latency_ms"], 3),
            "p99_latency_ms": round(m["p99_latency_ms"], 3),
            "throughput_points_per_s":
                round(m["throughput_points_per_s"], 1),
            "qps": round(m["qps"], 2),
            "fit_jobs": fit_m["requests"] if fit_m else 0,
            "compiled_assign": compiled[key]["assign"],
            "compiled_fit": compiled[key]["fit"],
        })

    payload = {
        "benchmark": "serve_cluster",
        "buckets": list(buckets),
        "devices": len(jax.devices()),
        "parity_checks": parity_checks,
        "claims": {
            "one_program_per_model_bucket": bool(one_per_bucket),
            "served_labels_match_engine": bool(labels_match),
        },
        "note": "latencies are steady-state (buckets pre-compiled via "
                "warmup); one compiled assign program per (model, bucket) "
                "regardless of arriving batch sizes; fit jobs advance the "
                "registered params under the artifact's own engine regime",
        "models": {a.name: {"key": keys[a.name],
                            "provenance": a.model.engine_config}
                   for a in artifacts},
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_serve_cluster.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(_REGISTRY)
    t0 = time.time()
    for name in names:
        t1 = time.time()
        rows = _REGISTRY[name]()
        _emit(name, rows)
        print(f"# {name} took {time.time() - t1:.1f}s")
    print(f"\n# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
