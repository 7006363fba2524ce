#!/usr/bin/env python3
"""Bring-up run of the clustering system on a TPU, through its entry points.

    python chip_smoke.py             # one chip: kernels, pipeline, serve
    python chip_smoke.py --chips 4   # four chips: the sharded cloud fit

One process drives the chip: every phase calls the entry points' functions
in-process (``repro.launch.cluster``, the serving registry and server), at
the paper's data sizes.

  · kernels  — one sweep of ``kmeans_assign`` and ``gmm_estep`` at poker's
    width (1,025,010 × 11, K = 10) through the dispatched ops, whose
    backend must resolve to the compiled ``tpu`` kernel, against the
    ``xla`` reference on the same data and parameters; then a restart
    fleet of R = 4 on the kernels' restart grid.
  · pipeline — ``repro.launch.cluster``'s pipeline: h(r) fitted on sampled
    groups, then the early-stopped production run and the full-convergence
    reference on the whole data set.  k-means and EM on poker, on the
    default XLA path and on the kernel path; then skin with minibatch
    sweeps, 4 restarts and the kernel.  Each line says what the early
    stop did: "met r*" or "missed r*" (Rand index of the early stop
    against the full run), or "not exercised" where the fitted h* sits on
    ``threshold_for``'s floor and the run waits for the fit to stop
    moving.  A summary line lists the runs under each; at least one run's
    early stop must meet r*.
  · serve    — the k-means kernel-path fit saved as a ``ClusterArtifact``,
    registered and served by ``ClusterServer``; the served labels must
    equal the engine's assignment at the same parameters.
  · sharded  — (``--chips 4`` only) ``run_production(shard=True)`` on a
    4-device data mesh, k-means and EM, full and minibatch, 4 restarts,
    fp32 and int8 stats reduction, each against the same fit on device 0:
    stop iteration within ±1 and best objective within the restart-fleet
    tolerances, at a fixed h* per algorithm.  The same fits also run at
    the h* the pipeline fits for that regime (r* = 0.99); those lines
    report the comparison (``gated: false``) and do not decide the run.

Every check prints one JSON line naming the device kind, iterations,
accuracy, and compile seconds apart from run seconds (from JAX's compile
events).  The last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, with ``REPRO_FORCE_KERNEL_BACKEND`` set, or without this
repository's ``src/`` beside the script, it exits 2 naming the cause and
prints no result; a failed check raises and exits 1.

JAX's persistent compilation cache is on (``repro.launch.compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

POKER_K = 10          # paper Table 1: poker hands, 10 classes
SKIN_K = 2            # skin / non-skin
RESTARTS = 4
GROUP_SIZE = 20_000   # training-group size (paper §5.2: >= 10,000)
TRAIN_GROUPS = 4
MAX_ITERS = 300
DESIRED_ACCURACY = 0.99


def _refuse(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(2)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


@contextlib.contextmanager
def timed():
    """The block's wall seconds split into ``compile_s``, what JAX spent
    lowering and compiling inside it (``repro.spans``' totals), and
    ``run_s``, the rest, with the ``lowerings`` (programs lowered) inside
    it.  Tracing is left on the run side."""
    from repro import spans

    def summed():
        t = spans.totals().values()
        return (sum(v["compile_s"] for v in t), sum(v["lowerings"] for v in t))

    rec: dict = {}
    (c0, l0), t0 = summed(), time.perf_counter()
    with spans.span("smoke.check"):
        yield rec
    c1, l1 = summed()
    rec.update(compile_s=c1 - c0, lowerings=l1 - l0)
    rec["run_s"] = time.perf_counter() - t0 - rec["compile_s"]


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _label_ties(x, diff, la, lb, score):
    """True where rows ``diff`` with labels la ≠ lb are ties of ``score``
    (float64, per row and label) within f32 rounding of its expansion."""
    sa, scale_a = score(x[diff], la[diff])
    sb, scale_b = score(x[diff], lb[diff])
    return np.abs(sa - sb) <= 1e-5 * np.maximum(scale_a, scale_b)


def compare_kmeans(x, c, got, ref, what):
    """Kernel vs reference stats of one k-means sweep: labels agree except
    on distance ties; sums, counts and J agree to f32 accumulation over
    ~1M rows (rtol 1e-4), with the tied rows' moves allowed for."""
    la, sa, na, ja = (np.asarray(a) for a in got)
    lb, sb, nb, jb = (np.asarray(a) for a in ref)
    cd = c.astype(np.float64)

    def dist(xr, lab):
        xr = xr.astype(np.float64)
        return (np.sum((xr - cd[lab]) ** 2, axis=1),
                np.sum(xr ** 2, axis=1) + np.sum(cd[lab] ** 2, axis=1))

    diff = np.nonzero(la != lb)[0]
    ties = _label_ties(x, diff, la, lb, dist)
    check(ties.all(), f"{what}: {int((~ties).sum())} labels differ off a "
                      "distance tie")
    m = len(diff)
    check(np.all(np.abs(na - nb) <= m), f"{what}: counts differ")
    check(np.allclose(sa, sb, rtol=1e-4,
                      atol=m * float(np.abs(x).max()) + 1e-3),
          f"{what}: sums differ")
    check(np.isclose(ja, jb, rtol=1e-4), f"{what}: J {ja} vs {jb}")
    return m


def compare_gmm(x, params, got, ref, what):
    """Kernel vs reference E-step: labels agree except on log-density
    ties; loglik and the responsibility moments to rtol 1e-4."""
    mu, var, log_w = (np.asarray(p, np.float64) for p in params)
    la, lla, rsa, rxa, rx2a = (np.asarray(a) for a in got)
    lb, llb, rsb, rxb, rx2b = (np.asarray(a) for a in ref)

    def logp(xr, lab):
        xr = xr.astype(np.float64)
        q = np.sum((xr - mu[lab]) ** 2 / var[lab], axis=1)
        lp = log_w[lab] - 0.5 * (q + np.sum(np.log(var[lab]), axis=1))
        return lp, (np.sum(xr ** 2 / var[lab], axis=1)
                    + np.sum(mu[lab] ** 2 / var[lab], axis=1))

    diff = np.nonzero(la != lb)[0]
    ties = _label_ties(x, diff, la, lb, logp)
    check(ties.all(), f"{what}: {int((~ties).sum())} labels differ off a "
                      "log-density tie")
    n = x.shape[0]
    xmax = float(np.abs(x).max())
    check(np.isclose(lla, llb, rtol=1e-4), f"{what}: loglik {lla} vs {llb}")
    check(np.allclose(rsa, rsb, rtol=1e-4, atol=1e-6 * n),
          f"{what}: r_sum differs")
    check(np.allclose(rxa, rxb, rtol=1e-4, atol=1e-6 * n * xmax),
          f"{what}: r_x differs")
    check(np.allclose(rx2a, rx2b, rtol=1e-4, atol=1e-6 * n * xmax ** 2),
          f"{what}: r_x2 differs")
    return len(diff)


def kernels_phase(x_np, k, restarts, *, backend, kind, seed=0):
    import jax
    import jax.numpy as jnp
    from repro.core import EngineConfig
    from repro.kernels import dispatch
    from repro.kernels.gmm_estep.ops import gmm_estep
    from repro.kernels.kmeans_assign.ops import kmeans_assign

    resolved = dispatch.resolve_backend("auto")
    check(resolved == backend, f"auto resolved to {resolved!r}, not "
                               f"{backend!r}")
    check(EngineConfig(use_kernel=True).kernel_backend == backend,
          "EngineConfig(use_kernel=True) did not resolve to the chip")
    rng = np.random.default_rng(seed)
    n, d = x_np.shape
    x = jnp.asarray(x_np)
    # data rows jittered off poker's integer grid, so exact ties are rare
    cs = np.stack([x_np[rng.choice(n, k, replace=False)]
                   + rng.normal(0.0, 0.25, (k, d)) for _ in range(restarts)])
    cs = cs.astype(np.float32)
    var = (np.var(x_np, axis=0) * rng.uniform(0.5, 2.0, (restarts, k, d))
           + 0.1).astype(np.float32)
    log_w = np.log(rng.dirichlet(np.full(k, 5.0), restarts)).astype(
        np.float32)

    for r in (1, restarts):
        c = cs[0] if r == 1 else cs
        gp = (cs[0], var[0], log_w[0]) if r == 1 else (cs, var, log_w)

        def km(b):
            if r == 1:
                return kmeans_assign(x, jnp.asarray(c), backend=b)
            return jax.vmap(lambda cc: kmeans_assign(x, cc, backend=b))(
                jnp.asarray(c))

        def gm(b):
            if r == 1:
                return gmm_estep(x, *map(jnp.asarray, gp), backend=b)
            return jax.vmap(lambda m, v, w: gmm_estep(x, m, v, w, backend=b))(
                *map(jnp.asarray, gp))

        for op, fn, compare in (("kmeans_assign", km, compare_kmeans),
                                ("gmm_estep", gm, compare_gmm)):
            with timed() as t:
                got = jax.block_until_ready(fn(None))     # auto → chip
            ref = jax.block_until_ready(fn("xla"))
            ties = 0
            for i in range(r):
                pick = (lambda a: a) if r == 1 else (lambda a, i=i: a[i])
                params = (c if r == 1 else c[i]) if op == "kmeans_assign" \
                    else tuple(pick(p) for p in gp)
                ties += compare(x_np, params, tuple(map(pick, got)),
                                tuple(map(pick, ref)), f"{op} R={r}[{i}]")
            emit(phase="kernels", op=op, backend=backend, restarts=r,
                 n=n, d=d, k=k, device_kind=kind, iterations=1,
                 label_agreement=1.0 - ties / (n * r), tied_labels=ties,
                 parity="pass", **t)


# --------------------------------------------------------------------------
# pipeline
# --------------------------------------------------------------------------

def pipeline_phase(data, argv, *, kind, group_size=GROUP_SIZE,
                   train_groups=TRAIN_GROUPS):
    """``repro.launch.cluster``'s pipeline with ``data`` as the one
    production group and ``train_groups`` groups sampled from it:
    (report, run name, what the early stop did)."""
    from repro import core
    from repro.core.regression import H_STAR_FLOOR
    from repro.launch import cluster

    args = cluster.parse_args(argv)
    train = core.random_groups(data, group_size, max_groups=train_groups,
                               seed=1)
    with timed() as t:
        res = cluster.pipeline(args, train, [data])
    h_star = float(res["h_star"])
    it_es, it_fu = res["iters_earlystop"], res["iters_full"]
    rand = res["accuracies"][0]
    check(np.isfinite(h_star) and h_star > 0, f"h* = {h_star}")
    check(1 <= it_es <= args.max_iters, f"early stop at {it_es}")
    check(1 <= it_fu, f"full run stopped at {it_fu}")
    check(0.0 <= rand <= 1.0, f"Rand index {rand}")
    meets = rand >= args.desired_accuracy
    if h_star <= H_STAR_FLOOR:
        early_stop = "not exercised (h* on the floor)"
    else:
        early_stop = "met r*" if meets else "missed r*"
    emit(phase="pipeline", dataset=args.dataset, n=int(data.shape[0]),
         algorithm=args.algorithm, mode=args.mode, restarts=args.restarts,
         path="kernel" if args.use_kernel else "xla", device_kind=kind,
         h_star=h_star, iters_earlystop=it_es, iters_full=it_fu,
         rand_index_vs_full=rand, desired_accuracy=args.desired_accuracy,
         meets_desired_accuracy=meets, early_stop=early_stop, **t)
    name = f"{args.dataset}/{args.algorithm}/{args.mode}/" + (
        "kernel" if args.use_kernel else "xla")
    return res, name, early_stop


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def serve_phase(res, data, k, *, backend, kind, seed=0):
    import jax
    from repro.core import ClusterArtifact, ClusteringEngine, EngineConfig
    from repro.serving import AssignRequest, ClusterServer, ModelRegistry

    art = ClusterArtifact(
        name=f"poker-kmeans-k{k}", algorithm="kmeans",
        params=np.asarray(jax.device_get(res["params"])), model=res["model"],
        desired_accuracy=DESIRED_ACCURACY)
    art = ClusterArtifact.from_json(art.to_json())      # its saved form
    registry = ModelRegistry()
    key = registry.register(art)
    entry = registry[key]
    check(entry.backend == backend,
          f"registry serves through {entry.backend!r}, not {backend!r}")
    server = ClusterServer(registry)
    with timed() as warm:
        server.warmup(key)
    rng = np.random.default_rng(seed)
    sizes = [1, 37, 256, 1000, 3000, 4096, 9000, 16384]
    starts = rng.integers(0, data.shape[0] - max(sizes), len(sizes))
    batches = [data[s:s + m] for s, m in zip(starts, sizes)]
    with timed() as t:
        for rid, xb in enumerate(batches):
            server.submit(AssignRequest(x=xb, model_key=key, rid=rid))
        out = server.drain()
    # the engine's assignment at the same parameters, on the same kernel
    eng = ClusteringEngine("kmeans", EngineConfig(use_kernel=True))
    _, ref, _ = eng.step(np.concatenate(batches), entry.params)
    ref = np.asarray(ref)
    off = 0
    for rid, xb in enumerate(batches):
        m = xb.shape[0]
        check(np.array_equal(out[rid], ref[off:off + m]),
              f"served labels of request {rid} differ from the engine's")
        off += m
    programs = server.compiled_programs()[key]["assign"]
    check(programs <= len(server.buckets), f"{programs} assign programs")
    emit(phase="serve", model=key, backend=entry.backend, device_kind=kind,
         requests=len(sizes), points=int(sum(sizes)),
         assign_programs=programs, labels_match_engine=True,
         warmup_compile_s=warm["compile_s"], **t)


# --------------------------------------------------------------------------
# sharded (four chips)
# --------------------------------------------------------------------------

# fixed thresholds in the steep part of each fit's h decay, so the stop is
# decided by the fit rather than by reduction-order or int8 noise.  EM's is
# looser: on poker's integer grid its components collapse onto the
# variance floor after a handful of iterations, and from there on even a
# reordered fp32 psum moves the log-likelihood by ~1e-3.
SHARDED_H_STAR = {"kmeans": 1e-4, "em": 1e-2}
# the phase runs restart fleets (restarts=4) and compares their best
# objectives, so it holds them to the restart-fleet tests' tolerances in
# tests/test_engine_sharded.py: an fp32 psum reorders the sum (1e-4), the
# int8_ef ring quantises the stats (1e-2)
SHARDED_RTOL = {"none": 1e-4, "int8_ef": 1e-2}
# an EM fit must keep every component (int8 rounding can starve one)
MIN_EM_WEIGHT = 1e-2


def sharded_phase(data, k, n_dev, *, kind, restarts=RESTARTS,
                  chunks=8, batch_chunks=2, max_iters=MAX_ITERS):
    import jax
    import jax.numpy as jnp
    from repro import core
    from repro.core import ClusteringEngine, EngineConfig
    from repro.launch import cluster

    devices = jax.devices()
    check(len(devices) >= n_dev, f"{len(devices)} devices, need {n_dev}")
    mesh = jax.make_mesh((n_dev,), ("data",), devices=devices[:n_dev],
                         axis_types=(jax.sharding.AxisType.Auto,))
    # the points really are spread over the mesh: one row-slice of every
    # chunk per device
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), jnp.asarray(data),
                                    k, chunks=chunks)
    prog = ClusteringEngine("kmeans", EngineConfig(chunks=chunks)) \
        .sharded_fit_callable(data, c0, mesh)
    xc = prog.args[0]
    shards = xc.addressable_shards
    check(len({s.device for s in shards}) == n_dev,
          f"points on {len({s.device for s in shards})} devices")
    check(all(s.data.shape == (xc.shape[0], xc.shape[1] // n_dev,
                               xc.shape[2]) for s in shards),
          "uneven point shards")
    emit(phase="sharded", check="placement", device_kind=kind,
         devices=n_dev, shard_shape=list(shards[0].data.shape))

    train = core.random_groups(data, GROUP_SIZE, max_groups=TRAIN_GROUPS,
                               seed=1)
    failed = []
    for alg in ("kmeans", "em"):
        for mode in ("full", "minibatch"):
            kw = dict(max_iters=max_iters, seed=0, restarts=restarts,
                      chunks=chunks, mode=mode,
                      batch_chunks=batch_chunks if mode == "minibatch" else 0)
            # the stop model the pipeline fits for this regime
            args = cluster.parse_args([
                "--dataset", "poker", "--k", str(k), "--algorithm", alg,
                "--mode", mode, "--chunks", str(chunks),
                "--batch-chunks", str(kw["batch_chunks"]),
                "--restarts", str(restarts), "--max-iters", str(max_iters),
                "--family", "auto",
                "--desired-accuracy", str(DESIRED_ACCURACY)])
            model, _, _ = cluster.fit_stop_model(args, train)
            fitted = dict(model=model, desired_accuracy=DESIRED_ACCURACY)
            for stop, h, extra_kw, gated in (
                    ("fixed", SHARDED_H_STAR[alg], {}, True),
                    ("fitted", model.threshold_for(DESIRED_ACCURACY),
                     fitted, False)):
                with timed() as t1:
                    _, j1, it1, _ = cluster.run_production(
                        data, k, alg, h, **extra_kw, **kw)
                for comp in ("none", "int8_ef"):
                    with timed() as t:
                        _, js, its, _, params = cluster.run_production(
                            data, k, alg, h, shard=True,
                            stats_compression=comp, return_params=True,
                            **extra_kw, **kw)
                    rtol = SHARDED_RTOL[comp]
                    ok = abs(its - it1) <= 1 and np.isclose(js, j1,
                                                            rtol=rtol)
                    extra = {}
                    if alg == "em":
                        w = float(np.exp(np.asarray(params.log_w)).min())
                        ok = ok and w > MIN_EM_WEIGHT
                        extra["min_weight"] = w
                    emit(phase="sharded", algorithm=alg, mode=mode,
                         restarts=restarts, stats_compression=comp,
                         devices=n_dev, device_kind=kind, stop=stop,
                         h_star=h, gated=gated, iters=its,
                         iters_device0=it1, objective=js,
                         objective_device0=j1,
                         objective_rel_diff=abs(js - j1) / abs(j1),
                         rtol=rtol, **extra,
                         parity="pass" if ok else "FAIL",
                         device0_compile_s=t1["compile_s"],
                         device0_run_s=t1["run_s"], **t)
                    if gated and not ok:
                        failed.append(f"{alg}/{mode}/{comp}")
    check(not failed, f"sharded fits off their device-0 twins: {failed}")


# --------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fit on a 4-device mesh")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FORCE_KERNEL_BACKEND"):
        _refuse("REPRO_FORCE_KERNEL_BACKEND is set; it would reroute the "
                "kernels off the chip")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _refuse(f"no TPU: JAX found {dev.platform!r} ({dev.device_kind})")
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        _refuse(f"--chips {args.chips} needs {args.chips} devices; JAX "
                f"found {n_dev}")
    if not (ROOT / "src" / "repro").is_dir():
        _refuse(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.data import PAPER_SIZES, load
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    kind = dev.device_kind
    emit(phase="start", platform=dev.platform, device_kind=kind,
         devices=n_dev, chips=args.chips, compile_cache=cache)
    poker = load("poker", n=PAPER_SIZES["poker"])

    if args.chips == 4:
        sharded_phase(poker, POKER_K, 4, kind=kind)
    else:
        kernels_phase(poker, POKER_K, RESTARTS, backend="tpu", kind=kind)
        # --family auto: the paper's model-selection comparison (§5.3.1);
        # a pinned quadratic goes negative below r* = 0.99 on these sets
        common = ["--dataset", "poker", "--k", str(POKER_K),
                  "--max-iters", str(MAX_ITERS), "--family", "auto",
                  "--desired-accuracy", str(DESIRED_ACCURACY)]
        runs = {}
        for alg in ("kmeans", "em"):
            for kernel in (False, True):
                runs[alg, kernel] = pipeline_phase(
                    poker, common + ["--algorithm", alg]
                    + (["--use-kernel"] if kernel else []),
                    kind=kind)
        skin = load("skin", n=PAPER_SIZES["skin"])
        runs["skin"] = pipeline_phase(skin, [
            "--dataset", "skin", "--k", str(SKIN_K), "--mode", "minibatch",
            "--chunks", "8", "--batch-chunks", "2",
            "--restarts", str(RESTARTS), "--use-kernel",
            "--max-iters", str(MAX_ITERS), "--family", "auto",
            "--desired-accuracy", str(DESIRED_ACCURACY)],
            kind=kind)
        summary = {}
        for _, name, early_stop in runs.values():
            summary.setdefault(early_stop, []).append(name)
        emit(phase="pipeline", summary=True, device_kind=kind,
             early_stop=summary)
        check(summary.get("met r*"),
              "no pipeline run's early stop certified r*")
        serve_phase(runs["kmeans", True][0], poker, POKER_K, backend="tpu",
                    kind=kind)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": n_dev}}))


if __name__ == "__main__":
    main()
