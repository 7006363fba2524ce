"""Production clustering driver — the paper's pipeline end-to-end (§4, §5).

    PYTHONPATH=src python -m repro.launch.cluster \
        --dataset skin --k 2 --algorithm kmeans --desired-accuracy 0.99

Pipeline: synthesize/load data → random-sample into groups → 10-fold split →
harvest (r_i, h_i) traces from the training groups through the engine's
on-device trace recording (--train-mode matched harvests under the exact
production engine configuration; full harvests full-batch sweeps, the
transfer regime) → fit the regression (model selection or pinned quadratic,
harvest regime stamped as provenance) → h* = f(r*) → early-stopped
production clustering (on-device while_loop; shard_map over the data axis
when this host has multiple devices — full sweeps, minibatch, vmapped
multi-restart and the --use-kernel fused sweeps all compose with --shard;
--kernel-backend pins a registry backend) → validation: achieved accuracy
vs. the full run + cost report (Eq. 6/9/10).

Set ``--devices N`` via XLA host-platform flag *before* launch to exercise
the distributed path, e.g.:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m ... --shard
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core, spans
from repro.core import em_gmm
from repro.data import load as load_data, spacenet_pixels
from repro.launch import compile_cache


def train_regression(groups, k: int, algorithm: str, *, max_iters: int,
                     family: str | None, use_kernel: bool = False,
                     train_mode: str = "full", production_config=None,
                     seed: int = 0):
    """Fit h(r) from the training groups.  Paper §5.3.1, mode-matched.

    Both train modes route through ``repro.core.longtail_train``: the
    engine's fit drivers record the (J, paired-h, params) trace on device
    and the accuracy r_i is read off the parameter trajectory — no
    host-side step loop re-running sweeps.

    ``train_mode="full"`` harvests full-batch traces (the legacy transfer
    regime: h* rides the paired Eq. 7 stop into whatever configuration
    production uses); ``train_mode="matched"`` harvests under
    ``production_config`` itself — same mode, chunk layout, batch draws,
    decay/ema and kernel routing the threshold will serve — which is what
    tightens the achieved-accuracy spread (ROADMAP;
    ``BENCH_longtail_matched.json``).  Either way the harvest regime is
    stamped into the model's provenance, so
    ``EngineConfig.from_longtail`` warns on a mismatch at serve time.
    """
    from repro.core.engine import EngineConfig
    from repro.core.longtail_train import TrainingPlan, fit_for_config
    t0 = time.time()
    if train_mode == "matched":
        if production_config is None:
            raise ValueError("train_mode='matched' needs the production "
                             "EngineConfig to harvest under")
        cfg = production_config
    elif train_mode == "full":
        # full-batch harvest regime; keep the kernel routing (and the pinned
        # backend) so --use-kernel trains through the same sweep math
        kw = dict(max_iters=max_iters)
        src = production_config
        if src is not None and src.use_kernel:
            kw.update(use_kernel=True, kernel_backend=src.kernel_backend)
        elif use_kernel:
            kw["use_kernel"] = True
        cfg = EngineConfig(**kw)
    else:
        raise ValueError(f"unknown train_mode {train_mode!r} "
                         "(expected 'matched' or 'full')")
    plan = TrainingPlan(algorithm=algorithm, k=k, config=cfg, family=family,
                        max_iters=max_iters, seed=seed)
    model = fit_for_config(plan, groups)
    return model, time.time() - t0


def _data_mesh():
    """A 1-axis ("data",) mesh over every visible device."""
    n_dev = len(jax.devices())
    return jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _resolve_shard(shard: bool, n_devices: int) -> bool:
    """--shard on a 1-device host cannot shard anything: refuse, rather
    than run the single-device path while the user believes the
    distributed drivers ran."""
    if shard and n_devices < 2:
        raise ValueError(
            "--shard needs more than one device, but only 1 is visible.  "
            "On a CPU host set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N before launch to run the distributed drivers.")
    return shard


@spans.span("entry.job")
def run_production(x, k: int, algorithm: str, h_star: float, *,
                   max_iters: int, seed: int = 0, shard: bool = False,
                   use_kernel: bool = False, patience: int = 3,
                   chunks: int = 1, restarts: int = 1,
                   mode: str = "full", batch_chunks: int = 0,
                   decay: float = 1.0, kernel_backend: str | None = None,
                   model=None, desired_accuracy: float | None = None,
                   stats_compression: str = "none", prefetch: bool = False,
                   return_params: bool = False):
    """Early-stopped production run; optional shard_map over host devices.

    ``chunks`` streams each sweep over N/C pieces; ``restarts`` runs R seeds
    as one vmapped program and keeps the best objective.  Pass a fitted
    ``model`` (LongTailModel) + ``desired_accuracy`` to derive the threshold
    through ``EngineConfig.from_longtail`` instead of a raw ``h_star``.

    ``mode="minibatch"`` samples ``batch_chunks`` of the ``chunks`` pieces
    per iteration with learning-rate updates (forgetting factor ``decay``) —
    the fitted threshold still drives the stop via the engine's paired
    Eq. 7 change rate.  Both minibatch and multi-restart compose with
    ``shard``: the engine's ``fit_sharded`` / ``fit_restarts_sharded``
    drivers chunk the points globally and shard each chunk's rows, so the
    distributed run reproduces the single-device trajectory (same seeded
    chunk draws, psum'd stats and stop decision) up to fp32 reduction
    order.

    For k-means, ``h_star == 0.0`` (no model) means the full-convergence
    reference run: stop only when the centroids freeze.  An h-based stop at
    h*=0 quits on fp32 J plateaus before the Lloyd fixed point (see
    ``kmeans_fit_full``), which would corrupt the Time_full baseline.

    ``stats_compression="int8_ef"`` routes the sharded sweeps' stats
    reductions through the int8 ring all-reduce with error feedback
    (``EngineConfig.stats_compression``); ``prefetch`` double-buffers the
    chunk scan.  ``return_params=True`` appends the fitted parameters to
    the result tuple (for ``--save-artifact``).
    """
    from repro.core.engine import ClusteringEngine, EngineConfig
    with spans.span("entry.transfer"):
        x = jnp.asarray(x)

    shard = _resolve_shard(shard, len(jax.devices()))
    full_reference = (algorithm == "kmeans" and model is None
                      and float(h_star) == 0.0 and mode == "full")
    if stats_compression != "none" and full_reference:
        raise ValueError(
            "the full-convergence k-means reference stops on frozen "
            "centroids, which int8-quantised stats never reach — run the "
            "reference with stats_compression='none'")
    cfg_kw = dict(max_iters=max_iters, patience=patience, chunks=chunks,
                  use_kernel=use_kernel, use_h_stop=not full_reference,
                  stop_when_frozen=(algorithm == "kmeans"
                                    and stats_compression == "none"),
                  mode=mode, batch_chunks=batch_chunks, decay=decay,
                  stats_compression=stats_compression, prefetch=prefetch)
    if use_kernel and kernel_backend not in (None, "auto"):
        cfg_kw["kernel_backend"] = kernel_backend
    if mode == "minibatch":
        # config is a static jit argument: only bake the seed in when the
        # engine actually samples from it, or every per-group seed would
        # force a fresh full-mode compile
        cfg_kw["seed"] = seed
    if model is not None and desired_accuracy is None:
        raise ValueError("model routing needs desired_accuracy")
    with spans.span("entry.config"):
        cfg = (EngineConfig.from_longtail(model, desired_accuracy, **cfg_kw)
               if model is not None
               else EngineConfig(h_star=float(h_star), **cfg_kw))
    eng = ClusteringEngine(algorithm, cfg)

    with spans.span("entry.seed"):
        key = jax.random.PRNGKey(seed)
        if restarts > 1 and algorithm == "em":
            # match the single-restart init quality: kmeans++-seeded GMMs
            # per restart (the engine default draws uniform data points)
            inits = [em_gmm.init_from_kmeans(
                x, core.kmeans_plus_plus_init(kk, x, k, chunks=chunks))
                for kk in jax.random.split(key, restarts)]
            params0 = jax.tree.map(lambda *ls: jnp.stack(ls), *inits)
        elif restarts > 1:
            params0 = eng.init_restarts(key, x, k, restarts)
        else:
            c0 = core.kmeans_plus_plus_init(key, x, k, chunks=chunks)
            params0 = (c0 if algorithm == "kmeans"
                       else em_gmm.init_from_kmeans(x, c0))

    # the fit's seconds (Eq. 10's time): dispatch to labels ready.  The
    # sharded driver is the engine's chunk-layout one for both modes and
    # both sweep implementations: cfg already encodes the stop semantics
    # (incl. the full_reference frozen-centroids guard via
    # use_h_stop=False) and the kernel routing, and its padded layout
    # keeps every row, so the label contract matches the unsharded run.
    t0 = time.perf_counter()
    if restarts > 1:
        res = (eng.fit_restarts_sharded(x, params0, _data_mesh()) if shard
               else eng.fit_restarts(x, params0)).best
    elif shard:
        res = eng.fit_sharded(x, params0, _data_mesh())
    else:
        res = eng.fit(x, params0)
    with spans.span("entry.wait"):
        jax.block_until_ready(res.labels)
    fit_s = time.perf_counter() - t0
    with spans.span("entry.readback"):
        out = (res.labels, float(res.objective), int(res.n_iters), fit_s)
    return out + (res.params,) if return_params else out


def parse_args(argv=None):
    """The CLI's flags → argparse Namespace, with the minibatch defaults
    resolved (the bare ``--mode minibatch`` recipe runs)."""
    ap = argparse.ArgumentParser(prog="repro.launch.cluster")
    ap.add_argument("--dataset", default="skin",
                    choices=["road3d", "skin", "poker", "spacenet"])
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--algorithm", default="kmeans", choices=["kmeans", "em"])
    ap.add_argument("--desired-accuracy", type=float, default=0.99)
    ap.add_argument("--n", type=int, default=60_000)
    ap.add_argument("--group-size", type=int, default=10_000)
    ap.add_argument("--train-groups", type=int, default=4)
    ap.add_argument("--prod-groups", type=int, default=2)
    ap.add_argument("--max-iters", type=int, default=300)
    ap.add_argument("--family", default="quadratic",
                    help="'auto' runs the paper's model-selection comparison")
    ap.add_argument("--shard", action="store_true")
    ap.add_argument("--chunks", type=int, default=1,
                    help="stream each sweep over C chunks (engine mode)")
    ap.add_argument("--mode", default="full", choices=["full", "minibatch"],
                    help="minibatch: sample --batch-chunks of --chunks per "
                         "iteration with learning-rate updates")
    ap.add_argument("--batch-chunks", type=int, default=0,
                    help="minibatch size in chunks (B of C per iteration)")
    ap.add_argument("--decay", type=float, default=1.0,
                    help="minibatch count forgetting factor (1.0 = Sculley "
                         "1/t annealing)")
    ap.add_argument("--restarts", type=int, default=1,
                    help="vmapped multi-restart count; best objective wins")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route sweeps through the kernel dispatch layer "
                         "(backend registry: Pallas compiled on TPU/GPU, "
                         "interpreter elsewhere; composes with --shard, "
                         "--restarts and --mode minibatch)")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "tpu", "gpu", "interpret", "xla"],
                    help="pin a registry backend for --use-kernel (auto "
                         "resolves from jax.default_backend(); xla is the "
                         "reference contract)")
    ap.add_argument("--train-mode", default=None,
                    choices=["matched", "full"],
                    help="harvest the h(r) training traces under the "
                         "production engine configuration ('matched' — "
                         "mode, chunks, batch draws, kernel routing) or "
                         "under plain full-batch sweeps ('full', the "
                         "transfer regime).  Default: matched when --mode "
                         "minibatch, else full")
    ap.add_argument("--stats-compression", default="none",
                    choices=["none", "int8_ef"],
                    help="compress the sharded sweeps' stats reductions "
                         "(int8 ring all-reduce with error feedback; "
                         "requires --shard)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffer the streaming chunk scan so the "
                         "next chunk's load overlaps the current compute "
                         "(bit-identical results)")
    ap.add_argument("--save-model", default=None, metavar="PATH",
                    help="write the fitted LongTailModel JSON (regression "
                         "+ harvest-regime provenance) to PATH")
    ap.add_argument("--save-artifact", default=None, metavar="PATH",
                    help="write a ClusterArtifact JSON (fitted params + "
                         "LongTailModel) from the first production group — "
                         "loadable by serve_cluster --registry")
    ap.add_argument("--instance", default="m5.large")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.kernel_backend != "auto" and not args.use_kernel:
        ap.error("--kernel-backend only applies with --use-kernel")
    if args.stats_compression != "none" and not args.shard:
        ap.error("--stats-compression only applies with --shard (it "
                 "compresses the cross-device stats reduction)")

    if args.mode == "minibatch":
        # make the bare `--mode minibatch` recipe runnable: the full-sweep
        # defaults (--chunks 1 --batch-chunks 0) cannot subsample, so pick
        # the documented 25%-touch defaults and say so
        defaulted = []
        if args.chunks < 2:
            args.chunks = 8
            defaulted.append(f"--chunks {args.chunks}")
        if args.batch_chunks < 1:
            args.batch_chunks = max(1, args.chunks // 4)
            defaulted.append(f"--batch-chunks {args.batch_chunks}")
        if defaulted:
            print("[cluster] minibatch defaults: " + " ".join(defaulted))
    return args


def load_groups(args):
    """(training groups, production groups) drawn from ``args.dataset``."""
    n_prod = max(args.prod_groups, 1)
    if args.dataset == "spacenet":
        groups = spacenet_pixels(n_images=args.train_groups + n_prod,
                                 k_true=args.k)
    else:
        data = load_data(args.dataset, n=args.n)
        groups = core.random_groups(data, args.group_size,
                                    max_groups=args.train_groups + n_prod)
    return groups[:args.train_groups], groups[args.train_groups:]


def fit_stop_model(args, train_g):
    """h(r) fitted on ``train_g`` for the production regime ``args``
    describes: (LongTailModel, training seconds, train mode)."""
    family = None if args.family == "auto" else args.family
    train_mode = args.train_mode or (
        "matched" if args.mode == "minibatch" else "full")
    # the regime the fitted threshold will serve — harvested under in
    # matched mode, stamped into the model's provenance in both modes
    from repro.core.engine import EngineConfig
    cfg_kw = dict(max_iters=args.max_iters, chunks=args.chunks,
                  use_kernel=args.use_kernel,
                  stop_when_frozen=(args.algorithm == "kmeans"),
                  mode=args.mode)
    if args.use_kernel and args.kernel_backend != "auto":
        cfg_kw["kernel_backend"] = args.kernel_backend
    if args.mode == "minibatch":
        cfg_kw.update(batch_chunks=args.batch_chunks, decay=args.decay)
    production_cfg = EngineConfig(**cfg_kw)
    model, t_train = train_regression(train_g, args.k, args.algorithm,
                                      max_iters=args.max_iters, family=family,
                                      train_mode=train_mode,
                                      production_config=production_cfg)
    return model, t_train, train_mode


def pipeline(args, train_g, prod_g) -> dict:
    """The paper's pipeline on given groups: fit h(r) on ``train_g``, then
    per production group an early-stopped run at h* = f(r*) and the
    full-convergence reference.  Returns the report (the ``--out`` JSON
    fields, plus per-group ``iters_*``/``accuracies``, the fitted
    ``model`` and the first production group's ``params``)."""
    model, t_train, train_mode = fit_stop_model(args, train_g)
    h_star = model.threshold_for(args.desired_accuracy)
    print(f"regression ({model.regression.family}, {train_mode} harvest): "
          f"coeffs={[round(c, 6) for c in model.regression.coeffs]} "
          f"R²={model.regression.metrics.r2:.4f}")
    print(f"h*({args.desired_accuracy}) = {h_star:.3e}   "
          f"(training took {t_train:.1f}s, amortised — Eq. 9)")

    # production: each group is one clustering task — the paper's unit of
    # work (§5.2 "image = group"; the regression transfers within-regime)
    t_actual = t_full = 0.0
    accs, iters_es, iters_fu = [], [], []
    params = None
    for gi, g in enumerate(prod_g):
        # the fitted LongTailModel drives the threshold through EngineConfig
        labels, j, it1, t1, *rest = run_production(
            g, args.k, args.algorithm, h_star, max_iters=args.max_iters,
            seed=100 + gi, shard=args.shard, use_kernel=args.use_kernel,
            chunks=args.chunks, restarts=args.restarts,
            mode=args.mode, batch_chunks=args.batch_chunks, decay=args.decay,
            kernel_backend=args.kernel_backend,
            model=model, desired_accuracy=args.desired_accuracy,
            stats_compression=args.stats_compression, prefetch=args.prefetch,
            return_params=(gi == 0))
        if rest:
            params = rest[0]
        # the full-convergence baseline always runs full sweeps — it is the
        # Time_full / 100%-accuracy reference the savings are measured from
        labels_f, j_f, it2, t2 = run_production(
            g, args.k, args.algorithm, 0.0, max_iters=args.max_iters * 3,
            seed=100 + gi, shard=args.shard, use_kernel=args.use_kernel,
            kernel_backend=args.kernel_backend, chunks=args.chunks)
        t_actual += t1
        t_full += t2
        accs.append(float(core.rand_index(labels[:labels_f.shape[0]],
                                          labels_f, args.k, args.k)))
        iters_es.append(int(it1))
        iters_fu.append(int(it2))
    acc = float(np.mean(accs))
    rep = core.report(t_actual, t_full, time_train_s=t_train,
                      instance=args.instance)
    print(f"early-stop: {iters_es} iters {t_actual:.2f}s | "
          f"full: {iters_fu} iters {t_full:.2f}s | achieved accuracy "
          f"{acc:.4f} (per group: {[round(a, 3) for a in accs]})")
    print(f"cost-effectiveness (Eq.10) = {rep.cost_effectiveness:.3f}  "
          f"savings = ${rep.savings_usd:.6f} on {args.instance}")
    return {
        "dataset": args.dataset, "k": args.k,
        "algorithm": args.algorithm, "mode": args.mode,
        "desired_accuracy": args.desired_accuracy,
        "achieved_accuracy": acc, "h_star": h_star,
        "iters_earlystop": sum(iters_es), "iters_full": sum(iters_fu),
        "time_actual_s": t_actual, "time_full_s": t_full,
        "time_train_s": t_train,
        "cost_effectiveness": rep.cost_effectiveness,
        "regression": json.loads(model.to_json()),
        "accuracies": accs, "iters_earlystop_per_group": iters_es,
        "iters_full_per_group": iters_fu,
        "model": model, "params": params,
    }


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    train_g, prod_g = load_groups(args)
    res = pipeline(args, train_g, prod_g)
    model = res["model"]
    if args.save_model:
        with open(args.save_model, "w") as f:
            f.write(model.to_json() + "\n")
        print(f"saved LongTailModel → {args.save_model}")
    if args.save_artifact:
        # host-side copy of the first group's early-stopped fit, paired
        # with the stop-model that certified it — the registry unit
        # serve_cluster --registry loads
        art = core.ClusterArtifact(
            name=f"{args.dataset}-{args.algorithm}-k{args.k}",
            algorithm=args.algorithm,
            params=jax.tree.map(np.asarray, res["params"]),
            model=model, desired_accuracy=args.desired_accuracy)
        art.save(args.save_artifact)
        print(f"saved ClusterArtifact ({art.k} clusters, d={art.d}) → "
              f"{args.save_artifact}")
    if args.out:
        keep = ("dataset", "k", "algorithm", "mode", "desired_accuracy",
                "achieved_accuracy", "h_star", "iters_earlystop",
                "iters_full", "time_actual_s", "time_full_s",
                "time_train_s", "cost_effectiveness", "regression")
        with open(args.out, "w") as f:
            json.dump({k: res[k] for k in keep}, f, indent=1)


if __name__ == "__main__":
    main()
