"""Cost-aware provisioning planner CLI (docs/cost_planning.md walks this).

    PYTHONPATH=src python -m repro.launch.plan \
        --dataset skin --k 2 --target-r 0.99 --deadline-s 3600

Pipeline: load the dataset → sample training groups → per candidate mode,
harvest (r, h) traces under that mode's engine regime and fit BOTH the
h(r) regression (``core.longtail_train``, provenance-stamped) and the
geometric :class:`IterationModel` from the same traces → interpolate
per-iteration throughput from the committed ``BENCH_*.json`` → enumerate
(mode × devices × compression × prefetch × instance × pricing), price
each candidate (Eq. 6 at market rate, spot walls inflated by the
expected-restart model), and print the cheapest feasible plan plus the
runner-up table.

``--validate`` then executes the chosen plan through the real fit
drivers on a held-out group: the early-stopped run, the full-convergence
reference it is priced against, and a short host-stepped loop wrapped in
``launch.straggler.StragglerMonitor`` so slow-shard evidence rides
along.  The predicted-vs-actual record (``benchmarks/run.py --only plan``
commits it as ``BENCH_plan.json``) is CI-gated.

Exit codes: 0 plan emitted (validation, if requested, within tolerance);
2 no feasible plan (``PlanError`` — the message names the binding
constraint); 3 validation ran but actual iterations fell outside the
stated tolerance band of predicted.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core.cost_model import PriceTable, candidate_cost_usd
from repro.core.engine import ClusteringEngine, EngineConfig
from repro.core.longtail_train import (TrainingPlan, fit_for_config,
                                       harvest_traces)
from repro.core.planner import (IterationModel, PlanError, PlanReport,
                                PlanSpec, ThroughputModel, plan)
from repro.data import load as load_data
from repro.launch import compile_cache
from repro.launch.straggler import StragglerMonitor

EXIT_OK = 0
EXIT_INFEASIBLE = 2
EXIT_VALIDATION = 3

# predicted-vs-actual acceptance band, stamped into every validation
# record: iterations are host-independent (hard-gated in CI); wall is
# advisory (BENCH throughput was measured on a different host class)
TOLERANCE = {"iters_rel": 0.5, "iters_abs": 5, "wall_advisory": True}


def _mode_config(mode: str, *, algorithm: str, chunks: int,
                 batch_chunks: int, decay: float,
                 max_iters: int) -> EngineConfig:
    kw = dict(max_iters=max_iters, chunks=chunks, mode=mode,
              stop_when_frozen=(algorithm == "kmeans"))
    if mode == "minibatch":
        kw.update(batch_chunks=batch_chunks, decay=decay)
    return EngineConfig(**kw)


def fit_models(groups, *, algorithm: str = "kmeans", k: int = 2,
               modes=("full", "minibatch"), chunks: int = 16,
               batch_chunks: int = 4, decay: float = 0.95,
               max_iters: int = 400, family: str | None = "quadratic",
               seed: int = 0, dataset: str = "skin"):
    """Per-mode (LongTailModel, IterationModel) from ONE harvest each.

    The same iteration-ordered h sequences feed both fits: the h(r)
    regression pools (r, h) pairs, the iteration model the h trajectory —
    so the planner's two predictors cannot disagree about the regime they
    describe.
    """
    models: dict = {}
    iteration_models: dict = {}
    for mode in modes:
        cfg = _mode_config(mode, algorithm=algorithm, chunks=chunks,
                           batch_chunks=batch_chunks, decay=decay,
                           max_iters=max_iters)
        tplan = TrainingPlan(algorithm=algorithm, k=k, config=cfg,
                             family=family, max_iters=max_iters,
                             seed=seed, dataset=dataset)
        traces = harvest_traces(tplan, groups)
        models[mode] = fit_for_config(tplan, groups, traces=traces)
        iteration_models[mode] = IterationModel.from_traces(
            [h for _, h in traces])
    return models, iteration_models


def predict_for_candidate(chosen, n: int, throughput: ThroughputModel,
                          price, *, train_time_s: float = 0.0,
                          restart_overhead_s: float = 60.0,
                          checkpoint_interval_s: float | None = None):
    """Re-predict the CHOSEN candidate's wall/cost at a different N (the
    validation group is smaller than the planning target — predicted and
    actual must compare like for like)."""
    touched = (2.0 * n * chosen.batch_chunks / chosen.chunks
               if chosen.mode == "minibatch" else float(n))
    s_iter = throughput.seconds_per_iter(
        touched, chosen.devices, mode=chosen.mode, backend=chosen.backend,
        compression=chosen.stats_compression)
    wall = chosen.predicted_iters * s_iter
    cost = candidate_cost_usd(
        wall + train_time_s, price, chosen.devices, chosen.pricing,
        restart_overhead_s=restart_overhead_s,
        checkpoint_interval_s=checkpoint_interval_s)
    return {"iters": chosen.predicted_iters, "wall_s": wall,
            "cost_usd": cost}


def _monitored_steps(x, cfg: EngineConfig, algorithm: str, k: int,
                     n_steps: int, seed: int) -> dict:
    """Short host-stepped loop under the chosen config, each iteration
    timed by StragglerMonitor — the slow-shard evidence channel the
    jitted while_loop fit cannot expose (no host boundary per step).
    Fleet rebalancing on these flags stays a future PR (ROADMAP)."""
    eng = ClusteringEngine(algorithm, cfg)
    params = eng.init(jax.random.PRNGKey(seed), x, k)
    mon = StragglerMonitor(window=16, grace_steps=2)
    for _ in range(n_steps):
        mon.start()
        params, _, obj = eng.step(x, params)
        jax.block_until_ready(obj)
        mon.stop()
    return mon.report()


def validate_plan(report: PlanReport, x_val, *, algorithm: str, k: int,
                  models: dict, throughput: ThroughputModel,
                  prices: PriceTable, target_r: float, max_iters: int,
                  monitor_steps: int = 12, seed: int = 123) -> dict:
    """Execute the chosen plan through the real fit drivers and record
    predicted vs actual (iterations, wall, Eq. 6 cost at the chosen
    market rate) plus the full-convergence reference and the straggler
    report.  This dict is the body of ``BENCH_plan.json``."""
    from repro.launch.cluster import run_production

    chosen = report.chosen
    n_val = int(x_val.shape[0])
    price = prices.get(chosen.instance)
    predicted = predict_for_candidate(chosen, n_val, throughput, price)

    shard = chosen.devices > 1 and len(jax.devices()) > 1
    t0 = time.time()

    def _warm(run):
        # each leg runs twice with identical static config/shapes: the
        # first call pays XLA compilation, the second reuses the jit
        # cache — Eq. 6/10 compares steady-state compute walls, and on a
        # small validation group compile time would otherwise dominate
        # both legs and drown the comparison
        run()
        return run()

    labels, _, iters_es, wall_es = _warm(lambda: run_production(
        x_val, k, algorithm, chosen.h_star, max_iters=max_iters,
        seed=seed, shard=shard, chunks=chosen.chunks, mode=chosen.mode,
        batch_chunks=chosen.batch_chunks, decay=chosen.decay,
        model=models[chosen.mode], desired_accuracy=target_r,
        stats_compression=(chosen.stats_compression if shard else "none"),
        prefetch=chosen.prefetch))
    # the Time_full baseline the saving is measured from (Eq. 10)
    labels_f, _, iters_fu, wall_fu = _warm(lambda: run_production(
        x_val, k, algorithm, 0.0, max_iters=max_iters * 3, seed=seed,
        shard=shard, chunks=chosen.chunks))
    accuracy = float(core.rand_index(labels, labels_f, k, k))

    actual_cost = candidate_cost_usd(wall_es, price, chosen.devices,
                                     chosen.pricing)
    full_cost = candidate_cost_usd(wall_fu, price, chosen.devices,
                                   chosen.pricing)
    straggler = _monitored_steps(
        x_val, EngineConfig(**{**chosen.engine_kwargs(),
                               "max_iters": max_iters}),
        algorithm, k, monitor_steps, seed)

    iters_err = abs(iters_es - predicted["iters"])
    iters_band = max(TOLERANCE["iters_rel"] * predicted["iters"],
                     TOLERANCE["iters_abs"])
    return {
        "n_val": n_val,
        "wall_clock_validate_s": time.time() - t0,
        "predicted": predicted,
        "actual": {"iters": int(iters_es), "wall_s": wall_es,
                   "cost_usd": actual_cost, "accuracy": accuracy},
        "full_actual": {"iters": int(iters_fu), "wall_s": wall_fu,
                        "cost_usd": full_cost},
        "tolerance": TOLERANCE,
        "iters_within_tolerance": bool(iters_err <= iters_band),
        "cost_fraction_actual": (actual_cost / full_cost
                                 if full_cost > 0 else float("inf")),
        "straggler": straggler,
    }


def _parse_grid(s: str, cast=int) -> tuple:
    return tuple(cast(v) for v in s.split(",") if v)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="search the engine configuration space for the "
                    "cheapest plan meeting (r*, deadline) on a price "
                    "table; see docs/cost_planning.md")
    ap.add_argument("--target-r", type=float, default=0.99,
                    help="desired accuracy r* (Rand index vs the "
                         "full-convergence partition)")
    ap.add_argument("--deadline-s", type=float, default=3600.0,
                    help="billed-wall deadline per clustering task "
                         "(spot candidates are inflated by the "
                         "expected-restart model before this check)")
    ap.add_argument("--prices", default=None, metavar="PATH",
                    help="price-table JSON (list of {name, "
                         "on_demand_per_hour, spot_per_hour, "
                         "preemption_per_hour}); omit for the built-in "
                         "EC2+TPU defaults")
    ap.add_argument("--dataset", default="skin",
                    choices=["road3d", "skin", "poker"])
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--algorithm", default="kmeans",
                    choices=["kmeans", "em"])
    ap.add_argument("--plan-n", type=int, default=None,
                    help="N the plan targets (default: --n); throughput "
                         "is interpolated/extrapolated to this size")
    ap.add_argument("--n", type=int, default=60_000,
                    help="dataset rows to load for harvest + validation")
    ap.add_argument("--group-size", type=int, default=6_000)
    ap.add_argument("--train-groups", type=int, default=3)
    ap.add_argument("--max-iters", type=int, default=400)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument("--batch-chunks", type=int, default=4)
    ap.add_argument("--decay", type=float, default=0.95)
    ap.add_argument("--patience", type=int, default=3)
    ap.add_argument("--family", default="quadratic",
                    help="'auto' runs the Eq. 8 model-selection "
                         "comparison per mode")
    ap.add_argument("--modes", default="full,minibatch",
                    help="comma list of candidate modes")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma list of candidate device counts")
    ap.add_argument("--compressions", default="none,int8_ef",
                    help="comma list of candidate stats_compression "
                         "values (int8_ef applies to sharded minibatch)")
    ap.add_argument("--backend", default=None,
                    choices=["tpu", "gpu", "interpret", "xla"],
                    help="pin a kernel backend for every candidate "
                         "(default: the jnp sweep path)")
    ap.add_argument("--bench-dir", default=None,
                    help="directory holding the committed BENCH_*.json "
                         "(default: the repo root)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--validate", action="store_true",
                    help="execute the chosen plan on a held-out group "
                         "through the real fit drivers and record "
                         "predicted-vs-actual (+ straggler report)")
    ap.add_argument("--monitor-steps", type=int, default=12,
                    help="host-stepped iterations timed by the "
                         "StragglerMonitor during --validate")
    ap.add_argument("--json", action="store_true",
                    help="print the PlanReport JSON instead of the table")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the PlanReport (+ validation record) "
                         "JSON to PATH")
    args = ap.parse_args(argv)
    compile_cache.enable()

    prices = PriceTable.default()
    if args.prices:
        with open(args.prices) as f:
            prices = PriceTable.from_json(f.read())

    data = load_data(args.dataset, n=args.n)
    n_groups = args.train_groups + (1 if args.validate else 0)
    groups = core.random_groups(data, args.group_size,
                                max_groups=n_groups)
    train_g = groups[:args.train_groups]
    modes = tuple(args.modes.split(","))

    t0 = time.time()
    models, iteration_models = fit_models(
        train_g, algorithm=args.algorithm, k=args.k, modes=modes,
        chunks=args.chunks, batch_chunks=args.batch_chunks,
        decay=args.decay, max_iters=args.max_iters,
        family=None if args.family == "auto" else args.family,
        seed=args.seed, dataset=args.dataset)
    t_train = time.time() - t0
    for m in modes:
        im = iteration_models[m]
        print(f"[plan] {m}: h(r) {models[m].regression.family} "
              f"R²={models[m].regression.metrics.r2:.4f} | iteration "
              f"model h0={im.h0:.3e} rho={im.rho:.4f} "
              f"floor={im.h_floor:.3e} n_full={im.n_full}")

    throughput = ThroughputModel.from_bench_dir(args.bench_dir)
    spec = PlanSpec(
        n=args.plan_n or args.n, d=int(data.shape[1]), k=args.k,
        target_r=args.target_r, deadline_s=args.deadline_s,
        prices=prices, max_iters=args.max_iters, chunks=args.chunks,
        batch_chunks=args.batch_chunks, decay=args.decay,
        patience=args.patience,
        device_grid=_parse_grid(args.devices), modes=modes,
        compressions=tuple(args.compressions.split(",")),
        backend=args.backend, train_time_s=t_train)
    try:
        report = plan(spec, models=models,
                      iteration_models=iteration_models,
                      throughput=throughput)
    except PlanError as e:
        print(f"[plan] ERROR: {e}", file=sys.stderr)
        return EXIT_INFEASIBLE

    chosen = report.chosen
    if args.json:
        print(report.to_json())
    else:
        print(report.table())
        print(f"[plan] chosen: {chosen.describe()} — "
              f"{chosen.predicted_iters} iters, "
              f"{chosen.predicted_wall_s:.3f}s wall, "
              f"${chosen.predicted_cost_usd:.8f} "
              f"({report.cost_fraction:.3f}× the full-convergence cost)")
        print(f"[plan] EngineConfig kwargs: {chosen.engine_kwargs()}")

    payload = json.loads(report.to_json())
    rc = EXIT_OK
    if args.validate:
        x_val = jnp.asarray(groups[-1], jnp.float32)
        record = validate_plan(
            report, x_val, algorithm=args.algorithm, k=args.k,
            models=models, throughput=throughput, prices=prices,
            target_r=args.target_r, max_iters=args.max_iters,
            monitor_steps=args.monitor_steps, seed=args.seed + 123)
        payload["validation"] = record
        print(f"[plan] validate: predicted {record['predicted']['iters']}"
              f" iters / ${record['predicted']['cost_usd']:.8f} vs actual"
              f" {record['actual']['iters']} iters / "
              f"${record['actual']['cost_usd']:.8f} "
              f"(accuracy {record['actual']['accuracy']:.4f}, "
              f"cost fraction {record['cost_fraction_actual']:.3f})")
        print(f"[plan] straggler: {record['straggler']}")
        if not record["iters_within_tolerance"]:
            print("[plan] VALIDATION OUT OF TOLERANCE: actual iterations "
                  f"{record['actual']['iters']} vs predicted "
                  f"{record['predicted']['iters']} (band: ±max("
                  f"{TOLERANCE['iters_rel']:.0%}, "
                  f"{TOLERANCE['iters_abs']}))", file=sys.stderr)
            rc = EXIT_VALIDATION

    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"[plan] wrote {args.out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
