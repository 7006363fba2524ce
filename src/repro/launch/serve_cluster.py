"""Clustering-as-a-service launcher: registry + continuous-batching server.

    # serve artifacts saved by launch/cluster.py --save-artifact
    PYTHONPATH=src python -m repro.launch.serve_cluster \
        --registry artifacts/ --requests 64

    # self-contained demo: fit two small models, serve a mixed stream
    PYTHONPATH=src python -m repro.launch.serve_cluster --synthetic \
        --requests 32 --fit-jobs 2

The traffic generator enqueues assignment batches of mixed sizes across
every registered model (plus optional incremental fit jobs), drains the
queue through the bucket-padded hot path, and prints the per-model p50/p99
latency, throughput, QPS and compiled-program counts the capacity planner
consumes (PAPERS.md: D-SPACE4Cloud).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.core import (ClusterArtifact, ClusteringEngine, EngineConfig,
                        TrainingPlan, fit_for_config, load_registry_dir)
from repro.launch import compile_cache
from repro.serving import AssignRequest, ClusterServer, FitRequest, ModelRegistry


def _blobs(n, d, k, seed, spread=6.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, spread, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.0, (n // k, d))
                        for c in centers])
    return x[rng.permutation(len(x))].astype(np.float32)


def demo_artifacts(seed: int = 0, n: int = 3000, d: int = 4,
                   k: int = 3) -> list[ClusterArtifact]:
    """Two small fitted artifacts under distinct engine regimes — a
    minibatch k-means and a full-batch EM — for the demo/smoke path (and
    the serve benchmark, which needs models with real provenance)."""
    groups = np.stack([_blobs(n, d, k, seed + g) for g in range(2)])
    out = []
    for name, algorithm, config in (
            ("kmeans-mb", "kmeans",
             EngineConfig(mode="minibatch", chunks=8, batch_chunks=2,
                          patience=3, max_iters=60)),
            ("em-full", "em", EngineConfig(max_iters=40))):
        plan = TrainingPlan(algorithm=algorithm, k=k, config=config,
                            family="quadratic", seed=seed)
        model = fit_for_config(plan, groups)
        eng = ClusteringEngine(algorithm, config)
        x = groups[0]
        res = eng.fit(x, eng.init(jax.random.PRNGKey(seed), x, k),
                      h_star=model.threshold_for(0.95))
        params = jax.tree.map(np.asarray, res.params)
        out.append(ClusterArtifact(name=name, algorithm=algorithm,
                                   params=params, model=model,
                                   desired_accuracy=0.95))
    return out


def run_traffic(server: ClusterServer, keys, *, requests: int,
                min_batch: int, max_batch: int, fit_jobs: int, d: int,
                seed: int):
    """Enqueue a mixed stream across ``keys`` and drain it."""
    rng = np.random.default_rng(seed)
    rid = 0
    for _ in range(requests):
        key = keys[rng.integers(0, len(keys))]
        n = int(rng.integers(min_batch, max_batch + 1))
        server.submit(AssignRequest(x=rng.normal(0, 4, (n, d)), model_key=key,
                                    rid=rid))
        rid += 1
    for _ in range(fit_jobs):
        key = keys[rng.integers(0, len(keys))]
        n = int(rng.integers(max(min_batch, 64), max_batch + 1))
        server.submit(FitRequest(x=rng.normal(0, 4, (n, d)), model_key=key,
                                 rid=rid))
        rid += 1
    return server.drain()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="directory of ClusterArtifact *.json files")
    ap.add_argument("--synthetic", action="store_true",
                    help="fit two small demo artifacts instead of loading")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--fit-jobs", type=int, default=0)
    ap.add_argument("--min-batch", type=int, default=20)
    ap.add_argument("--max-batch", type=int, default=800)
    ap.add_argument("--buckets", default="256,1024,4096",
                    help="comma-separated bucket sizes (compile shapes)")
    ap.add_argument("--fit-steps", type=int, default=20,
                    help="max engine iterations per incremental fit job")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip pre-compiling the bucket programs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the metrics summary as JSON")
    args = ap.parse_args()
    compile_cache.enable()

    if args.synthetic:
        artifacts = demo_artifacts(args.seed)
    elif args.registry:
        artifacts = load_registry_dir(args.registry)
    else:
        ap.error("pass --registry DIR or --synthetic")
    if not artifacts:
        ap.error("no artifacts to serve")

    buckets = tuple(int(b) for b in args.buckets.split(","))
    registry = ModelRegistry(devices=len(jax.devices()),
                             fit_steps=args.fit_steps)
    keys = [registry.register(a) for a in artifacts]
    server = ClusterServer(registry, buckets=buckets)
    for key in keys:
        print(f"registered {key}")
        if not args.no_warmup:
            server.warmup(key)

    d = artifacts[0].d
    results = run_traffic(server, keys, requests=args.requests,
                          min_batch=args.min_batch,
                          max_batch=min(args.max_batch, buckets[-1]),
                          fit_jobs=args.fit_jobs, d=d, seed=args.seed)

    summary = {"metrics": server.metrics.summary(),
               "compiled_programs": server.compiled_programs(),
               "n_results": len(results)}
    for key, m in sorted(summary["metrics"].items()):
        print(f"{key}: {m['requests']} req / {m['batches']} batches, "
              f"p50 {m['p50_latency_ms']:.2f}ms p99 "
              f"{m['p99_latency_ms']:.2f}ms, "
              f"{m['throughput_points_per_s']:.0f} pts/s, "
              f"{m['qps']:.1f} qps")
    for key, c in sorted(summary["compiled_programs"].items()):
        print(f"{key}: {c['assign']} assign / {c['fit']} fit "
              "compiled programs")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
