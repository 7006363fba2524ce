#!/usr/bin/env python3
"""Readings that set the output check's limits, on every job of a cell.

    python3 bench/control.py --workload poker-km.whole \
        --modes program control state_unchanged half_batch altered_answer

For each mode, each job of the cell's pool (or the first ``--jobs``) runs
early-stopped and to full convergence, and is compared with the float64
replay (the configuration's ``references/`` file) by the numbers the benchmark checks:

    program         the program as the benchmark runs it (lower readings);
    control         the reference put in the program's place, its distances
                    computed one precision step below the configuration's:
                    float32 at three bfloat16 passes ('high') instead of
                    float32 at 'highest' (upper readings);
    state_unchanged the centroid update returns the centroids it was given;
    half_batch      each sweep's sums, counts and J cover the first half of
                    the points only, the means taken over them;
    altered_answer  the assignment pass returns point 0 in the next cluster.

The faults are planted in the program (``repro.core.kmeans``) for the
length of their mode.  One process reads every mode, so the pool, the stop
model and the replays are made once.  Prints one JSON line per mode with
the worst of each number over the jobs, and writes every job's numbers to
``--out`` when given.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def distances_bf16x3(x, c):
    """float32 distances whose products take three bfloat16 passes
    (hi*hi + hi*lo + lo*hi), as the TPU computes precision 'high'."""
    import jax
    import jax.numpy as jnp

    def split(a):
        hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    xh, xl = split(x)
    ch, cl = split(c)
    xc = mm(xh, ch) + (mm(xh, cl) + mm(xl, ch))
    return (jnp.sum(x * x, axis=1)[:, None] - 2.0 * xc
            + jnp.sum(c * c, axis=1)[None, :])


def control_jobs(config, replay):
    """(early, full) job functions of the control, in the harness's form:
    the configuration's reference ``replay`` in float32 at bf16x3."""
    fits = {}

    def both(x, init_seed):
        if init_seed not in fits:
            fits[init_seed] = replay(x.astype(np.float32), init_seed, config,
                                     dist=distances_bf16x3)
        return fits[init_seed]

    def job(x, init_seed):
        return tuple(both(x, init_seed)[0])

    def full(x, init_seed):
        return tuple(both(x, init_seed)[1])

    return job, full


@contextlib.contextmanager
def planted(fault: str | None):
    """Plant ``fault`` in the program's k-means sweep for the block."""
    import jax
    from repro.core import kmeans as km
    saved = (km.update_centroids, km.assign_and_stats)
    orig = km.assign_and_stats

    def half_batch(x, centroids, axis_name=None, use_kernel=False,
                   mask=None, kernel_backend=None):
        labels = orig(x, centroids, axis_name, use_kernel, mask,
                      kernel_backend)[0]
        m = x.shape[0] // 2
        _, sums, counts, j = orig(x[:m], centroids, axis_name, use_kernel,
                                  None if mask is None else mask[:m],
                                  kernel_backend)
        return labels, sums, counts, j

    def altered_answer(x, centroids, axis_name=None, use_kernel=False,
                       mask=None, kernel_backend=None):
        labels, sums, counts, j = orig(x, centroids, axis_name, use_kernel,
                                       mask, kernel_backend)
        labels = labels.at[0].set((labels[0] + 1) % centroids.shape[0])
        return labels, sums, counts, j

    if fault == "state_unchanged":
        km.update_centroids = lambda centroids, sums, counts: centroids
    elif fault == "half_batch":
        km.assign_and_stats = half_batch
    elif fault == "altered_answer":
        km.assign_and_stats = altered_answer
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        km.update_centroids, km.assign_and_stats = saved
        jax.clear_caches()


FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def readings(s, modes, jobs, out=None):
    """Worst of each compared number over ``jobs``, per mode."""
    from bench import reference
    from bench.harness import to_fit
    replays = {}
    summary = {}
    for mode in modes:
        if mode == "control":
            job, full = control_jobs(s.config, s.replay)
            ctx = contextlib.nullcontext()
        else:
            job, full = s.job, s.full
            ctx = planted(None if mode == "program" else mode)
        t0 = time.perf_counter()
        worst, rands, per_job = {}, [], []
        with ctx:
            for j in jobs:
                x = s.pool.jobs[j]
                got = (to_fit(job(x, j)), to_fit(full(x, j)))
                if j not in replays:
                    replays[j] = s.replay(x.astype(np.float64), j, s.config)
                row = {"job": j, "iters": [got[0].n_iters, got[1].n_iters],
                       "ref_iters": [replays[j][0].n_iters,
                                     replays[j][1].n_iters]}
                for which, g, r in (("early", got[0], replays[j][0]),
                                    ("full", got[1], replays[j][1])):
                    for name, v in reference.compare(
                            x.astype(np.float64), g, r,
                            which == "full").items():
                        row[f"{name}.{which}"] = v
                        worst[name] = max(worst.get(name, 0), v)
                rand = reference.rand_index(got[0].labels, got[1].labels,
                                            s.config["k"])
                row["rand_vs_full"] = rand
                rands.append(rand)
                per_job.append(row)
        summary[mode] = dict(worst, rand_vs_full_mean=float(np.mean(rands)),
                             rand_vs_full_min=float(np.min(rands)),
                             jobs=len(jobs),
                             seconds=time.perf_counter() - t0)
        print(json.dumps({"mode": mode, **summary[mode]}), flush=True)
        if out is not None:
            with open(out, "a") as f:
                for row in per_job:
                    f.write(json.dumps({"cell": s.name, "mode": mode,
                                        **row}) + "\n")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--modes", nargs="+",
                    default=["program", "control", *FAULTS])
    ap.add_argument("--jobs", type=int, default=None,
                    help="read the first N jobs of the pool (default all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.spec import Spec
    try:
        s = harness.setup(Spec(ROOT, BENCH), args.workload)
    except harness.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    n = len(s.pool.jobs) if args.jobs is None else args.jobs
    print(json.dumps({"cell": args.workload, "h_star": s.config["h_star"],
                      "family": s.model.regression.family,
                      "device": s.device}), flush=True)
    readings(s, args.modes, list(range(n)), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
