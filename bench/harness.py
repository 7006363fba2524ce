"""One run of one cell: set-up, the measured window, the output check.

    set-up   the pool of jobs (``traffic.py``), the stop model fitted with
             ``repro.launch.cluster.fit_stop_model`` (timed as ``train_s``),
             one warm-up job: every program the window runs is compiled.
    window   passes over the pool in the seed's order, each job one call
             of ``run_production`` from the host array to labels ready,
             until ``seconds`` have passed and the pass in flight is done:
             every seed runs the same jobs, in another order.  ``fit_s`` is
             the window's wall time over its jobs.
             With ``trace`` the first ``TRACE_S`` seconds are traced.
    check    after the window and the memory reading: the full-convergence
             run of the first ``rstar_jobs`` jobs, the Rand index of each
             early stop against it (held to the configuration's r*), and
             the configuration's float64 replay (``references/``) of a
             seeded sample of them, compared with what the program
             returned.
"""
from __future__ import annotations

import json
import sys
import time
from typing import NamedTuple

import numpy as np

from bench import reference, trace_reduce, traffic
from bench.spec import Spec, quantity

TRACE_S = 2.0
# the numbers read against the replay (``reference.compare``); a cell holds
# those that its limits file gives a limit, and reports the others
NUMBERS = ("iters_gap", "label_gap", "label_excess", "objective_gap",
           "fixed_point_gap")
# a lowering is a program the jit cache missed: each one compiles or
# loads from the persistent cache
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Refused(Exception):
    """The run cannot measure what it was asked to: no result is printed."""


class CompileCounter:
    """Programs lowered while ``active`` (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event: str, duration: float, **_):
        del duration
        if self.active and event == _LOWERING:
            self.count += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform!r} "
                      f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found "
                      f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``), keeping every program however
    fast it compiled, so that a warm run compiles nothing."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def profile_options():
    """Device operations and the benchmark's own host spans only: no
    Python function tracer (hundreds of thousands of events a second on a
    host-bound job, which slow the host it is measuring) and no HLO
    protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# the regime keys the full-convergence run takes, as ``cluster.pipeline``
# makes it (the other keys shape the early-stopped production run only)
FULL_REGIME = ("chunks", "use_kernel", "kernel_backend")


def regime_argv(regime: dict) -> list[str]:
    """``repro.launch.cluster``'s flags for a configuration's ``regime``."""
    argv = []
    for key, value in regime.items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            argv.append(flag)
        elif value is not False and value is not None:
            argv += [flag, str(value)]
    return argv


def stop_model(config: dict, train):
    from repro.launch import cluster
    argv = ["--k", str(config["k"]), "--algorithm", config["algorithm"],
            "--max-iters", str(config["max_iters"]),
            "--family", config["family"],
            "--desired-accuracy", str(config["desired_accuracy"]),
            *regime_argv(config.get("regime", {}))]
    t0 = time.perf_counter()
    model, _, _ = cluster.fit_stop_model(cluster.parse_args(argv), train)
    return model, time.perf_counter() - t0


def make_job(config: dict, model):
    """The cell's job: ``run_production`` with the fitted stop model, in the
    configuration's regime; and its full-convergence run."""
    from repro.launch import cluster
    regime = config.get("regime", {})
    full_regime = {k: v for k, v in regime.items() if k in FULL_REGIME}

    def job(x, init_seed):
        labels, j, n_iters, _, params = cluster.run_production(
            x, config["k"], config["algorithm"], 0.0,
            max_iters=config["max_iters"], patience=config["patience"],
            seed=init_seed, model=model,
            desired_accuracy=config["desired_accuracy"], return_params=True,
            **regime)
        return labels, j, n_iters, params

    def full(x, init_seed):
        labels, j, n_iters, _, params = cluster.run_production(
            x, config["k"], config["algorithm"], 0.0,
            max_iters=3 * config["max_iters"], seed=init_seed,
            return_params=True, **full_regime)
        return labels, j, n_iters, params

    return job, full


def to_fit(out) -> reference.Fit:
    labels, j, n_iters, params = out
    return reference.Fit(np.asarray(labels), float(j), int(n_iters),
                         np.asarray(params, np.float64))


def held(limits: dict) -> list[str]:
    """The numbers a cell's limits file holds to a limit."""
    return [name for name in NUMBERS if name in limits]


def check_outputs(config, limits, pool, kept, full, sample, replay):
    """(the numbers compared, each with its limit and its direction; the
    worst reading of every number); ``replay`` is the configuration's
    plain reference."""
    k = config["k"]
    rands = [reference.rand_index(kept[j].labels, full[j].labels, k)
             for j in kept]
    worst = dict.fromkeys(NUMBERS, 0.0)
    for j in sample:
        x = pool.jobs[j].astype(np.float64)
        ref_early, ref_full = replay(x, j, config)
        for got, ref, is_full in ((kept[j], ref_early, False),
                                  (full[j], ref_full, True)):
            for name, v in reference.compare(x, got, ref, is_full).items():
                worst[name] = max(worst[name], v)
    checks = {name: {"value": worst[name], "limit": limits[name],
                     "pass": worst[name] <= limits[name]}
              for name in held(limits)}
    r = float(np.mean(rands))
    checks["rand_vs_full"] = {"value": r,
                              "limit": config["desired_accuracy"],
                              "pass": r >= config["desired_accuracy"]}
    return checks, rands, worst


class Setup(NamedTuple):
    """What a run builds before its window, shared by every seed."""
    name: str
    cell: dict
    config: dict        # the configuration's file, with the fitted h_star
    replay: object      # the configuration's plain reference of a job
    traffic: dict
    limits: dict
    device: dict
    peak: dict | None
    pool: traffic.Pool
    model: object
    train_s: float
    job: object         # (x, init_seed) -> (labels, J, n_iters, centroids)
    full: object        # the same, run to full convergence
    counter: CompileCounter


def setup(spec: Spec, name: str, *, require_tpu: bool = True,
          cache: bool = True) -> Setup:
    """The chip check, the compile cache, the pool and the stop model."""
    cell = spec.cell(name)
    config = spec.config(cell["config"])
    tr = spec.traffic(cell["traffic"])
    limits = json.loads((spec.bench / "limits" / f"{name}.json").read_text())
    device = device_info(int(cell["chips"]), require_tpu)
    peak = spec.peak(device["kind"]) if require_tpu else None
    if cache:
        enable_compile_cache()
    counter = CompileCounter()
    replay = spec.reference(config["reference"])
    pool = traffic.build(config, tr,
                         spec.generator(config["data"]["generator"]))
    model, train_s = stop_model(config, pool.train)
    config = dict(config, h_star=float(
        model.threshold_for(config["desired_accuracy"])))
    job, full = make_job(config, model)
    return Setup(name, cell, config, replay, tr, limits, device, peak, pool,
                 model, train_s, job, full, counter)


def window(s: Setup, seed: int, seconds: float, trace_dir=None) -> dict:
    """Warm up, then run whole passes over the pool in the seed's order
    for ``seconds``; the first ``rstar_jobs`` of the order are kept for the
    output check."""
    import jax
    n_jobs = len(s.pool.jobs)
    order = traffic.order(n_jobs, seed)
    n_keep = min(int(s.traffic["rstar_jobs"]), n_jobs)
    keep = set(int(j) for j in order[:n_keep])
    s.job(s.pool.jobs[int(order[0])], int(order[0]))       # warm-up

    kept, iters, traced_iters = {}, [], []
    span = None
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
        span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        span.__enter__()
    t0 = time.perf_counter()
    s.counter.count = 0
    s.counter.active = True
    n = 0
    while True:
        j = int(order[n % n_jobs])
        with jax.profiler.TraceAnnotation(trace_reduce.JOB):
            out = s.job(s.pool.jobs[j], j)
        iters.append(int(out[2]))
        if j in keep and j not in kept:
            kept[j] = out
        n += 1
        now = time.perf_counter()
        if span is not None:
            traced_iters.append(int(out[2]))
            if now - t0 >= min(TRACE_S, seconds):
                span.__exit__(None, None, None)
                span = None
                jax.profiler.stop_trace()
        # whole passes over the pool only, so every seed does the same work
        if now - t0 >= seconds and n % n_jobs == 0:
            break
    s.counter.active = False
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"t0": t0, "window_s": now - t0, "jobs": n, "iters": iters,
            "traced_iters": traced_iters, "kept": kept,
            "compiles": s.counter.count}


def verify(s: Setup, seed: int, kept: dict):
    """Full-convergence runs of the kept jobs, then the reference's replay
    of a sample of them drawn from the seed: (checks, Rand indices,
    sample, the worst reading of every number)."""
    full = {j: to_fit(s.full(s.pool.jobs[j], j)) for j in sorted(kept)}
    kept = {j: to_fit(v) for j, v in kept.items()}
    rng = np.random.default_rng([seed, 1])
    n_check = min(int(s.traffic["check_jobs"]), len(kept))
    sample = [int(j) for j in rng.choice(sorted(kept), n_check,
                                         replace=False)]
    checks, rands, worst = check_outputs(s.config, s.limits, s.pool, kept,
                                         full, sample, s.replay)
    return checks, rands, sample, worst


def run(spec: Spec, name: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, require_tpu: bool = True, cache: bool = True,
        trace_dir: str | None = None) -> dict:
    """One run of cell ``name``: the result line's fields."""
    s = setup(spec, name, require_tpu=require_tpu, cache=cache)
    w = window(s, seed, seconds, trace_dir if trace else None)
    import jax
    device = dict(s.device, memory_peak_bytes=memory_peak(
        jax.devices()[:s.cell["chips"]]))
    t_check = time.perf_counter()
    checks, rands, sample, worst = verify(s, seed, w.pop("kept"))

    ctx = dict(config=s.config, peak=s.peak,
               iters=w["iters"], traced_iters=w["traced_iters"],
               window_compiles=w["compiles"], train_s=s.train_s,
               rands=rands, trace=None, shape=s.pool.jobs[0].shape)
    result = {"correct": all(c["pass"] for c in checks.values()),
              "attempted": w["jobs"], "failed": 0}
    if trace:
        if trace_dir is not None:
            red = trace_reduce.reduce_file(trace_reduce.trace_file(trace_dir))
            ctx["trace"] = red
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        metrics = {}
        for m in spec.metrics(name, "per_layer"):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"fit_s": w["window_s"] / w["jobs"],
                  "setup_s": w["t0"] - t_start}
        metrics = {m["name"]: {"value": values[quantity(m["name"])],
                               "unit": m["unit"]}
                   for m in spec.metrics(name, "end_to_end")}
    result.update(metrics=metrics, device=device)
    result["notes"] = {"h_star": s.config["h_star"],
                       "family": s.model.regression.family,
                       "jobs_in_pool": len(s.pool.jobs),
                       "window_s": w["window_s"], "train_s": s.train_s,
                       "check_s": time.perf_counter() - t_check,
                       "checked_jobs": sample,
                       "unheld": {k: v for k, v in worst.items()
                                  if k not in checks}}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    result["_pass"] = {k: c["pass"] for k, c in checks.items()}
    return result


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    passed = result.pop("_pass")
    for name, c in result["checks"].items():
        rule = ">=" if name == "rand_vs_full" else "<="
        verdict = "ok" if passed[name] else "FAIL"
        print(f"check {name} {c['value']!r} {rule} {c['limit']!r} {verdict}",
              file=err)
    print(json.dumps(result), file=out, flush=True)
