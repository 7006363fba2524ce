"""Idle gaps named by the program's own spans, and the spans per job.

A stopgap, to be deleted: ``read_spans`` and ``label`` belong in
``trace_reduce``, so that ``breakdown.idle_gaps`` names the program's spans,
and ``harness.run`` should put the window's ``repro.spans.totals()`` in
``ctx`` for the readers; both files are the benchmark's own and stay as
they are until a ``benchmark`` PR folds this file into them.

``trace_reduce`` puts every idle gap inside a job under one label.  The
program opens host spans at its layer boundaries (``repro.spans.NAMES``,
``jax.profiler.TraceAnnotation``), on the clock of the device's operations,
and JAX writes its own lowering and compile events beside them.  Here a gap
inside a job is labelled with the innermost program span at its midpoint,
with `` (lowering)`` added where a JAX lowering or compile event covers the
midpoint too; a gap under no program span keeps ``trace_reduce``'s label.

``traced(ctx)`` sums the program's spans over the traced jobs for the
per-layer metrics that read them.  It reads the newest trace under
``bench_out/trace`` and refuses one whose window or job count is not this
run's; a program without ``repro.spans`` opens no spans, and then it
returns None.

    PYTHONPATH=src python3 -m bench.span_reduce <trace dir or .xplane.pb>

prints the named idle gaps and the job sums of a trace.
"""
from __future__ import annotations

import functools
import json
import os
import sys

from bench import trace_reduce as tr
from bench.spec import ROOT

TRACES = ROOT / "bench_out" / "trace"
JAX_LOWERING = ("lower_sharding_computation", "backend_compile",
                "backend_compile_and_load")
UNANNOTATED = f"{tr.JOB} (unannotated inside the program)"
BETWEEN = f"{tr.WINDOW} (between jobs)"


def program_names() -> tuple:
    try:
        from repro import spans
    except ImportError:
        return ()
    return spans.NAMES


def _host_events(pd, names):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from (ev for ev in line.events if ev.name in names)


def read_spans(pd):
    """[(name, start_ns, end_ns)] of the program's spans and of JAX's
    lowering and compile events, from ``jax.profiler.ProfileData``."""
    names = set(program_names()) | set(JAX_LOWERING)
    return [(ev.name, ev.start_ns, ev.end_ns)
            for ev in _host_events(pd, names)]


def label(mid, jobs, spans) -> str:
    """What the host was doing at ``mid``: the innermost program span,
    ``(lowering)`` where JAX was lowering or compiling under it."""
    if not any(s <= mid < e for s, e in jobs):
        return BETWEEN
    inner, lowering = None, False
    for name, s, e in spans:
        if s <= mid < e:
            if name in JAX_LOWERING:
                lowering = True
            elif inner is None or s > inner[1]:
                inner = (name, s)
    if inner is None:
        return UNANNOTATED
    return inner[0] + (" (lowering)" if lowering else "")


def idle_gaps(window, jobs, device_ops, spans) -> dict:
    """{label: seconds} of the window's idle time, per device on average,
    as ``trace_reduce.reduce_events`` splits it, labelled by ``spans``."""
    lo, hi = window
    out = {}
    for ops in device_ops.values():
        busy = tr.union(tr.clip([(s, e) for _, s, e in ops], lo, hi))
        for s, e in tr.gaps(busy, lo, hi):
            name = label((s + e) / 2, jobs, spans)
            out[name] = out.get(name, 0.0) + (e - s) / len(device_ops) / 1e9
    return out


def job_sums(pd) -> dict:
    """Sums over the ``entry.job`` spans inside the traced window: ``jobs``,
    the seconds of ``entry.job``, ``entry.wait`` and ``entry.seed``, and
    ``seed_lowerings``, the programs lowered under ``entry.seed`` and the
    spans inside it (the ``lowerings`` the program writes on a span);
    ``window_s`` is the window's length."""
    (lo, hi), _, _ = tr.read_events(pd)
    events = [ev for ev in _host_events(pd, set(program_names()))
              if lo <= ev.start_ns and ev.end_ns <= hi]
    sums = dict(window_s=(hi - lo) / 1e9, jobs=0, job_s=0.0, wait_s=0.0,
                seed_s=0.0, seed_lowerings=0)
    seeds = []
    for ev in events:
        if ev.name == "entry.job":
            sums["jobs"] += 1
            sums["job_s"] += ev.duration_ns / 1e9
        elif ev.name == "entry.wait":
            sums["wait_s"] += ev.duration_ns / 1e9
        elif ev.name == "entry.seed":
            sums["seed_s"] += ev.duration_ns / 1e9
            seeds.append((ev.start_ns, ev.end_ns))
    for ev in events:
        if any(s <= ev.start_ns and ev.end_ns <= e for s, e in seeds):
            sums["seed_lowerings"] += int(dict(ev.stats).get("lowerings", 0))
    return sums


@functools.lru_cache(maxsize=1)
def _job_sums_of(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData
    return job_sums(ProfileData.from_file(path))


def traced(ctx) -> dict | None:
    """``job_sums`` of this run's trace; None in an untraced run or where
    the program opened no ``entry.job`` span.  Raises where the newest
    trace is not this run's: another window length than ``ctx["trace"]``
    or another number of jobs than ``ctx["traced_iters"]``."""
    if ctx["trace"] is None or not program_names():
        return None
    path = tr.trace_file(str(TRACES))
    sums = _job_sums_of(path, os.path.getmtime(path))
    if not sums["jobs"]:
        return None
    if (sums["window_s"] != ctx["trace"]["window_s"]
            or sums["jobs"] != len(ctx["traced_iters"])):
        raise ValueError(
            f"{path} is not this run's trace: window {sums['window_s']} s "
            f"and {sums['jobs']} jobs, against {ctx['trace']['window_s']} s "
            f"and {len(ctx['traced_iters'])}")
    return sums


def main(argv) -> None:
    from jax.profiler import ProfileData
    path = argv[0]
    if not path.endswith(".xplane.pb"):
        path = tr.trace_file(path)
    pd = ProfileData.from_file(path)
    gaps = idle_gaps(*tr.read_events(pd), read_spans(pd))
    gaps = sorted(gaps.items(), key=lambda kv: -kv[1])
    print(json.dumps({"trace": path, "job_sums": job_sums(pd),
                      "idle_gaps": gaps}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
