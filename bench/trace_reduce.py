"""Reduce a profiler trace of the benchmark's window to device busy time,
idle share, the top device operations and the idle gaps by host span.

The benchmark wraps its traced jobs in a host span ``bench.window`` and
each job in ``bench.job`` (``jax.profiler.TraceAnnotation``).  On the
device planes (``/device:TPU:<i>``) the line ``XLA Ops`` holds one event
per operation run.  Busy time is the union of those intervals inside the
window, averaged over the devices; the idle share is 1 - busy / window.
Each idle gap is put down to what the host was doing at its midpoint: in a
job (the program's own phases are not annotated yet) or between jobs.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
JOB = "bench.job"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def union(intervals):
    """Sorted, disjoint [start, end) intervals covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi) between the disjoint ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def reduce_events(window, jobs, device_ops):
    """The reduction on plain numbers (nanoseconds).

    window: (start, end) of the traced window.
    jobs: [(start, end)] host spans of the jobs.
    device_ops: {device: [(name, start, end)]} operations per device.
    """
    lo, hi = window
    window_ns = hi - lo
    if window_ns <= 0 or not device_ops:
        raise ValueError("no traced window or no device operations")
    busy_ns, op_ns, gap_ns = [], {}, {}
    jobs = sorted(jobs)
    for ops in device_ops.values():
        iv = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns.append(sum(e - s for s, e in iv))
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0) + d / len(device_ops)
        for s, e in gaps(iv, lo, hi):
            mid = (s + e) / 2
            inside = any(js <= mid < je for js, je in jobs)
            label = (f"{JOB} (unannotated inside the program)" if inside
                     else f"{WINDOW} (between jobs)")
            gap_ns[label] = gap_ns.get(label, 0) + (e - s) / len(device_ops)
    busy = sum(busy_ns) / len(busy_ns)
    if busy <= 0:
        raise ValueError("no device operation ran inside the traced window")

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": busy / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy / window_ns,
        "jobs_traced": sum(1 for s, e in jobs if s >= lo and e <= hi),
        "device_ops": top(op_ns),
        "idle_gaps": top(gap_ns),
    }


def read_events(pd):
    """(window, jobs, device_ops) from ``jax.profiler.ProfileData``."""
    window, jobs, device_ops = None, [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns, ev.end_ns)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name == JOB:
                        jobs.append((ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return window, jobs, {k: v for k, v in device_ops.items() if v}


def trace_file(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_events(*read_events(ProfileData.from_file(path)))
