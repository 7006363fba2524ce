"""Request batching for the cluster assignment server.

The assignment server holds its batch shape fixed with *buckets*: drained
request batches are packed greedily (arrival order) up to the largest
bucket, padded to the smallest bucket that holds them
(``kernels.layout.bucket_for``), and the padding rows are absorbed by the
ops' mask operand.  XLA therefore
compiles one program per (model, bucket) — the recompile-count claim
``BENCH_serve_cluster.json`` tracks.

Admission is strict: a malformed request raises ``ValueError`` naming the
offender *before* any device work — empty batches, wrong feature width,
unknown model keys and batches larger than the largest bucket never enter
the queue.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np


@dataclasses.dataclass
class AssignRequest:
    """Label ``x`` [n, D] under a registered model: the high-traffic path."""
    x: Any
    model_key: str
    rid: int = 0


@dataclasses.dataclass
class FitRequest:
    """Small incremental fit: advance the registered model's parameters on
    a fresh batch (the artifact's own engine regime, its own h* stop)."""
    x: Any
    model_key: str
    rid: int = 0


def pack_batches(requests, max_rows: int):
    """Greedily pack requests into groups of ≤ ``max_rows`` total rows,
    preserving arrival order (a served batch never reorders the queue)."""
    groups: list[list] = []
    cur: list = []
    cur_rows = 0
    for r in requests:
        n = int(np.shape(r.x)[0])
        if cur and cur_rows + n > max_rows:
            groups.append(cur)
            cur, cur_rows = [], 0
        cur.append(r)
        cur_rows += n
    if cur:
        groups.append(cur)
    return groups


class ServeMetrics:
    """Per-model latency/throughput accounting (the D-SPACE4Cloud-style
    capacity numbers a cost planner consumes — see PAPERS.md)."""

    def __init__(self):
        self._lat: dict[str, list[float]] = {}
        self._points: dict[str, int] = {}
        self._requests: dict[str, int] = {}

    def record(self, key: str, latency_s: float, points: int,
               requests: int) -> None:
        self._lat.setdefault(key, []).append(latency_s)
        self._points[key] = self._points.get(key, 0) + points
        self._requests[key] = self._requests.get(key, 0) + requests

    def summary(self) -> dict[str, dict]:
        out = {}
        for key, lats in self._lat.items():
            arr = np.asarray(lats, np.float64)
            wall = float(arr.sum())
            out[key] = {
                "batches": int(arr.size),
                "requests": self._requests[key],
                "points": self._points[key],
                "p50_latency_ms": float(np.percentile(arr, 50) * 1e3),
                "p99_latency_ms": float(np.percentile(arr, 99) * 1e3),
                "throughput_points_per_s":
                    self._points[key] / wall if wall > 0 else float("inf"),
                "qps":
                    self._requests[key] / wall if wall > 0 else float("inf"),
            }
        return out
