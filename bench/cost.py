"""Bytes and operations one clustering job needs, from its shapes alone.

A job reads its points once per pass: K k-means++ passes (the first
point's distances, then one per further centroid), one pass per Lloyd
sweep, and the final labels pass; it writes N int32 labels.  That is the
least traffic any implementation of the algorithm moves, padded or not,
fused or not, so the count does not change with what implements a sweep.
"""
from __future__ import annotations


def passes(k: int, n_iters: int) -> int:
    """Passes over the points: K seeding passes, the sweeps, the labels."""
    return k + int(n_iters) + 1


def job_bytes(n: int, d: int, k: int, n_iters: int) -> int:
    """HBM bytes the job needs: every pass reads N x D float32 points, and
    the labels are written once."""
    return passes(k, n_iters) * n * d * 4 + n * 4


def job_flops(n: int, d: int, k: int, n_iters: int) -> int:
    """Operations the job needs: a seeding pass takes N x D differences,
    squares and adds; a sweep or labels pass takes the [N, D] x [D, K]
    product (2 N D K), adds the norms and takes the minimum (3 N K)."""
    seeding = k * 3 * n * d
    sweeps = (int(n_iters) + 1) * (2 * n * d * k + 3 * n * k)
    return seeding + sweeps


def roofline_s(bytes_: float, flops: float, peak: dict) -> tuple[float, str]:
    """(least seconds the chip could take, the bound that sets it)."""
    t_mem = bytes_ / float(peak["hbm_bytes_per_s"])
    t_flop = flops / float(peak["flops_per_s"])
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
