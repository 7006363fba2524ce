"""Fused k-means assignment Pallas kernel (TPU compiled / Triton on GPU /
interpreter elsewhere — ``ops.py`` dispatches via ``kernels.dispatch``).

One pass over the points produces labels, per-cluster sums/counts and the
objective J.  The unfused baseline reads X three times (assign, accumulate,
objective); fusing gives arithmetic intensity ≈ 2K FLOP/byte on the distance
matmul plus the one-hot accumulation matmul — both MXU work.

Grid: ``(R, N // block_n)`` — a leading **restart axis** so vmapped
multi-restart programs map onto the grid instead of needing a pallas-level
batching rule (``ops.py`` installs a ``custom_vmap`` that routes here).
R = 1 recovers the single-restart sweep.  The points (and their row-weight
mask) may be shared across restarts (index map pins their restart block to
0) or per-restart (minibatch draws differ per restart).

Row validity is a **mask operand** ``w`` (f32 row weights; 0 = padding),
replacing the old static ``n_valid`` — the same kernel now serves flat
sweeps, the engine's padded ``[C, P, D]`` chunk layout, and dynamically
drawn minibatch chunks without recompiling per remainder.

Accumulation: TPU grids execute sequentially with the last axis innermost,
so for ``accumulate=True`` the reduction outputs use a constant (per-r)
index map and every N-step accumulates into the same VMEM block, re-zeroed
at step 0 of each restart.  GPU (Triton) grid cells are parallel CTAs, so
``accumulate=False`` instead writes per-step partials ``[R, S, ...]`` that
the wrapper reduces with one ``jnp.sum`` — the standard split reduction.

Shapes are pre-padded by ops.py per the backend's ``layout.TilePolicy``;
padded centroid rows are +1e9 so no point selects them; padded point rows
carry weight 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dot(a, b):
    """f32 MXU matmul (Mosaic's default contraction precision for f32
    operands is not guaranteed to be f32)."""
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _kernel(x_ref, w_ref, c_ref, labels_ref, sums_ref, counts_ref, j_ref,
            *, accumulate: bool):
    step = pl.program_id(1)

    if accumulate:
        @pl.when(step == 0)
        def _init():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            counts_ref[...] = jnp.zeros_like(counts_ref)
            j_ref[...] = jnp.zeros_like(j_ref)

    # Every value stays 2-D: per-point quantities are [1, T] rows with the
    # points on the lanes, per-cluster ones are [K, 1] columns (Mosaic has
    # no lowering for reducing a 1-D lane vector to a scalar).
    x = x_ref[0].astype(jnp.float32)              # [T, D]
    w = w_ref[0].astype(jnp.float32)              # [1, T]
    c = c_ref[0].astype(jnp.float32)              # [K, D]
    k = c.shape[0]
    xt = x.T                                      # [D, T]

    x2 = jnp.sum(xt * xt, axis=0, keepdims=True)                 # [1, T]
    c2 = jnp.sum(c * c, axis=1, keepdims=True)                   # [K, 1]
    d2 = x2 - 2.0 * _dot(c, xt)                                  # MXU matmul
    d2 = d2 + c2                                                 # [K, T]

    # argmin over K as the first row attaining the minimum
    mind2 = jnp.min(d2, axis=0, keepdims=True)                   # [1, T]
    rows = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    labels = jnp.min(jnp.where(d2 == mind2, rows, k), axis=0,
                     keepdims=True)                              # [1, T]
    valid = w > 0.0

    labels_ref[0] = jnp.where(valid, labels, -1)
    onehot = (rows == labels).astype(jnp.float32) * w            # [K, T]
    j_blk = jnp.sum(jnp.maximum(mind2, 0.0) * w, axis=1,
                    keepdims=True)                               # [1, 1]
    sums_blk = _dot(onehot, x)                                   # [K, D] MXU
    counts_blk = jnp.sum(onehot, axis=1, keepdims=True)          # [K, 1]
    if accumulate:
        j_ref[0] += j_blk
        sums_ref[0] += sums_blk
        counts_ref[0] += counts_blk
    else:                                        # per-step partials (GPU)
        j_ref[0, 0] = j_blk
        sums_ref[0, 0] = sums_blk
        counts_ref[0, 0] = counts_blk


def kmeans_assign_kernel(x, w, centroids, *, block_n: int = 1024,
                         interpret: bool = False, accumulate: bool = True):
    """Padded inputs → fused stats over a (restarts, row-blocks) grid.

    x [Rx, Npad, Dpad] (Rx ∈ {1, R}: shared or per-restart points),
    w [Rw, Npad] row weights, centroids [R, Kpad, Dpad].  Returns
    (labels [R, Npad] i32, sums, counts, j) — reduction outputs are
    [R, ...] when ``accumulate`` else per-step partials [R, S, ...] for the
    wrapper to sum (parallel-grid backends).

    Inside the call every per-restart operand and output carries a
    singleton axis ([R, 1, Npad] labels and weights, [R, Kpad, 1] counts,
    [R, 1, 1] J), so each block's last two dims equal the array's and the
    restart grid is legal for any R under the TPU block-shape rule.
    """
    rx, n, d = x.shape
    rw = w.shape[0]
    r, k, _ = centroids.shape
    assert n % block_n == 0, (n, block_n)
    assert rx in (1, r) and rw in (1, r), (rx, rw, r)
    s = n // block_n
    grid = (r, s)
    xi = (lambda ri, i: (ri, i, 0)) if rx == r and r > 1 \
        else (lambda ri, i: (0, i, 0))
    wi = (lambda ri, i: (ri, 0, i)) if rw == r and r > 1 \
        else (lambda ri, i: (0, 0, i))
    if accumulate:
        red_specs = [
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),   # sums
            pl.BlockSpec((1, k, 1), lambda ri, i: (ri, 0, 0)),   # counts
            pl.BlockSpec((1, 1, 1), lambda ri, i: (ri, 0, 0)),   # J
        ]
        red_shapes = [(r, k, d), (r, k, 1), (r, 1, 1)]
    else:
        red_specs = [
            pl.BlockSpec((1, 1, k, d), lambda ri, i: (ri, i, 0, 0)),
            pl.BlockSpec((1, 1, k, 1), lambda ri, i: (ri, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda ri, i: (ri, i, 0, 0)),
        ]
        red_shapes = [(r, s, k, d), (r, s, k, 1), (r, s, 1, 1)]
    labels, sums, counts, j = pl.pallas_call(
        functools.partial(_kernel, accumulate=accumulate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d), xi),              # points tile
            pl.BlockSpec((1, 1, block_n), wi),              # row weights
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),  # centroids
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_n), lambda ri, i: (ri, 0, i)),  # labels
            *red_specs,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, n), jnp.int32),
            *(jax.ShapeDtypeStruct(sh, jnp.float32) for sh in red_shapes),
        ],
        interpret=interpret,
    )(x, w[:, None, :], centroids)
    return labels[:, 0], sums, counts[..., 0], j[..., 0]
