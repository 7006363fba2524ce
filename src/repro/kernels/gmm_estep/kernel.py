"""Fused diagonal-GMM E-step Pallas kernel (TPU compiled / Triton on GPU /
interpreter elsewhere — ``ops.py`` dispatches via ``kernels.dispatch``).

Per tile of points, computes component log-densities via the matmul
decomposition  lp = const_k − 0.5·x²·(1/σ²)ᵀ + x·(μ/σ²)ᵀ,  then log-sum-exp,
responsibilities, labels, and ALL M-step sufficient statistics (Σr, Σr·x,
Σr·x²) — one HBM read of the points per EM iteration instead of four.

Grid: ``(R, N // block_n)`` with a leading restart axis (see the
kmeans_assign kernel header; same contract: points/weights shared or
per-restart, parameters per-restart, R = 1 for single fits).  Row validity
is the ``w`` mask operand.  ``accumulate=False`` writes per-step partials
for parallel-grid (GPU) backends; the wrapper sums them.

ops.py pre-computes the [R,K,D] operand matrices and the per-component
constant (log w − ½(Σμ²/σ² + Σlog σ² + D·log 2π)), and pads per the
backend's ``layout.TilePolicy``:
  D → lane multiple with inv_var = 0 (padded dims contribute nothing),
  K → sublane multiple with const = −1e30 (zero responsibility),
  N → ×block_n with weight 0.
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


def _kernel(x_ref, w_ref, a_ref, b_ref, const_ref,
            labels_ref, loglik_ref, rsum_ref, rx_ref, rx2_ref,
            *, accumulate: bool):
    step = pl.program_id(1)

    if accumulate:
        @pl.when(step == 0)
        def _init():
            loglik_ref[...] = jnp.zeros_like(loglik_ref)
            rsum_ref[...] = jnp.zeros_like(rsum_ref)
            rx_ref[...] = jnp.zeros_like(rx_ref)
            rx2_ref[...] = jnp.zeros_like(rx2_ref)

    # 2-D throughout, as in the kmeans_assign kernel: per-point rows
    # [1, T] with the points on the lanes, per-component columns [K, 1].
    x = x_ref[0].astype(jnp.float32)          # [T, D]
    w = w_ref[0].astype(jnp.float32)          # [1, T]
    a = a_ref[0]                              # [K, D] = 1/σ²
    b = b_ref[0]                              # [K, D] = μ/σ²
    const = const_ref[0]                      # [K, 1]
    k = a.shape[0]

    xx = x * x
    xt = x.T                                  # [D, T]
    lp = (const - 0.5 * _dot(a, xx.T) + _dot(b, xt))        # [K, T]

    m = jnp.max(lp, axis=0, keepdims=True)                   # online-safe LSE
    e = jnp.exp(lp - m)
    s = jnp.sum(e, axis=0, keepdims=True)
    lse = m + jnp.log(s)                                     # [1, T]
    resp = e / s                                             # [K, T]
    # argmax over K as the first row attaining the maximum
    rows = jax.lax.broadcasted_iota(jnp.int32, lp.shape, 0)
    labels = jnp.min(jnp.where(lp == m, rows, k), axis=0, keepdims=True)
    valid = w > 0.0
    respw = resp * w

    labels_ref[0] = jnp.where(valid, labels, -1)
    ll_blk = jnp.sum(lse * w, axis=1, keepdims=True)         # [1, 1]
    rsum_blk = jnp.sum(respw, axis=1, keepdims=True)         # [K, 1]
    rx_blk = _dot(respw, x)                                  # [K, D]
    rx2_blk = _dot(respw, xx)
    if accumulate:
        loglik_ref[0] += ll_blk
        rsum_ref[0] += rsum_blk
        rx_ref[0] += rx_blk
        rx2_ref[0] += rx2_blk
    else:                                    # per-step partials (GPU)
        loglik_ref[0, 0] = ll_blk
        rsum_ref[0, 0] = rsum_blk
        rx_ref[0, 0] = rx_blk
        rx2_ref[0, 0] = rx2_blk


def gmm_estep_kernel(x, w, a, b, const, *, block_n: int = 1024,
                     interpret: bool = False, accumulate: bool = True):
    """Padded operands → fused E-step stats over a (restarts, rows) grid.

    x [Rx, Npad, Dpad], w [Rw, Npad], a/b [R, Kpad, Dpad], const [R, Kpad]
    (Rx, Rw ∈ {1, R}).  Returns (labels [R, Npad], loglik, r_sum, r_x,
    r_x2) with reduction outputs [R, ...] when ``accumulate`` else
    per-step partials [R, S, ...].  Per-restart operands and outputs carry
    a singleton axis inside the call, as in ``kmeans_assign_kernel``.
    """
    rx_, n, d = x.shape
    rw = w.shape[0]
    r, k, _ = a.shape
    assert n % block_n == 0, (n, block_n)
    assert rx_ in (1, r) and rw in (1, r), (rx_, rw, r)
    s = n // block_n
    grid = (r, s)
    xi = (lambda ri, i: (ri, i, 0)) if rx_ == r and r > 1 \
        else (lambda ri, i: (0, i, 0))
    wi = (lambda ri, i: (ri, 0, i)) if rw == r and r > 1 \
        else (lambda ri, i: (0, 0, i))
    if accumulate:
        red_specs = [
            pl.BlockSpec((1, 1, 1), lambda ri, i: (ri, 0, 0)),   # loglik
            pl.BlockSpec((1, k, 1), lambda ri, i: (ri, 0, 0)),   # r_sum
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),   # r_x
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),   # r_x2
        ]
        red_shapes = [(r, 1, 1), (r, k, 1), (r, k, d), (r, k, d)]
    else:
        red_specs = [
            pl.BlockSpec((1, 1, 1, 1), lambda ri, i: (ri, i, 0, 0)),
            pl.BlockSpec((1, 1, k, 1), lambda ri, i: (ri, i, 0, 0)),
            pl.BlockSpec((1, 1, k, d), lambda ri, i: (ri, i, 0, 0)),
            pl.BlockSpec((1, 1, k, d), lambda ri, i: (ri, i, 0, 0)),
        ]
        red_shapes = [(r, s, 1, 1), (r, s, k, 1), (r, s, k, d), (r, s, k, d)]
    labels, loglik, r_sum, r_x, r_x2 = pl.pallas_call(
        functools.partial(_kernel, accumulate=accumulate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d), xi),
            pl.BlockSpec((1, 1, block_n), wi),
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),
            pl.BlockSpec((1, k, d), lambda ri, i: (ri, 0, 0)),
            pl.BlockSpec((1, k, 1), lambda ri, i: (ri, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_n), lambda ri, i: (ri, 0, i)),
            *red_specs,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, n), jnp.int32),
            *(jax.ShapeDtypeStruct(sh, jnp.float32) for sh in red_shapes),
        ],
        interpret=interpret,
    )(x, w[:, None, :], a, b, const[..., None])
    return labels[:, 0], loglik[..., 0], r_sum[..., 0], r_x, r_x2
