"""Public GMM E-step op, dispatched through the backend registry.

Same dispatch surface as ``kmeans_assign.ops`` (see that module header):
``tpu``/``gpu`` compile the Pallas kernel, ``interpret`` runs it under the
interpreter (CPU CI), ``xla`` is the pure-jnp reference contract; the
Pallas backends pre-compute the matmul-decomposition operands and pad per
``layout.TilePolicy``.  A ``custom_vmap`` rule maps ``jax.vmap`` (the
engine's multi-restart driver) onto the kernel grid's restart axis, and
``mask`` is an optional [N] f32 row-weight vector (0 drops the row and
labels it -1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune, dispatch, layout

from .kernel import gmm_estep_kernel

_LOG2PI = 1.8378770664093453
_NEG = -1.0e30

OP = dispatch.get_op("gmm_estep")


# --------------------------------------------------------------------------
# Backend implementations.  Shared internal contract:
#   impl(x, w, means, var, log_w, *, block_n)
#     -> (labels, loglik, r_sum, r_x, r_x2)
# with x [N, D] | [R, N, D], w [N] | [R, N], params [K, ...] | [R, K, ...];
# outputs carry the leading R iff the parameters do.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n", "backend"))
def _pallas_impl(x, w, means, var, log_w, *, block_n: int, backend: str):
    pol = layout.tile_policy(backend)
    batched = means.ndim == 3
    mu = means if batched else means[None]
    vr = var if batched else var[None]
    lw = log_w if batched else log_w[None]
    x3 = x if x.ndim == 3 else x[None]
    w2 = w if w.ndim == 2 else w[None]
    if mu.ndim != 3 or x3.ndim != 3:
        raise NotImplementedError(
            "gmm_estep supports one leading restart axis at most; "
            f"got x {x.shape}, means {means.shape}")
    n, d = x3.shape[1:]
    k = mu.shape[1]
    inv_var = 1.0 / vr
    b_op = (mu * inv_var).astype(jnp.float32)          # b operand: μ/σ²
    const = (lw - 0.5 * (jnp.sum(mu ** 2 * inv_var, axis=-1)
                         + jnp.sum(jnp.log(vr), axis=-1)
                         + d * _LOG2PI)).astype(jnp.float32)
    n_pad = layout.round_up(n, block_n)
    d_pad = pol.align_d(d)
    k_pad = pol.align_k(k)
    xp = jnp.pad(x3.astype(jnp.float32),
                 ((0, 0), (0, n_pad - n), (0, d_pad - d)))
    wp = jnp.pad(w2.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
    ap = jnp.pad(inv_var.astype(jnp.float32),
                 ((0, 0), (0, k_pad - k), (0, d_pad - d)))
    bp = jnp.pad(b_op, ((0, 0), (0, k_pad - k), (0, d_pad - d)))
    cp = jnp.pad(const, ((0, 0), (0, k_pad - k)), constant_values=_NEG)
    if backend == "gpu":   # parallel grid cells: split reduction
        labels, loglik, r_sum, r_x, r_x2 = gmm_estep_kernel(
            xp, wp, ap, bp, cp, block_n=block_n, interpret=False,
            accumulate=False)
        loglik, r_sum, r_x, r_x2 = (jnp.sum(loglik, axis=1),
                                    jnp.sum(r_sum, axis=1),
                                    jnp.sum(r_x, axis=1),
                                    jnp.sum(r_x2, axis=1))
    else:
        labels, loglik, r_sum, r_x, r_x2 = gmm_estep_kernel(
            xp, wp, ap, bp, cp, block_n=block_n,
            interpret=(backend == "interpret"))
    labels, loglik = labels[:, :n], loglik[:, 0]
    r_sum, r_x, r_x2 = r_sum[:, :k], r_x[:, :k, :d], r_x2[:, :k, :d]
    if not batched:
        labels, loglik = labels[0], loglik[0]
        r_sum, r_x, r_x2 = r_sum[0], r_x[0], r_x2[0]
    return labels, loglik, r_sum, r_x, r_x2


for _b in dispatch.PALLAS_BACKENDS:
    OP.register(_b)(functools.partial(_pallas_impl, backend=_b))


@OP.register("xla")
@functools.partial(jax.jit, static_argnames=("block_n",))
def _xla_impl(x, w, means, var, log_w, *, block_n: int):
    # delegates to the ref oracle (one copy of the math — see ref.py)
    del block_n
    from .ref import gmm_estep_masked_ref
    if means.ndim == 2:
        return gmm_estep_masked_ref(x, w, means, var, log_w)
    return jax.vmap(gmm_estep_masked_ref,
                    in_axes=(0 if x.ndim == 3 else None,
                             0 if w.ndim == 2 else None,
                             0, 0, 0))(x, w, means, var, log_w)


# --------------------------------------------------------------------------
# Public op (+ the custom_vmap restart-axis rule)
# --------------------------------------------------------------------------

# (block_n, backend) → custom_vmap-wrapped call; the restart-axis batching
# rule lives in dispatch.make_dispatched_factory (shared with kmeans_assign)
_dispatched = dispatch.make_dispatched_factory(OP, n_out=5)


def gmm_estep(x, means, var, log_w, *, mask=None, block_n: int | None = None,
              backend: str | None = None, interpret: bool | None = None):
    """Fused E-step: (labels, loglik [], r_sum [K], r_x [K,D], r_x2 [K,D]).

    Accepts a leading restart axis on the parameters (and ``x``/``mask``)
    and composes with ``jax.vmap``; see the module docstring.

    Block resolution mirrors ``kmeans_assign``: explicit ``block_n`` >
    active autotune cache (``kernels.autotune.tuning`` scope) >
    ``TilePolicy`` default — always ``block_for``-aligned.
    """
    b, _ = OP.impl(backend, interpret)       # unregistered names fail here
    pol = layout.tile_policy(b)
    n = x.shape[-2]
    if block_n is None:
        tuned = autotune.tuned_blocks(
            "gmm_estep", b, n=n, k=means.shape[-2], d=x.shape[-1])
        if tuned:
            block_n = tuned.get("block_n")
    bn = pol.block_for(n, block_n)
    w = (jnp.ones(x.shape[:-1], jnp.float32) if mask is None
         else jnp.asarray(mask, jnp.float32))
    return _dispatched(bn, b)(x, w, means, var, log_w)


def gmm_estep_chunked(x, means, var, log_w, *, chunks: int = 1, mask=None,
                      block_n: int | None = None,
                      backend: str | None = None,
                      interpret: bool | None = None):
    """Streaming entry point for the fused E-step (engine ``chunks`` mode).

    Statically slices N via the shared chunked-call driver
    (``layout.chunked_sweep``), runs the dispatched op per slice,
    accumulates the additive sufficient statistics.  Same contract as
    ``gmm_estep``.
    """
    n = x.shape[-2]
    if chunks <= 1 or n <= 1:
        return gmm_estep(x, means, var, log_w, mask=mask, block_n=block_n,
                         backend=backend, interpret=interpret)

    def call(a, b):
        return gmm_estep(
            x[..., a:b, :], means, var, log_w,
            mask=None if mask is None else mask[..., a:b],
            block_n=block_n, backend=backend, interpret=interpret)

    return layout.chunked_sweep(call, n, chunks)
