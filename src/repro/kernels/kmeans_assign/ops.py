"""Public k-means assignment op, dispatched through the backend registry.

``kernels.dispatch`` selects the implementation per call: ``tpu`` /
``gpu`` compile the Pallas kernel (Mosaic / Triton lowering, per-backend
``layout.TilePolicy`` padding), ``interpret`` runs the same kernel under
the Pallas interpreter (the CPU CI path), and ``xla`` is the pure-jnp
reference contract.  ``backend=None`` auto-resolves from
``jax.default_backend()``; the legacy ``interpret=`` kwarg still forces
the interpreter.

Padding policy (Pallas backends):
  D → multiple of the backend's lane alignment with zeros — distances
      unchanged;
  K → multiple of the sublane alignment with +1e9 sentinel centroids —
      never argmin;
  N → multiple of block_n — padded rows carry weight 0.

Restart axis: ``centroids`` (and optionally ``x``/``mask``) accept a
leading [R, ...] batch dimension, mapped onto the kernel grid's restart
axis; a ``jax.custom_batching.custom_vmap`` rule routes ``jax.vmap`` of
this op (the engine's multi-restart driver) onto that axis instead of
failing in the pallas batching rule.

``mask`` is an optional [N] f32 row-weight vector (0 drops a row from the
statistics and labels it -1) — the contract the engine's padded chunk
layout and minibatch draws rely on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune, dispatch, layout
from repro.kernels.layout import chunk_bounds  # noqa: F401  (historical home)

from .kernel import kmeans_assign_kernel

_PAD_CENTROID = 1.0e9

OP = dispatch.get_op("kmeans_assign")


# --------------------------------------------------------------------------
# Backend implementations.  Shared internal contract:
#   impl(x, w, c, *, block_n) -> (labels, sums, counts, j)
# with x [N, D] | [R, N, D], w [N] | [R, N], c [K, D] | [R, K, D]; outputs
# carry the leading R iff the centroids do.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n", "backend"))
def _pallas_impl(x, w, c, *, block_n: int, backend: str):
    pol = layout.tile_policy(backend)
    batched = c.ndim == 3
    c3 = c if batched else c[None]
    x3 = x if x.ndim == 3 else x[None]
    w2 = w if w.ndim == 2 else w[None]
    if c3.ndim != 3 or x3.ndim != 3:
        raise NotImplementedError(
            "kmeans_assign supports one leading restart axis at most; "
            f"got x {x.shape}, centroids {c.shape}")
    n, d = x3.shape[1:]
    k = c3.shape[1]
    n_pad = layout.round_up(n, block_n)
    d_pad = pol.align_d(d)
    k_pad = pol.align_k(k)
    xp = jnp.pad(x3.astype(jnp.float32),
                 ((0, 0), (0, n_pad - n), (0, d_pad - d)))
    wp = jnp.pad(w2.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
    cp = jnp.pad(c3.astype(jnp.float32),
                 ((0, 0), (0, k_pad - k), (0, d_pad - d)))
    if k_pad > k:  # sentinel rows: huge distance, never selected
        cp = cp.at[:, k:, :].set(_PAD_CENTROID)
    if backend == "gpu":   # parallel grid cells: split reduction
        labels, sums, counts, j = kmeans_assign_kernel(
            xp, wp, cp, block_n=block_n, interpret=False, accumulate=False)
        sums, counts, j = (jnp.sum(sums, axis=1), jnp.sum(counts, axis=1),
                           jnp.sum(j, axis=1))
    else:
        labels, sums, counts, j = kmeans_assign_kernel(
            xp, wp, cp, block_n=block_n,
            interpret=(backend == "interpret"))
    labels, sums = labels[:, :n], sums[:, :k, :d]
    counts, j = counts[:, :k], j[:, 0]
    if not batched:
        labels, sums, counts, j = labels[0], sums[0], counts[0], j[0]
    return labels, sums, counts, j


for _b in dispatch.PALLAS_BACKENDS:
    OP.register(_b)(functools.partial(_pallas_impl, backend=_b))


@OP.register("xla")
@functools.partial(jax.jit, static_argnames=("block_n",))
def _xla_impl(x, w, c, *, block_n: int):
    # delegates to the ref oracle (one copy of the math — see ref.py)
    del block_n
    from .ref import kmeans_assign_masked_ref
    if c.ndim == 2:
        return kmeans_assign_masked_ref(x, w, c)
    return jax.vmap(kmeans_assign_masked_ref,
                    in_axes=(0 if x.ndim == 3 else None,
                             0 if w.ndim == 2 else None, 0))(x, w, c)


# --------------------------------------------------------------------------
# Public op (+ the custom_vmap restart-axis rule)
# --------------------------------------------------------------------------

# (block_n, backend) → custom_vmap-wrapped call; the restart-axis batching
# rule lives in dispatch.make_dispatched_factory (shared with gmm_estep)
_dispatched = dispatch.make_dispatched_factory(OP, n_out=4)


def kmeans_assign(x, centroids, *, mask=None, block_n: int | None = None,
                  backend: str | None = None, interpret: bool | None = None):
    """Fused assignment: (labels [N] i32, sums [K,D], counts [K], j []).

    Accepts a leading restart axis on ``centroids`` (and ``x``/``mask``)
    and composes with ``jax.vmap``; see the module docstring for the
    backend registry and ``mask`` contract.

    Block resolution: an explicit ``block_n`` always wins; otherwise an
    active autotune cache (``kernels.autotune.tuning`` scope — what
    ``EngineConfig(autotune=True)`` enters) supplies the tuned block for
    this (backend, shape) cell; with neither, the backend's hand-picked
    ``TilePolicy`` default applies, bit-for-bit as before.  Either way
    the block passes through ``TilePolicy.block_for`` alignment.
    """
    b, _ = OP.impl(backend, interpret)       # unregistered names fail here
    pol = layout.tile_policy(b)
    n = x.shape[-2]
    if block_n is None:
        tuned = autotune.tuned_blocks(
            "kmeans_assign", b, n=n, k=centroids.shape[-2], d=x.shape[-1])
        if tuned:
            block_n = tuned.get("block_n")
    bn = pol.block_for(n, block_n)
    w = (jnp.ones(x.shape[:-1], jnp.float32) if mask is None
         else jnp.asarray(mask, jnp.float32))
    return _dispatched(bn, b)(x, w, centroids)


def kmeans_assign_chunked(x, centroids, *, chunks: int = 1, mask=None,
                          block_n: int | None = None,
                          backend: str | None = None,
                          interpret: bool | None = None):
    """Streaming entry point for the fused op (engine ``chunks`` mode).

    Slices N into statically-sized pieces via the shared chunked-call
    driver (``layout.chunked_sweep``), runs the dispatched op per piece,
    and accumulates the additive statistics — the [N, K] intermediate
    never exceeds one chunk.  Same contract as ``kmeans_assign``.
    """
    n = x.shape[-2]
    if chunks <= 1 or n <= 1:
        return kmeans_assign(x, centroids, mask=mask, block_n=block_n,
                             backend=backend, interpret=interpret)

    def call(a, b):
        return kmeans_assign(
            x[..., a:b, :], centroids,
            mask=None if mask is None else mask[..., a:b],
            block_n=block_n, backend=backend, interpret=interpret)

    return layout.chunked_sweep(call, n, chunks)
