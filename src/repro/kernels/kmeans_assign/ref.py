"""Pure-jnp oracle for the fused k-means assignment kernel.

``kmeans_assign_masked_ref`` is the one copy of the reference math — the
registered ``xla`` backend delegates here (so the test oracle and the
backend users run with ``kernel_backend="xla"`` cannot drift), and the
historical ``kmeans_assign_ref`` signature wraps it with unit weights.

It deliberately keeps the scatter-add by label for ``sums``/``counts``:
the default jnp sweep (``repro.core.kmeans.assign_and_stats``) forms them
as a one-hot MXU contraction, so this oracle checks that path by an
independent computation.  The ``xla`` backend therefore keeps the
scatter's cost: on a TPU the scatter serialises its colliding updates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def kmeans_assign_masked_ref(x, w, centroids):
    """(labels [N] i32, sums [K,D] f32, counts [K] f32, j [] f32).

    ``w`` are f32 row weights; weight-0 rows are labelled -1 and carry no
    statistics — the kernel ops' mask contract.
    """
    x = x.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    w = w.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    # f32 matmul: the TPU's default precision multiplies in bf16
    d2 = (x2 - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
          + c2[None, :])
    labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    mind2 = jnp.maximum(jnp.min(d2, axis=-1), 0.0)
    j = jnp.sum(mind2 * w)
    k = c.shape[0]
    sums = jnp.zeros_like(c).at[labels].add(x * w[:, None])
    counts = jnp.zeros((k,), jnp.float32).at[labels].add(w)
    return jnp.where(w > 0, labels, -1), sums, counts, j


def kmeans_assign_ref(x, centroids):
    """(labels [N] i32, sums [K,D] f32, counts [K] f32, j [1] f32)."""
    labels, sums, counts, j = kmeans_assign_masked_ref(
        x, jnp.ones((x.shape[0],), jnp.float32), centroids)
    return labels, sums, counts, j[None]
