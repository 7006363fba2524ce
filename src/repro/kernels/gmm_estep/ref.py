"""Pure-jnp oracle for the fused GMM E-step kernel.

``gmm_estep_masked_ref`` is the one copy of the reference math — the
registered ``xla`` backend delegates here (so the test oracle and the
backend users run with ``kernel_backend="xla"`` cannot drift), and the
historical ``gmm_estep_ref`` signature wraps it with unit weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# f32 matmuls: the TPU's default precision multiplies in bf16
_HI = jax.lax.Precision.HIGHEST

_LOG2PI = 1.8378770664093453


def gmm_estep_masked_ref(x, w, means, var, log_w):
    """(labels [N] i32, loglik [], r_sum [K], r_x [K,D], r_x2 [K,D]).

    ``w`` are f32 row weights; weight-0 rows are labelled -1 and carry no
    statistics — the kernel ops' mask contract.
    """
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    inv_var = 1.0 / var
    quad = (jnp.matmul(x * x, inv_var.T, precision=_HI)
            - 2.0 * jnp.matmul(x, (means * inv_var).T, precision=_HI)
            + jnp.sum(means ** 2 * inv_var, axis=-1)[None, :])
    log_det = jnp.sum(jnp.log(var), axis=-1)
    d = x.shape[-1]
    lp = log_w[None, :] - 0.5 * (quad + log_det[None, :] + d * _LOG2PI)
    lse = jax.scipy.special.logsumexp(lp, axis=-1)
    resp = jnp.exp(lp - lse[:, None]) * w[:, None]
    labels = jnp.argmax(lp, axis=-1).astype(jnp.int32)
    return (jnp.where(w > 0, labels, -1), jnp.sum(lse * w),
            jnp.sum(resp, axis=0), jnp.matmul(resp.T, x, precision=_HI),
            jnp.matmul(resp.T, x * x, precision=_HI))


def gmm_estep_ref(x, means, var, log_w):
    """(labels [N] i32, loglik [1], r_sum [K], r_x [K,D], r_x2 [K,D])."""
    labels, loglik, r_sum, r_x, r_x2 = gmm_estep_masked_ref(
        x, jnp.ones((x.shape[0],), jnp.float32), means, var, log_w)
    return labels, loglik[None], r_sum, r_x, r_x2
