"""Public flash-attention op, dispatched through the backend registry.

Backends (see ``kernels.dispatch``): ``tpu`` compiles the Pallas kernel
(Mosaic), ``interpret`` runs the same kernel under the interpreter (CPU
CI), and ``xla`` is the exact-softmax reference.  **No ``gpu`` backend is
registered**: the kernel carries its online-softmax state in TPU VMEM
scratch across the sequential innermost kv grid axis, which is invalid
under Triton's parallel CTAs (the clustering kernels got an
``accumulate=False`` split-reduction variant for exactly this reason; a
Triton-safe flash variant is future work) — on a GPU host the registry
fails loud with the available list; pass ``backend="xla"`` there.
Padding: Sq/Skv → multiples of the block sizes (padded kv columns are
masked inside the kernel via seq_len; padded q rows produce garbage rows
that are trimmed); dh → multiple of 128 with zeros (contributes nothing
to scores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune, dispatch, layout
from repro.kernels.layout import round_up

from .kernel import flash_attention_kernel
from .ref import attention_ref

OP = dispatch.get_op("flash_attention")

# sequential-grid Pallas backends only — see the module docstring for why
# there is no "gpu" registration
_SEQ_GRID_BACKENDS = ("tpu", "interpret")


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "backend"))
def _pallas_impl(q, k, v, *, causal, window, scale, block_q, block_k,
                 backend):
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    sq_p = round_up(sq, block_q)
    sk_p = round_up(skv, block_k)
    dh_p = round_up(dh, 128)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, dh_p - dh)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - skv), (0, dh_p - dh)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - skv), (0, dh_p - dh)))
    o = flash_attention_kernel(qp, kp, vp, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               interpret=(backend == "interpret"))
    return o[:, :, :sq, :dh]


for _b in _SEQ_GRID_BACKENDS:
    OP.register(_b)(functools.partial(_pallas_impl, backend=_b))


@OP.register("xla")
@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k"))
def _xla_impl(q, k, v, *, causal, window, scale, block_q, block_k):
    del block_q, block_k
    return attention_ref(q, k, v, causal=causal, window=window, scale=scale)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int | None = None,
                    block_k: int | None = None,
                    backend: str | None = None,
                    interpret: bool | None = None):
    """Flash attention with GQA: q [B,Hq,S,dh], k/v [B,Hkv,S,dh].

    Block resolution mirrors the clustering ops: explicit ``block_q`` /
    ``block_k`` win; else an active autotune cache
    (``kernels.autotune.tuning`` scope) supplies the tuned pair for this
    (backend, Sq, Skv, dh) cell; else the hand-picked 128×128 default —
    all capped to the aligned sequence lengths as before.
    """
    b, fn = OP.impl(backend, interpret)      # unregistered names fail here
    pol = layout.tile_policy(b)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    tuned = None
    if block_q is None and block_k is None:
        tuned = autotune.tuned_blocks(
            "flash_attention", b, n=q.shape[2], k=k.shape[2], d=q.shape[3])
    bq = block_q if block_q is not None else (tuned or {}).get("block_q", 128)
    bk = block_k if block_k is not None else (tuned or {}).get("block_k", 128)
    bq = min(bq, round_up(q.shape[2], pol.row_align))
    bk = min(bk, round_up(k.shape[2], pol.row_align))
    return fn(q, k, v, causal=causal, window=window, scale=float(scale),
              block_q=bq, block_k=bk)
