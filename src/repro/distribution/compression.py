"""int8 all-reduce with error feedback for the sharded drivers' per-sweep
sufficient statistics (``EngineConfig(stats_compression="int8_ef")``).

Wire-format compression needs the reduction implemented manually — a plain
``psum(int8)`` would still move int32 on the wire after XLA's accumulation-
type promotion.  ``ring_allreduce_int8`` is a textbook ring: N−1
reduce-scatter steps + N−1 all-gather steps via ``lax.ppermute``, moving
int8 chunks only → 4× collective-byte reduction vs f32 psum (2× vs bf16).

Quantisation: a shared scale = pmax(|g|)/127, one per row of a matrix leaf
(per cluster for the engine's [K, D] moments) and one per vector leaf,
stochastic-free symmetric rounding.  :func:`compress_with_feedback` carries
the per-leaf quantisation residual into the next sweep (error feedback,
Karimireddy et al. 2019), so the rounding bias does not accumulate.

Used under ``shard_map`` on the DP axes; validated numerically in
tests/test_distribution.py (ring semantics on the 8-host-device ``mesh8``
substrate) and tests/test_engine_sharded.py (stop-iteration parity of the
``EngineConfig(stats_compression="int8_ef")`` fit path against fp32 psum).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x, scale):
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def shared_scale(x, axis_name, axis_size: int = 1):
    """Shared int8 scale covering the worst-case partial SUM (running
    accumulations grow up to axis_size × the per-shard max — scaling by N
    prevents clipping at the cost of proportionally coarser rounding, the
    inherent precision/size trade of int8 reduction).

    A leaf of two or more dims gets one scale per leading index ([R, 1, ...],
    broadcastable against ``x``), so a row of small values (a cluster that
    has nearly converged) is not rounded on the step of the widest row."""
    if x.ndim >= 2:
        amax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)),
                       keepdims=True)
    else:
        amax = jnp.max(jnp.abs(x))
    amax = jax.lax.pmax(amax, axis_name)
    return jnp.maximum(amax * axis_size, 1e-12) / 127.0


def ring_allreduce_int8(x, axis_name: str, axis_size: int, *,
                        mean: bool = True):
    """All-reduce ``x`` (f32) with int8 wire traffic.

    x is padded to a multiple of axis_size and chunked; each step sends one
    int8 chunk to the next rank (ppermute ring). Local accumulation is f32
    (re-quantised before each hop — the re-quantisation error is what the
    error-feedback buffer absorbs).  ``mean=False`` returns the SUM, matching
    ``psum`` semantics for sufficient statistics.

    The output is bit-identical on every shard: each rank's own chunk goes
    through the same quantise→dequantise round trip as the copies it ships
    to its peers.  Replicated callers (e.g. a ``while_loop`` stop decision
    under ``shard_map``) depend on this — shards disagreeing in the last
    int8 ulp would take different trip counts and deadlock the collective.
    """
    if axis_size == 1:
        return x
    orig_shape = x.shape
    n = x.size
    pad = (-n) % axis_size

    def chunked(a, fill):                                   # [N, C]
        flat = jnp.concatenate([a.reshape(-1), jnp.full((pad,), fill,
                                                        a.dtype)])
        return flat.reshape(axis_size, -1)

    chunks = chunked(x, 0.0)
    # per-element view of the (replicated) shared scales, chunked like x
    scale = chunked(jnp.broadcast_to(shared_scale(x, axis_name, axis_size),
                                     orig_shape), 1.0)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # --- reduce-scatter: after N−1 steps, rank r owns the full sum of chunk
    # r+1.  Each hop permutes ONE int8 [C] chunk (the partial sum computed
    # last step), not the whole buffer — wire traffic is 2·(N−1)/N × payload.
    acc = chunks                                            # f32 accum

    def rs_step(i, acc):
        s = (idx - i) % axis_size               # chunk we finished last step
        send = quantize_int8(acc[s], scale[s])  # int8 [C] on the wire
        recv = jax.lax.ppermute(send, axis_name, perm)
        k = (idx - i - 1) % axis_size           # chunk we accumulate now
        return acc.at[k].add(dequantize_int8(recv, scale[k]))

    acc = jax.lax.fori_loop(0, axis_size - 1, rs_step, acc)

    # --- all-gather: circulate the owned (fully-reduced) chunk.  The owner
    # quantises once; the int8 payload is forwarded unchanged, and the owner
    # keeps the same quantise→dequantise round trip its peers see, so the
    # gathered result is bit-identical on every shard.
    own = (idx + 1) % axis_size
    own_q = quantize_int8(acc[own], scale[own])  # int8 [C]
    out = jnp.zeros_like(chunks)
    out = out.at[own].set(dequantize_int8(own_q, scale[own]))

    def ag_step(i, carry):
        out, send = carry
        recv = jax.lax.ppermute(send, axis_name, perm)
        k = (idx - i) % axis_size
        out = out.at[k].set(dequantize_int8(recv, scale[k]))
        return out, recv

    out, _ = jax.lax.fori_loop(0, axis_size - 1, ag_step, (out, own_q))
    total = out.reshape(-1)[:n].reshape(orig_shape)
    return total / axis_size if mean else total


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)


def compress_with_feedback(grads, ef_state, reduce_fn, scale_fn):
    """g' = reduce(g + e);  e ← (g + e) − dequant-path(g + e).

    ``reduce_fn(leaf)`` performs the lossy reduction (e.g. ring int8).  The
    residual uses the quantisation error of OUR contribution (the standard
    EF-SGD form).  ``scale_fn(leaf)`` must return the scale the reduce path
    quantises with — when ``reduce_fn`` is ``ring_allreduce_int8`` that is
    ``shared_scale`` (pmax × axis_size), NOT the local ``max(|leaf|)/127``:
    with the wrong scale the residual models rounding that never happened
    and the EF buffer absorbs the wrong error.
    """
    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        reduced = reduce_fn(corrected)
        # residual: what the wire's quantisation destroyed of OUR contribution
        scale = scale_fn(corrected)
        local_q = dequantize_int8(quantize_int8(corrected, scale), scale)
        new_e = corrected - local_q
        return reduced, new_e

    flat_g, tree = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(ef_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree.unflatten(tree, [o[0] for o in out])
    new_e = jax.tree.unflatten(tree, [o[1] for o in out])
    return new_g, new_e


def ring_wire_bytes(payload_bytes: int, axis_size: int) -> int:
    """Bytes each device SENDS for one ring all-reduce of a payload of
    ``payload_bytes``: N−1 reduce-scatter hops + N−1 all-gather hops, one
    1/N-sized chunk per hop → 2·(N−1)/N × payload.  The same factor applies
    to an fp32 ring, so it cancels in int8-vs-fp32 byte ratios — but the
    absolute numbers are what a cost model consumes."""
    if axis_size <= 1:
        return 0
    return int(2 * (axis_size - 1) * payload_bytes) // int(axis_size)

