"""The int8 ring all-reduce and its error feedback (in-process 8-device
mesh)."""
import functools
import re

import jax
import numpy as np


def test_ring_allreduce_int8(mesh8):
    """Numerics + int8 wire format, on the in-process 8-device mesh
    (conftest sets the host-platform device count for the whole session)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distribution.compression import ring_allreduce_int8

    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 1000))
                    .astype(np.float32))
    f = shard_map(functools.partial(ring_allreduce_int8, axis_name="d",
                                    axis_size=8),
                  mesh=mesh8, in_specs=P("d"), out_specs=P("d"),
                  check_vma=False)
    out = f(x)
    ref = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
    err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    # int8 ring: ≤ 1 rounding per hop, 7 hops on the reduce path
    assert err < scale * 8, (err, scale)
    # wire ops are int8: check the HLO
    hlo = jax.jit(f).lower(x).compile().as_text()
    assert "collective-permute" in hlo
    perms = re.findall(r"(s8|s32|f32)\[[^\]]*\][^=]*collective-permute",
                       hlo) or re.findall(
                       r"= \(?(s8|s32|f32)\[[^\]]*\].*collective-permute",
                       hlo)
    assert "s8" in perms, perms


def test_quantize_int8_roundtrip_bounds():
    """Round-trip error of one quantise→dequantise is ≤ scale/2 for values
    inside the representable range, and saturates (not wraps) outside it."""
    import jax.numpy as jnp
    from repro.distribution.compression import (dequantize_int8,
                                                quantize_int8)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 3, (4096,))
                    .astype(np.float32))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    rt = dequantize_int8(quantize_int8(x, scale), scale)
    assert float(jnp.max(jnp.abs(rt - x))) <= scale / 2 + 1e-7
    # out-of-range values clip to ±127·scale — saturation, never wraparound
    big = jnp.asarray([1e6, -1e6], jnp.float32)
    rt_big = dequantize_int8(quantize_int8(big, scale), scale)
    np.testing.assert_allclose(rt_big, [127 * scale, -127 * scale],
                               rtol=1e-6)


def test_shared_scale_headroom(mesh8):
    """The ring's shared scale carries axis_size× headroom: a partial sum
    of all shards' worst-case values still quantises without clipping, even
    when per-shard maxima differ by orders of magnitude."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distribution.compression import (dequantize_int8,
                                                quantize_int8, shared_scale)

    # shard i's max is 10^(i/3): local scales would differ ~200×
    x = jnp.stack([jnp.full((64,), 10.0 ** (i / 3), jnp.float32)
                   for i in range(8)])
    f = shard_map(lambda s: shared_scale(s, "d", 8)[None], mesh=mesh8,
                  in_specs=P("d"), out_specs=P("d"), check_vma=False)
    scales = np.asarray(f(x)).reshape(-1)
    expect = float(jnp.max(jnp.abs(x))) * 8 / 127.0
    np.testing.assert_allclose(scales, expect, rtol=1e-6)  # replicated
    # worst-case running accumulation: the full cross-shard sum
    total = jnp.sum(x, axis=0)
    rt = dequantize_int8(quantize_int8(total, scales[0]), scales[0])
    err = float(jnp.max(jnp.abs(rt - total)))
    assert err <= scales[0] / 2 + 1e-5, (err, scales[0])  # rounding, no clip


def test_ring_allreduce_int8_sum_mode_replicated(mesh8):
    """mean=False matches psum semantics, and the output is bit-identical
    on every shard — replicated while_loop stop decisions depend on it."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distribution.compression import ring_allreduce_int8

    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (8, 257))
                    .astype(np.float32))                 # 257 ∤ 8: pad path
    f = shard_map(functools.partial(ring_allreduce_int8, axis_name="d",
                                    axis_size=8, mean=False),
                  mesh=mesh8, in_specs=P("d"), out_specs=P("d"),
                  check_vma=False)
    out = np.asarray(f(x)).reshape(8, -1)
    ref = np.asarray(jnp.sum(x, 0))
    scale = float(jnp.max(jnp.abs(x))) * 8 / 127.0
    assert float(np.max(np.abs(out[0] - ref))) < scale * 8
    for r in range(1, 8):                                # bit-identical
        np.testing.assert_array_equal(out[r], out[0])


def test_compress_with_feedback_shared_scale_residual(mesh8):
    """Regression for the EF scale mismatch: when the reduce path is the
    ring (shared pmax·N scale), the residual must model THAT quantisation,
    not the local max(|g|)/127 one — with per-shard maxima orders of
    magnitude apart the two scales differ wildly."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distribution.compression import (
        compress_with_feedback, dequantize_int8, init_error_feedback,
        quantize_int8, ring_allreduce_int8, shared_scale)

    rng = np.random.default_rng(3)
    x = jnp.asarray(np.stack([rng.normal(0, 10.0 ** (i / 3), (128,))
                              for i in range(8)]).astype(np.float32))

    def shard_fn(g):
        reduced, new_e = compress_with_feedback(
            (g,), init_error_feedback((g,)),
            reduce_fn=functools.partial(ring_allreduce_int8, axis_name="d",
                                        axis_size=8, mean=False),
            scale_fn=lambda t: shared_scale(t, "d", 8))
        return new_e[0]

    f = shard_map(shard_fn, mesh=mesh8, in_specs=P("d"), out_specs=P("d"),
                  check_vma=False)
    new_e = np.asarray(f(x))
    # the ring quantises with the SHARED scale; residual must match it
    s = jnp.max(jnp.abs(x)) * 8 / 127.0
    ref_e = np.asarray(x - dequantize_int8(quantize_int8(x, s), s))
    np.testing.assert_allclose(new_e, ref_e, rtol=1e-6, atol=1e-7)
    # and must NOT be the local-scale residual on the small-magnitude shard
    s0 = jnp.max(jnp.abs(x[0])) / 127.0
    local_e0 = np.asarray(x[0] - dequantize_int8(quantize_int8(x[0], s0),
                                                 s0))
    assert float(np.max(np.abs(new_e[0] - local_e0))) > float(s0)


def test_ring_wire_bytes_factor():
    from repro.distribution.compression import ring_wire_bytes
    assert ring_wire_bytes(1000, 1) == 0        # nothing moves on 1 device
    assert ring_wire_bytes(1000, 2) == 1000     # 2·(1/2) × payload
    assert ring_wire_bytes(1000, 8) == 1750     # 2·(7/8) × payload
