"""Shared layout helpers for the kernel packages (ISSUE 4).

Every kernel wrapper used to carry its own ``_round_up`` / pad / chunk
plumbing; this module is the single copy.  It owns:

  · alignment arithmetic (:func:`round_up`) and the per-backend
    :class:`TilePolicy` table — TPU pads features to the 128-lane vector
    width and clusters to 8 sublanes; the GPU (Triton) policy uses the
    16-aligned shapes tensor-core ``dot`` wants and a smaller row block;
    ``interpret`` mirrors the TPU policy so CPU CI exercises TPU shapes.

  · the two chunk layouts the engine and the ops share:
    :func:`chunk_bounds` (static remainder-absorbing [start, stop) slices
    over a flat N — the kernels' streaming entry points) and
    :func:`chunk_points` (the engine's padded ``[C, ceil(N/C), D]`` + mask
    reshape).  ``kernels.kmeans_assign.ops.chunk_bounds`` and
    ``core.kmeans.chunk_points`` re-export these names, so historical
    import sites keep working.

  · the shared chunked-call drivers: :func:`chunked_sweep` streams a flat
    array through statically-sliced op calls, and
    :func:`subsampled_stats` runs a gather-free pass over a drawn subset
    of the ``chunk_points`` layout (``lax.dynamic_index_in_dim`` per scan
    step — each op call sees one statically-shaped ``[P, D]`` chunk, and
    the ``[B, P, D]`` gathered copy never materialises).  This is what
    lets ``mode="minibatch"`` compose with ``use_kernel=True``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# --------------------------------------------------------------------------
# Per-backend tile / padding policy
# --------------------------------------------------------------------------

def next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class TilePolicy:
    """Row-block and padding alignment for one kernel backend.

    ``pow2`` forces every padded block dimension (and the row block) up to
    the next power of two — Triton requires pow2 block shapes, while the
    TPU lowering only needs sublane/lane multiples.
    """
    block_rows: int      # default rows per grid step
    row_align: int       # rows are padded to a multiple of the block
    k_align: int         # cluster/component axis padding multiple
    d_align: int         # feature axis padding multiple
    pow2: bool = False   # padded dims must be powers of two (Triton)

    def _aligned(self, x: int, m: int) -> int:
        r = round_up(x, m)
        return next_pow2(r) if self.pow2 else r

    def block_for(self, n: int, block_rows: int | None = None) -> int:
        # explicit overrides are aligned too, so a hand-picked block_n can
        # never violate the backend's (e.g. Triton pow2) block-shape rules
        b = self._aligned(self.block_rows if block_rows is None
                          else block_rows, self.row_align)
        return min(b, self._aligned(max(n, self.row_align), self.row_align))

    def align_k(self, k: int) -> int:
        return self._aligned(k, self.k_align)

    def align_d(self, d: int) -> int:
        return self._aligned(d, self.d_align)


_TPU_POLICY = TilePolicy(block_rows=1024, row_align=8, k_align=8, d_align=128)

TILE_POLICIES: dict[str, TilePolicy] = {
    "tpu": _TPU_POLICY,
    # interpret emulates the TPU lowering — same shapes, so CPU CI parity
    # tests cover the tiles the TPU path compiles
    "interpret": _TPU_POLICY,
    # Triton tensor-core dot wants every dim >= 16 and pow2 block shapes;
    # the smaller row block keeps one (block, D) tile within shared memory
    "gpu": TilePolicy(block_rows=256, row_align=16, k_align=16, d_align=32,
                      pow2=True),
    # the jnp reference pads nothing and ignores block_n; the TPU policy
    # only keeps the one block the ops and the autotuner pass it aligned
    "xla": _TPU_POLICY,
}


def tile_policy(backend: str) -> TilePolicy:
    try:
        return TILE_POLICIES[backend]
    except KeyError:
        raise ValueError(
            f"no tile policy for kernel backend {backend!r} (known: "
            f"{sorted(TILE_POLICIES)}); add one to layout.TILE_POLICIES "
            "for a backend that pads"
        ) from None


# --------------------------------------------------------------------------
# Batch buckets (the serving layer's fixed compile shapes)
# --------------------------------------------------------------------------

DEFAULT_BUCKETS = (256, 1024, 4096, 16384)


def bucket_for(n: int, buckets=DEFAULT_BUCKETS) -> int:
    """Smallest padded size in the bucket ladder that holds ``n`` rows.

    The serving layer pads every drained request batch up to a bucket so
    XLA sees a closed set of shapes — one compiled program per (model,
    bucket) instead of one per arriving batch size.

    Contract (ISSUE 9): within the ladder, the smallest bucket ≥ n wins;
    **above the largest bucket the ladder continues in multiples of that
    bucket** (⌈n/B⌉·B for B = ``buckets[-1]``), so the shape set stays
    closed and countable at any n instead of failing implicitly.  Callers
    that must bound admitted sizes (the serving queue) enforce their own
    cap *before* bucketing — ``ClusterServer`` rejects oversize batches at
    admission.  Padding that is impossible fails loud: ``n < 1`` (nothing
    to pad) or an empty ``buckets`` ladder raise ``ValueError``.
    """
    if not buckets:
        raise ValueError("bucket_for needs a non-empty bucket ladder — "
                         "padding to a bucket is impossible without one")
    if n < 1:
        raise ValueError(f"cannot pad a batch of {n} rows to a bucket — "
                         "batches must have at least one row")
    for b in buckets:
        if n <= b:
            return b
    return round_up(n, buckets[-1])


def pad_to_bucket(x, bucket: int):
    """[N, D] → ([bucket, D], mask [bucket]) zero-padded; mask 0 marks the
    padding rows the ops' mask operand drops from labels and statistics."""
    n = x.shape[0]
    xp = jnp.pad(jnp.asarray(x, jnp.float32), ((0, bucket - n), (0, 0)))
    return xp, (jnp.arange(bucket) < n).astype(jnp.float32)


# --------------------------------------------------------------------------
# Chunk layouts
# --------------------------------------------------------------------------

def chunk_bounds(n: int, chunks: int) -> list[tuple[int, int]]:
    """Static [start, stop) slices covering N in ``chunks`` pieces; the last
    piece absorbs the remainder when chunks does not divide N."""
    c = max(1, min(int(chunks), n))
    per = -(-n // c)
    return [(s, min(s + per, n)) for s in range(0, n, per)]


def chunk_points(x, chunks: int):
    """[N, D] → ([C, ceil(N/C), D], mask [C, ceil(N/C)]) with zero-padding.

    Row-major: global row i lives at chunk i // per, slot i % per.  The mask
    is 1.0 for real rows, 0.0 for padding.
    """
    n, d = x.shape
    c = max(1, min(int(chunks), n))
    per = -(-n // c)
    pad = c * per - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    mask = (jnp.arange(c * per) < n).astype(jnp.float32).reshape(c, per)
    return xp.reshape(c, per, d), mask


# --------------------------------------------------------------------------
# Shared chunked-call drivers
# --------------------------------------------------------------------------

def chunked_sweep(call, n: int, chunks: int):
    """Stream a flat N through statically-sliced op calls.

    ``call(lo, hi)`` runs the op on rows [lo, hi) and returns
    ``(rows, *additive)`` — a per-row output (concatenated across chunks)
    plus additive sufficient statistics (summed).  Returns the same tuple
    shape the monolithic call produces.
    """
    rows, adds = [], None
    for a, b in chunk_bounds(n, chunks):
        r, *st = call(a, b)
        rows.append(r)
        adds = st if adds is None else [x + y for x, y in zip(adds, st)]
    # rows concatenate along the row axis (last — batched labels are [R, N])
    return (jnp.concatenate(rows, axis=-1), *adds)


def subsampled_stats(call, zero, xc, mask, idx, prefetch: bool = False):
    """Gather-free stats over drawn chunks of a ``chunk_points`` layout.

    ``call(x_chunk [P, D], w [P])`` returns a pytree of additive statistics
    (zero-initialised from the matching ``zero`` tree); ``idx`` is a traced
    [B] vector of chunk indices.  Each scan step ``dynamic_index``es one
    statically-shaped chunk out of ``xc [C, P, D]`` — no ``[B, P, D]``
    gathered copy ever materialises — and accumulates.  Returns
    ``(stats, n_batch)`` with ``n_batch`` the summed mask weight of the
    drawn rows.  Composes with ``vmap``: per-restart draws batch the
    indexed chunk, which the ops' batching rules route onto the kernels'
    restart grid axis.

    ``prefetch=True`` double-buffers the scan: the carry holds the chunk
    being processed while the body issues the load of the *next* drawn
    chunk, which has no data dependency on the current ``call`` — the
    scheduler can overlap copy i+1 with compute i.  Same chunk order, same
    adds: results are bit-identical.
    """
    def load(i):
        xi = jax.lax.dynamic_index_in_dim(xc, i, 0, keepdims=False)
        mi = jax.lax.dynamic_index_in_dim(mask, i, 0, keepdims=False)
        return xi, mi

    init = (zero, jnp.zeros((), jnp.float32))
    if prefetch and idx.shape[0] > 1:
        # shift the draw order one step: step t computes on the chunk
        # loaded at t-1 and loads the chunk for t+1 (the last step's load
        # is a harmless repeat that nothing computes on)
        nxt = jnp.concatenate([idx[1:], idx[-1:]])

        def body(carry, i_nxt):
            (acc, nb), (xi, mi) = carry
            x_nxt, m_nxt = load(i_nxt)
            st = call(xi, mi)
            out = (jax.tree.map(jnp.add, acc, st), nb + jnp.sum(mi))
            return (out, (x_nxt, m_nxt)), None

        ((stats, n_batch), _), _ = jax.lax.scan(
            body, (init, load(idx[0])), nxt)
    else:
        def body(carry, i):
            acc, nb = carry
            xi, mi = load(i)
            st = call(xi, mi)
            return (jax.tree.map(jnp.add, acc, st), nb + jnp.sum(mi)), None

        (stats, n_batch), _ = jax.lax.scan(body, init, idx)
    return stats, n_batch
