"""Sharding rules for clustering point sets, per mesh.

The engine's data-parallel drivers shard the *points* and replicate the
parameters: every shard computes its local [K, D] + [K] + [1] sufficient
statistics and one psum per sweep combines them.  Two layouts:

  · [N, D] flat points (:func:`points_spec`, :func:`shard_points`): N over
    the data axes, D replicated; N truncated to a multiple of the extent.
  · [C, N/C, D] pre-chunked points (:func:`chunked_points_spec`,
    :func:`shard_chunked_points`): the chunk axis replicated, rows within a
    chunk over the data axes, padded rows carrying mask 0.  This is the
    layout of the sharded full, minibatch and restart drivers.

The data axes are ``("pod", "data")``, whichever the mesh has.  On a mesh
with neither, the specs replicate the points, and the engine's sharded
drivers refuse the mesh.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axes(mesh: Mesh) -> tuple[tuple[str, ...], str | None, str | None]:
    """(dp_batch_axes, fsdp_axis, tp_axis) present in this mesh."""
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    fsdp = "data" if "data" in names else None
    tp = "model" if "model" in names else None
    return dp, fsdp, tp


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        return int(np.prod([mesh.shape[a] for a in ax]))
    return mesh.shape[ax]


def points_spec(mesh: Mesh) -> P:
    """[N, D] clustering points: N over the data axes, D replicated — the
    layout the engine's per-sweep psum of [K,D]+[K]+[1] stats assumes
    (the full-sweep shard_map drivers).

    Minibatch mode shards the pre-chunked [C, N/C, D] layout instead (see
    :func:`chunked_points_spec`): chunking *before* sharding keeps every
    shard's local chunk a row-slice of the global chunk, so the replicated
    chunk draw subsamples identically to the single-device run.
    """
    dp, _, _ = mesh_axes(mesh)
    return P(dp if dp else None, None)


def shard_points(x, mesh: Mesh):
    """Truncate N to a multiple of the data-axis extent and place the array.

    Returns (sharded [N', D] jax.Array, n_dropped).  Truncation (vs padding)
    keeps every resident row a real point, so the engine needs no global
    validity mask; callers stream the dropped tail separately if they care.
    """
    dp, _, _ = mesh_axes(mesh)
    size = _axis_size(mesh, dp) if dp else 1
    n = x.shape[0] // size * size
    xs = jax.device_put(jax.numpy.asarray(x[:n]),
                        NamedSharding(mesh, points_spec(mesh)))
    return xs, x.shape[0] - n


def chunked_points_spec(mesh: Mesh) -> P:
    """[C, N/C, D] pre-chunked points (``kmeans.chunk_points`` layout):
    chunk axis replicated, rows-within-chunk over the data axes, D
    replicated.

    This is the layout the engine's sharded minibatch/restart drivers use:
    every shard holds a row-slice of each *global* chunk, so the replicated
    seeded chunk draw selects the same global subsample on every shard, and
    shard-local stats only need the engine's once-per-iteration psum.  The
    accompanying [C, N/C] validity mask shards as ``P(*spec[:2])``.
    """
    dp, _, _ = mesh_axes(mesh)
    return P(None, dp if dp else None, None)


def shard_chunked_points(xc, mask, mesh: Mesh):
    """Pad a [C, P, D] chunk layout's row axis to the data-axis extent and
    place (xc, mask) with :func:`chunked_points_spec`.

    Padding (vs ``shard_points``'s truncation) is correct here because the
    chunk layout already carries a validity mask — padded rows get mask 0
    and contribute nothing to the masked sufficient statistics, so no input
    row is dropped on the sharded path.
    """
    dp, _, _ = mesh_axes(mesh)
    size = _axis_size(mesh, dp) if dp else 1
    pad = (-xc.shape[1]) % size
    if pad:
        xc = jax.numpy.pad(xc, ((0, 0), (0, pad), (0, 0)))
        mask = jax.numpy.pad(mask, ((0, 0), (0, pad)))
    spec = chunked_points_spec(mesh)
    xs = jax.device_put(jax.numpy.asarray(xc), NamedSharding(mesh, spec))
    ms = jax.device_put(jax.numpy.asarray(mask),
                        NamedSharding(mesh, P(*tuple(spec)[:2])))
    return xs, ms
