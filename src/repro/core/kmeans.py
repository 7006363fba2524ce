"""k-means (Lloyd) in JAX — MXU-shaped, distributable, early-stoppable.

Assignment uses the identity ‖x−c‖² = ‖x‖² − 2·x·cᵀ + ‖c‖² so the dominant
cost is an [N,D]×[D,K] matmul (TPU adaptation, DESIGN.md §2).  One fused pass
produces labels, per-cluster sums/counts and the objective J — the same
contract the Pallas kernel (``repro.kernels.kmeans_assign``) implements.
The per-cluster statistics are a second MXU product, one-hot [K,N] × [N,D]
at HIGHEST precision (the default would round the points to bf16), not a
scatter-add by label: with a handful of clusters and a million rows nearly
every scatter update collides, and the TPU serialises them.

Three drivers — all thin wrappers over ``repro.core.engine`` since ISSUE 1:
  · ``kmeans_fit_traced``     — host loop, records (J_i, labels_i) per
    iteration; used on *training groups* to harvest (r_i, h_i) pairs.
  · ``kmeans_fit_earlystop``  — ``lax.while_loop`` with the h ≤ h* predicate
    **on device**; the production path (§4).
  · ``kmeans_fit_full``       — run to convergence: stops only when the
    centroids freeze (the paper's 100%-accuracy reference, Time_full).

All three accept ``axis_name`` so the same code runs under ``shard_map`` with
points sharded over the data axes: the only cross-shard traffic per iteration
is a psum of [K,D]+[K]+[1] statistics.  ``chunks`` streams the assignment
pass over N/C-sized pieces (see the engine docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def assign_and_stats(x, centroids, axis_name=None, use_kernel: bool = False,
                     mask=None, kernel_backend: str | None = None):
    """Fused assignment pass.

    Returns (labels [N] int32, sums [K,D] f32, counts [K] f32, j []).
    ``axis_name``: psum the statistics over those mesh axes (shard_map mode).
    ``use_kernel``: route through the kernel dispatch layer
    (``repro.kernels.dispatch``: tpu/gpu Pallas, interpret elsewhere;
    ``kernel_backend`` forces a registry backend).
    ``mask``: [N] f32 row weights (streaming-chunk padding) — honoured by
    both the jnp and the kernel path (the kernels take a weight operand).

    The jnp path forms ``sums`` and ``counts`` from the weighted one-hot
    [K, N] (K first, so N lies on the lanes): ``sums = onehot @ x``, and
    ``counts`` its row sums.  The product runs at ``HIGHEST``: the one-hot
    is exact in bf16 but the points are not, and the TPU's default
    precision would round them to bf16.  Counts are exact integers below
    2^24; only the float32 summation order of ``sums`` differs from a
    scatter-add's.  Leading dims of ``x`` beyond [N, D] (a [C, P, D]
    chunk layout) are flattened into the contraction.
    """
    if use_kernel:
        from repro.kernels.kmeans_assign import ops as _kops
        labels, sums, counts, j = _kops.kmeans_assign(
            x, centroids, mask=mask, backend=kernel_backend)
    else:
        x = x.astype(jnp.float32)
        c = centroids.astype(jnp.float32)
        x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # [N,1]
        c2 = jnp.sum(c * c, axis=-1)                         # [K]
        # [N,K] MXU matmul, f32: the TPU's default precision is bf16
        d2 = (x2 - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
              + c2[None, :])
        labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        mind2 = jnp.maximum(jnp.min(d2, axis=-1), 0.0)       # clamp fp cancellation
        k = centroids.shape[0]
        onehot = (jnp.arange(k, dtype=jnp.int32)[:, None]
                  == labels.reshape(1, -1)).astype(jnp.float32)   # [K, N]
        if mask is None:
            j = jnp.sum(mind2)
        else:
            mask = mask.astype(jnp.float32)
            j = jnp.sum(mind2 * mask)
            onehot = onehot * mask.reshape(1, -1)
            # weight-0 rows are labelled -1 — the kernel ops' mask contract
            labels = jnp.where(mask > 0, labels, -1)
        sums = jnp.matmul(onehot, x.reshape(-1, x.shape[-1]),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)    # [K, D]
        counts = jnp.sum(onehot, axis=1)                          # [K]
    if axis_name is not None:
        sums = jax.lax.psum(sums, axis_name)
        counts = jax.lax.psum(counts, axis_name)
        j = jax.lax.psum(j, axis_name)
    return labels, sums, counts, j


def update_centroids(centroids, sums, counts):
    """New centroid = mean of members; empty clusters keep their old centroid."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    new = sums / safe
    return jnp.where(counts[:, None] > 0, new, centroids)


def minibatch_update_centroids(centroids, sums, counts, v, decay: float = 1.0):
    """Per-cluster learning-rate update (Sculley 2010, web-scale k-means).

    ``v`` accumulates how many points each cluster has ever absorbed; the
    batched form of the per-point rule c ← (1−1/v)c + (1/v)x is

        v_k ← decay·v_k + n_k        (n_k = batch count for cluster k)
        c_k ← c_k + (n_k / v_k) · (mean_batch_k − c_k)

    so the step size 1/v_k anneals like 1/t and the centroids converge even
    though every iteration only sees a subsample.  ``decay`` < 1 adds
    exponential forgetting (the step size no longer vanishes — useful for
    drifting streams); ``decay`` = 1 is Sculley's schedule exactly.  The
    first batch a cluster sees has n_k = v_k, i.e. a full Lloyd step.

    Sharded contract (shard_map): ``sums``/``counts`` must arrive already
    psum'd over the data axes — the engine reduces the shard-local batch
    stats *before* calling this rule — so ``v`` accumulates GLOBAL
    per-cluster counts, the 1/t step size anneals on the global point
    stream, and (params, v) stay bitwise replicated across shards without
    any further collective.  Feeding shard-local counts instead would both
    shrink the steps (B/shards points per batch) and de-synchronise v
    wherever shard contents differ.

    Returns (new_centroids, new_v); clusters with no batch members keep both.
    """
    v_new = decay * v + counts
    eta = counts / jnp.maximum(v_new, 1.0)
    target = sums / jnp.maximum(counts, 1.0)[:, None]
    new = centroids + eta[:, None] * (target - centroids)
    return jnp.where(counts[:, None] > 0, new, centroids), v_new


def kmeans_step(x, centroids, axis_name=None, use_kernel: bool = False):
    """One Lloyd iteration. Returns (new_centroids, labels, j)."""
    labels, sums, counts, j = assign_and_stats(x, centroids, axis_name, use_kernel)
    return update_centroids(centroids, sums, counts), labels, j


# --------------------------------------------------------------------------
# Chunk layout (shared by the engine's streaming sweep and the ++ init) —
# one copy in kernels.layout since ISSUE 4, re-exported from its
# historical home here.
# --------------------------------------------------------------------------

from repro.kernels.layout import chunk_points  # noqa: E402,F401


# --------------------------------------------------------------------------
# Initialisation
# --------------------------------------------------------------------------

def random_init(key, x, k: int):
    """k distinct data points chosen uniformly."""
    idx = jax.random.choice(key, x.shape[0], shape=(k,), replace=False)
    return x[idx].astype(jnp.float32)


def _min_d2_scan(xc, mask, c, d2):
    """d2 ← min(d2, ‖x − c‖²) streamed chunk-by-chunk ([C, P] in, [C, P] out).

    Padded rows stay pinned at 0 so they carry no sampling mass; the [P, D]
    difference tensor exists for one chunk at a time only.
    """
    def body(_, inp):
        xi, mi, d2i = inp
        diff = xi - c[None, :]
        nd = jnp.minimum(d2i, jnp.sum(diff * diff, axis=-1))
        return None, jnp.where(mi > 0, nd, 0.0)

    _, out = jax.lax.scan(body, None, (xc, mask, d2))
    return out


@functools.partial(jax.jit, static_argnames=("k", "chunks"))
def kmeans_plus_plus_init(key, x, k: int, chunks: int = 1):
    """k-means++ seeding (D² sampling), streamed over ``chunks`` pieces.

    The running min-distance table lives as [C, P] alongside the [C, P, D]
    chunk layout from :func:`chunk_points`; each of the k−1 D² draws is the
    exact hierarchical factorisation of the flat categorical —  pick a chunk
    with probability ∝ its d² mass, then a row within it ∝ d² — so the
    distribution is identical for every chunking, and the per-step temporary
    is one chunk's [P, D] difference, never a resident [N, D] (or any [N, K])
    intermediate.  The key schedule matches the historical monolithic
    implementation (one split per draw; the chunk pick uses a ``fold_in`` of
    the same sub-key and is deterministic when C = 1), so ``chunks=1``
    reproduces the flat pass bit-for-bit (property-tested) and existing
    seeds are unchanged.

    One program per (shape and dtype of ``x``, ``k``, ``chunks``): every
    later call of that shape hits the jit cache and dispatches once.  Run
    eagerly, the ``lax.scan`` of :func:`_min_d2_scan` and the ``fori_loop``
    below would each get a body closure built anew on every call, so each
    call would trace a new jaxpr, miss the cache and lower both programs
    again (a quarter of a second per job on a v5e host).  The compiled
    program draws the same points as its eager trace (property-tested).
    """
    x = x.astype(jnp.float32)
    n = x.shape[0]
    xc, mask = chunk_points(x, chunks)
    n_chunks, per = mask.shape

    key, sub = jax.random.split(key)
    flat = jax.random.randint(sub, (), 0, n)
    first = xc[flat // per, flat % per]
    centroids = jnp.zeros((k, x.shape[1]), jnp.float32).at[0].set(first)
    d2 = _min_d2_scan(xc, mask, first,
                      jnp.where(mask > 0, jnp.inf, 0.0))

    def body(i, carry):
        centroids, d2, key = carry
        key, sub = jax.random.split(key)
        w = jnp.sum(d2, axis=1)                                  # [C] mass
        ci = jax.random.choice(jax.random.fold_in(sub, 1), n_chunks,
                               p=w / jnp.maximum(jnp.sum(w), 1e-30))
        row = d2[ci]
        ri = jax.random.choice(sub, per,
                               p=row / jnp.maximum(jnp.sum(row), 1e-30))
        c = xc[ci, ri]
        centroids = centroids.at[i].set(c)
        return centroids, _min_d2_scan(xc, mask, c, d2), key

    centroids, _, _ = jax.lax.fori_loop(1, k, body, (centroids, d2, key))
    return centroids


# --------------------------------------------------------------------------
# Drivers
# --------------------------------------------------------------------------

def kmeans_fit_traced(x, centroids0, max_iters: int = 300,
                      use_kernel: bool = False, chunks: int = 1):
    """Host-side loop recording the per-iteration history (training groups).

    Returns dict with: labels_history [T,N], objectives [T], final labels,
    centroids, and n_iters.  Runs until the partition is stable or max_iters.
    """
    from .engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine("kmeans", EngineConfig(use_kernel=use_kernel,
                                                  chunks=chunks))
    centroids = jnp.asarray(centroids0, jnp.float32)
    x = jnp.asarray(x)
    labels_hist, js = [], []
    prev_labels = None
    for _ in range(max_iters):
        centroids, labels, j = eng.step(x, centroids)
        labels_hist.append(labels)
        js.append(float(j))
        if prev_labels is not None and bool(jnp.all(labels == prev_labels)):
            break
        prev_labels = labels
    return {
        "labels_history": jnp.stack(labels_hist),
        "objectives": jnp.asarray(js),
        "labels": labels_hist[-1],
        "centroids": centroids,
        "n_iters": len(js),
    }


def trace_accuracy(labels_history, k: int):
    """r_i = Rand(P_i, P_f) for every recorded iteration (paper §3.2)."""
    from .rand_index import rand_index
    final = labels_history[-1]
    # host call → the exact integer path in rand_index (no jit: tracing
    # would demote the pair counts to float32)
    return jnp.asarray([float(rand_index(labels_history[i], final, ka=k, kb=k))
                        for i in range(labels_history.shape[0])])


def trace_to_rh(result, k: int):
    """(r_i, h_i) pairs for regression fitting. h starts at i=2 (Eq. 7)."""
    js = result["objectives"]
    r = trace_accuracy(result["labels_history"], k)
    h = jnp.abs(js[1:] - js[:-1]) / jnp.maximum(jnp.abs(js[:-1]), 1e-30)
    return r[1:], h


def kmeans_fit_earlystop(x, centroids0, h_star, max_iters: int = 300,
                         axis_name=None, use_kernel: bool = False,
                         patience: int = 1, chunks: int = 1):
    """Production driver: lax.while_loop, stop when h_i ≤ h* (on device).

    ``patience`` requires that many CONSECUTIVE sub-threshold readings —
    h is not monotone iteration-to-iteration (plateau → re-acceleration),
    and a single early dip must not trigger the stop (robustification; the
    paper's first-crossing rule is patience=1).

    The stop decision is computed from globally psum'd statistics, so every
    shard sees the same h_i and the loop cannot diverge across devices.
    Returns (centroids, labels, j, n_iters).
    """
    from .engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=max_iters, patience=patience, chunks=chunks,
        axis_name=axis_name, use_kernel=use_kernel,
        use_h_stop=True, stop_when_frozen=True))
    res = eng.fit(x, centroids0, h_star=h_star)
    return res.params, res.labels, res.objective, res.n_iters


def kmeans_fit_full(x, centroids0, max_iters: int = 1000, axis_name=None,
                    use_kernel: bool = False, chunks: int = 1):
    """Run to full convergence: stop only when the centroids freeze.

    Deliberately NOT ``h* = 0``: near convergence the fp32 objective can
    plateau bit-for-bit (ΔJ below J's ulp) while boundary points are still
    migrating, so an h-based stop with h*=0 / patience=1 would return
    centroids that are not a Lloyd fixed point (ISSUE 1 regression).
    """
    from .engine import ClusteringEngine, EngineConfig
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=max_iters, chunks=chunks, axis_name=axis_name,
        use_kernel=use_kernel, use_h_stop=False, stop_when_frozen=True))
    res = eng.fit(x, centroids0)
    return res.params, res.labels, res.objective, res.n_iters
