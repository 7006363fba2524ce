"""ClusteringEngine: streaming-vs-monolithic parity, multi-restart vmap
equivalence, chunked kernel entry points, LongTailModel config routing,
the kmeans_fit_full frozen-only stop (ISSUE 1), minibatch mode (ISSUE 2):
tolerance parity with full-batch, the full-mode bit-identical regression
guard, config validation — and the kernel-dispatch composition (ISSUE 4):
fit_restarts / minibatch / both with use_kernel=True matching the jnp
trajectories."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import em_gmm
from repro.core.engine import ClusteringEngine, EngineConfig

K = 4


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(0)
    centers = np.array([[0, 0, 0], [8, 8, 8], [-8, 8, 0], [8, -8, 4]], float)
    x = np.concatenate([c + rng.normal(0, 1.0, (500, 3)) for c in centers])
    return jnp.asarray(x.astype(np.float32))   # N=2000: 4 | N, 7 ∤ N


@pytest.fixture(scope="module")
def c0(blobs):
    return core.kmeans_plus_plus_init(jax.random.PRNGKey(0), blobs, K)


# --------------------------------------------------------------------------
# Streaming parity — chunk counts that do and do not divide N
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 4, 7])
def test_streaming_parity_kmeans(blobs, c0, chunks):
    c_ref, l_ref, j_ref, it_ref = core.kmeans_fit_earlystop(
        blobs, c0, 1e-4, max_iters=100)
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=chunks, use_h_stop=True, stop_when_frozen=True))
    r = eng.fit(blobs, c0, h_star=1e-4)
    assert int(r.n_iters) == int(it_ref)
    np.testing.assert_allclose(r.params, c_ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(r.objective, j_ref, rtol=1e-5)
    # chunked fp error may flip the odd boundary point, nothing more
    assert float((r.labels == l_ref).mean()) > 0.999


@pytest.mark.parametrize("chunks", [1, 4, 7])
def test_streaming_parity_em(blobs, c0, chunks):
    p0 = em_gmm.init_from_kmeans(blobs, c0)
    p_ref, l_ref, ll_ref, it_ref = em_gmm.em_fit_earlystop(
        blobs, p0, 1e-5, max_iters=100)
    eng = ClusteringEngine("em", EngineConfig(max_iters=100, chunks=chunks))
    r = eng.fit(blobs, p0, h_star=1e-5)
    assert int(r.n_iters) == int(it_ref)
    np.testing.assert_allclose(r.params.means, p_ref.means,
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(r.params.var, p_ref.var, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(r.objective, ll_ref, rtol=1e-5)
    assert float((r.labels == l_ref).mean()) > 0.999


def test_streaming_wrapper_kwarg_matches_engine(blobs, c0):
    """The public drivers expose chunks= and agree with the engine."""
    c_a, _, j_a, it_a = core.kmeans_fit_earlystop(blobs, c0, 1e-4,
                                                  max_iters=100, chunks=5)
    c_b, _, j_b, it_b = core.kmeans_fit_earlystop(blobs, c0, 1e-4,
                                                  max_iters=100)
    assert int(it_a) == int(it_b)
    np.testing.assert_allclose(c_a, c_b, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(j_a, j_b, rtol=1e-5)


# --------------------------------------------------------------------------
# Multi-restart vmap with per-restart stop masks
# --------------------------------------------------------------------------

def test_multirestart_kmeans_matches_sequential(blobs):
    key = jax.random.PRNGKey(7)
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, use_h_stop=True, stop_when_frozen=True))
    seq = [eng.fit(blobs, core.kmeans_plus_plus_init(kk, blobs, K),
                   h_star=1e-4)
           for kk in jax.random.split(key, 3)]
    rr = eng.fit_restarts(blobs, key=key, k=K, restarts=3, h_star=1e-4)
    # seed-for-seed: same iteration counts and objectives per restart
    for i, s in enumerate(seq):
        assert int(rr.n_iters[i]) == int(s.n_iters), i
        np.testing.assert_allclose(rr.objectives[i], s.objective, rtol=1e-5)
    best_seq = int(np.argmin([float(s.objective) for s in seq]))
    assert int(rr.best_index) == best_seq
    np.testing.assert_allclose(rr.best.params, seq[best_seq].params,
                               rtol=1e-5, atol=1e-4)
    assert float((rr.best.labels == seq[best_seq].labels).mean()) > 0.999


def test_multirestart_em_matches_sequential(blobs):
    key = jax.random.PRNGKey(11)
    eng = ClusteringEngine("em", EngineConfig(max_iters=40))
    seq = [eng.fit(blobs, em_gmm.random_init(kk, blobs, K), h_star=1e-4)
           for kk in jax.random.split(key, 3)]
    rr = eng.fit_restarts(blobs, key=key, k=K, restarts=3, h_star=1e-4)
    for i, s in enumerate(seq):
        assert int(rr.n_iters[i]) == int(s.n_iters), i
        np.testing.assert_allclose(rr.objectives[i], s.objective,
                                   rtol=1e-4)
    best_seq = int(np.argmax([float(s.objective) for s in seq]))
    assert int(rr.best_index) == best_seq   # EM: argmax loglik


def test_multirestart_streaming_composes(blobs):
    """Both scale axes at once: vmapped restarts over chunked sweeps."""
    key = jax.random.PRNGKey(3)
    mono = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, stop_when_frozen=True))
    stream = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, chunks=7, stop_when_frozen=True))
    a = mono.fit_restarts(blobs, key=key, k=K, restarts=2, h_star=1e-4)
    b = stream.fit_restarts(blobs, key=key, k=K, restarts=2, h_star=1e-4)
    assert int(a.best_index) == int(b.best_index)
    np.testing.assert_array_equal(np.asarray(a.n_iters), np.asarray(b.n_iters))
    np.testing.assert_allclose(a.objectives, b.objectives, rtol=1e-5)


# --------------------------------------------------------------------------
# Chunked kernel entry points (fused contract, CPU interpret mode)
# --------------------------------------------------------------------------

def test_kmeans_assign_chunked_matches_monolithic(blobs, c0):
    from repro.kernels.kmeans_assign.ops import (kmeans_assign,
                                                 kmeans_assign_chunked)
    x = blobs[:777]                                    # 3 ∤ 777 remainder
    l1, s1, n1, j1 = kmeans_assign(x, c0)
    l2, s2, n2, j2 = kmeans_assign_chunked(x, c0, chunks=3)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(n1, n2, rtol=0)
    np.testing.assert_allclose(j1, j2, rtol=1e-5)


def test_gmm_estep_chunked_matches_monolithic(blobs, c0):
    from repro.kernels.gmm_estep.ops import gmm_estep, gmm_estep_chunked
    p = em_gmm.init_from_kmeans(blobs, c0)
    x = blobs[:777]
    o1 = gmm_estep(x, p.means, p.var, p.log_w)
    o2 = gmm_estep_chunked(x, p.means, p.var, p.log_w, chunks=3)
    np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(o2[0]))
    np.testing.assert_allclose(o1[1], o2[1], rtol=1e-5)
    for a, b in zip(o1[2:], o2[2:]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-3)


def test_engine_kernel_streaming_path(blobs, c0):
    """use_kernel=True + chunks>1 routes through the chunked fused ops."""
    x = blobs[:512]
    ref = ClusteringEngine("kmeans", EngineConfig(
        max_iters=10, stop_when_frozen=True))
    ker = ClusteringEngine("kmeans", EngineConfig(
        max_iters=10, chunks=4, use_kernel=True, stop_when_frozen=True))
    a = ref.fit(x, c0, h_star=1e-4)
    b = ker.fit(x, c0, h_star=1e-4)
    assert int(a.n_iters) == int(b.n_iters)
    np.testing.assert_allclose(a.params, b.params, rtol=1e-4, atol=1e-3)


# --------------------------------------------------------------------------
# LongTailModel → EngineConfig routing
# --------------------------------------------------------------------------

def test_config_from_longtail(blobs, c0):
    res = core.kmeans_fit_traced(blobs, c0, max_iters=100)
    r, h = core.trace_to_rh(res, K)
    model = core.fit_longtail([(np.asarray(r), np.asarray(h))],
                              algorithm="kmeans", dataset="blobs",
                              family="quadratic")
    cfg = EngineConfig.from_longtail(model, 0.95, max_iters=100,
                                     stop_when_frozen=True)
    assert cfg.h_star == pytest.approx(model.threshold_for(0.95))
    eng = ClusteringEngine("kmeans", cfg)
    out = eng.fit(blobs, c0)                  # threshold comes from config
    _, _, _, it_ref = core.kmeans_fit_earlystop(
        blobs, c0, model.threshold_for(0.95), max_iters=100)
    assert int(out.n_iters) == int(it_ref)
    acc = float(core.rand_index(out.labels, res["labels"], K, K))
    assert acc >= 0.90


# --------------------------------------------------------------------------
# Minibatch mode (ISSUE 2)
# --------------------------------------------------------------------------

def test_minibatch_kmeans_reaches_full_batch_quality(blobs, c0):
    """B-of-C subsampled sweeps with 1/t learning-rate updates land within
    tolerance of the full-batch objective and partition while touching a
    quarter of the points per iteration."""
    full = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, stop_when_frozen=True))
    rf = full.fit(blobs, c0, h_star=1e-4)
    mb = ClusteringEngine("kmeans", EngineConfig(
        mode="minibatch", chunks=8, batch_chunks=2, patience=3,
        max_iters=300, stop_when_frozen=True))
    rm = mb.fit(blobs, c0, h_star=1e-4)
    np.testing.assert_allclose(float(rm.objective), float(rf.objective),
                               rtol=0.02)
    acc = float(core.rand_index(rm.labels, rf.labels, K, K))
    assert acc >= 0.99, acc
    # paired Eq. 7 h actually stops the loop (no run-to-max_iters)
    assert int(rm.n_iters) < 300


def test_minibatch_em_reaches_full_batch_quality(blobs, c0):
    p0 = em_gmm.init_from_kmeans(blobs, c0)
    full = ClusteringEngine("em", EngineConfig(max_iters=100))
    rf = full.fit(blobs, p0, h_star=1e-5)
    mb = ClusteringEngine("em", EngineConfig(
        mode="minibatch", chunks=8, batch_chunks=2, patience=3,
        max_iters=300))
    rm = mb.fit(blobs, p0, h_star=1e-4)
    # stepwise EM on subsampled responsibilities: per-point loglik within 1%
    np.testing.assert_allclose(float(rm.objective), float(rf.objective),
                               rtol=0.01)
    acc = float(core.rand_index(rm.labels, rf.labels, K, K))
    assert acc >= 0.95, acc


def test_minibatch_restarts_compose(blobs):
    """Minibatch × vmapped restarts: every restart draws its own chunk
    stream, stops on its own mask, and the best full-sweep objective wins."""
    mb = ClusteringEngine("kmeans", EngineConfig(
        mode="minibatch", chunks=8, batch_chunks=2, patience=3,
        max_iters=200, stop_when_frozen=True))
    rr = mb.fit_restarts(blobs, key=jax.random.PRNGKey(5), k=K, restarts=3,
                         h_star=1e-4)
    assert rr.objectives.shape == (3,)
    best = int(np.argmin(np.asarray(rr.objectives)))
    assert int(rr.best_index) == best
    np.testing.assert_allclose(float(rr.best.objective),
                               float(rr.objectives[best]))
    full = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, stop_when_frozen=True))
    rf = full.fit(blobs, core.kmeans_plus_plus_init(
        jax.random.PRNGKey(0), blobs, K), h_star=1e-4)
    np.testing.assert_allclose(float(rr.best.objective),
                               float(rf.objective), rtol=0.02)


def test_minibatch_reduces_points_touched_per_iteration(blobs, c0):
    """The compiled minibatch sweep really gathers B chunks, not all C —
    checked on the jaxpr-level shapes of the scan carry input."""
    from repro.core.engine import _minibatch_sweep, KMEANS
    cfg = EngineConfig(mode="minibatch", chunks=8, batch_chunks=2,
                       max_iters=10)
    xc, mask = core.chunk_points(blobs, 8)
    stats, n_batch = jax.jit(
        lambda p, k: _minibatch_sweep(KMEANS, cfg, xc, mask, p, k)
    )(c0, jax.random.PRNGKey(0))
    assert float(n_batch) == pytest.approx(2 * mask.shape[1])
    assert float(n_batch) <= 0.26 * blobs.shape[0]


def test_minibatch_too_few_effective_chunks_fails_loud():
    """chunk_points clamps C to the row count; a tiny x must hit the
    engine's message, not choice(replace=False)'s trace error."""
    tiny = jnp.asarray(np.arange(20.0).reshape(10, 2), jnp.float32)
    eng = ClusteringEngine("kmeans", EngineConfig(
        mode="minibatch", chunks=64, batch_chunks=16, max_iters=5))
    c0 = jnp.asarray([[0.0, 1.0], [18.0, 19.0]], jnp.float32)
    with pytest.raises(ValueError, match="effective chunks"):
        eng.fit(tiny, c0)


def test_engine_config_validation():
    with pytest.raises(ValueError, match="chunks >= 2"):
        EngineConfig(mode="minibatch")
    with pytest.raises(ValueError, match="batch_chunks < chunks"):
        EngineConfig(mode="minibatch", chunks=8, batch_chunks=8)
    with pytest.raises(ValueError, match="unknown engine mode"):
        EngineConfig(mode="online")
    with pytest.raises(ValueError, match="decay"):
        EngineConfig(mode="minibatch", chunks=8, batch_chunks=2, decay=0.0)
    # minibatch + use_kernel is a supported combination since ISSUE 4
    EngineConfig(mode="minibatch", chunks=8, batch_chunks=2, use_kernel=True)
    # auto/None resolve to a concrete registry name at construction (so
    # the static config — and hence the jit cache key — carries it)
    cfg = EngineConfig(use_kernel=True)
    assert cfg.kernel_backend not in (None, "auto")
    if not os.environ.get("REPRO_FORCE_KERNEL_BACKEND"):
        with pytest.raises(ValueError, match="use_kernel=False"):
            EngineConfig(kernel_backend="interpret")


def test_engine_config_compression_validation():
    """ISSUE 7: the stats_compression knobs fail loud on every unusable
    combination instead of silently running uncompressed (or deadlocking
    a frozen-centroid stop that can never fire)."""
    with pytest.raises(ValueError, match="unknown stats_compression"):
        EngineConfig(stats_compression="fp8")
    with pytest.raises(ValueError, match="no effect"):
        EngineConfig(stats_axis_size=8)       # stray knob without int8_ef
    with pytest.raises(ValueError, match="stop_when_frozen"):
        EngineConfig(stats_compression="int8_ef", stop_when_frozen=True)
    with pytest.raises(ValueError, match="single-axis"):
        EngineConfig(stats_compression="int8_ef",
                     axis_name=("pod", "data"))
    with pytest.raises(ValueError, match="stats_axis_size"):
        EngineConfig(stats_compression="int8_ef", axis_name="data")
    # the combinations the sharded drivers build are valid
    EngineConfig(stats_compression="int8_ef")
    EngineConfig(stats_compression="int8_ef", axis_name="data",
                 stats_axis_size=8)


def test_prefetch_bit_identical_single_device(blobs, c0):
    """prefetch=True double-buffers the chunk scan without changing chunk
    order or accumulation: bit-identical fits, full-streaming and
    minibatch."""
    for base in (dict(max_iters=60, chunks=4, stop_when_frozen=True),
                 dict(mode="minibatch", chunks=8, batch_chunks=2,
                      patience=3, max_iters=120, seed=11,
                      stop_when_frozen=True)):
        a = ClusteringEngine("kmeans", EngineConfig(**base)).fit(
            blobs, c0, h_star=1e-4)
        b = ClusteringEngine("kmeans", EngineConfig(
            prefetch=True, **base)).fit(blobs, c0, h_star=1e-4)
        assert int(a.n_iters) == int(b.n_iters)
        np.testing.assert_array_equal(np.asarray(a.params),
                                      np.asarray(b.params))
        np.testing.assert_array_equal(np.asarray(a.labels),
                                      np.asarray(b.labels))


def test_stats_wire_bytes_leaf_policy():
    """Analytic bytes mirror the reducer's leaf policy: int8 moves a
    matrix leaf at 1 byte/element + one f32 scale per row, vector and
    scalar leaves stay f32; at k=8 the per-row scales and the exact counts
    cost 8/d bytes per element, so the fp32/int8 ratio reaches 3× from
    d=32."""
    from repro.core.engine import get_algorithm, stats_wire_bytes
    params = jnp.zeros((8, 8), jnp.float32)
    stats = get_algorithm("kmeans").zero_stats(params)
    fp32 = stats_wire_bytes(stats, 8, "none")
    int8 = stats_wire_bytes(stats, 8, "int8_ef")
    # payloads before the ring factor: (64+8+1)·4 = 292 B vs
    # 64·1 + 8·4 scales + 8·4 (counts) + 4 (scalar J) = 132 B
    assert fp32 == (2 * 7 * 292) // 8 == 511
    assert int8 == (2 * 7 * 132) // 8 == 231
    wide = get_algorithm("kmeans").zero_stats(jnp.zeros((8, 32), jnp.float32))
    assert (stats_wire_bytes(wide, 8, "none")
            / stats_wire_bytes(wide, 8, "int8_ef")) >= 3.0
    assert stats_wire_bytes(stats, 1, "int8_ef") == 0   # no ring, no wire


def test_engine_config_unregistered_backend_fails_at_dispatch(blobs, c0):
    """Custom register_backend() names are legal in the config; a name no
    op registered fails loud at the first dispatch with the available
    list, not at construction."""
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=5, use_kernel=True, kernel_backend="mosaic"))
    with pytest.raises(NotImplementedError, match="no 'mosaic' backend"):
        eng.fit(blobs, c0)


def test_full_mode_rejects_minibatch_only_knobs():
    """mode='full' used to silently ignore batch_chunks/decay/seed/ema, so
    a CLI typo like --batch-chunks without --mode minibatch ran a plain
    full-sweep fit while looking like a minibatch run.  Fail loud instead
    (ISSUE 3 satellite)."""
    for kw in ({"batch_chunks": 3}, {"decay": 0.5}, {"seed": 7},
               {"ema": 0.5}):
        with pytest.raises(ValueError, match="minibatch-only"):
            EngineConfig(**kw)
    with pytest.raises(ValueError, match="minibatch-only"):
        EngineConfig(mode="full", batch_chunks=3, decay=0.5, seed=7)
    EngineConfig()                        # defaults stay valid
    EngineConfig(chunks=8)                # streaming-only full mode too


def test_fit_restarts_use_kernel_matches_xla_path(blobs):
    """ISSUE 4: the vmapped multi-restart driver routes through the kernels'
    restart grid axis (custom_vmap rule) — seed-for-seed parity with the
    non-kernel fleet, where it used to raise NotImplementedError."""
    key = jax.random.PRNGKey(7)
    ref = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, stop_when_frozen=True))
    ker = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, stop_when_frozen=True, use_kernel=True))
    a = ref.fit_restarts(blobs, key=key, k=K, restarts=3, h_star=1e-4)
    b = ker.fit_restarts(blobs, key=key, k=K, restarts=3, h_star=1e-4)
    assert int(a.best_index) == int(b.best_index)
    np.testing.assert_array_equal(np.asarray(a.n_iters),
                                  np.asarray(b.n_iters))
    np.testing.assert_allclose(a.objectives, b.objectives, rtol=1e-4)
    np.testing.assert_allclose(a.best.params, b.best.params,
                               rtol=1e-4, atol=1e-3)
    assert float((a.best.labels == b.best.labels).mean()) > 0.999


# The kernel and jnp paths sum the stats in different fp32 orders, so the
# two trajectories differ by ulps.  At h* = 1e-4 the minibatch stop on these
# blobs is decided at that scale: one restart takes a step whose update is
# a single ulp, which freezes the parameters (stop_when_frozen) on one path
# and not on the other, and the paired h sits within 1% of h* for dozens of
# iterations.  At 1e-3 the h stop decides, with no iterate that close.
MB_PARITY_H_STAR = 1e-3


def test_minibatch_use_kernel_matches_xla_path(blobs, c0):
    """ISSUE 4: mode='minibatch' composes with use_kernel=True via the
    gather-free statically-sliced subsample driver — identical stop
    iteration and params (within fp32 tolerance) to the jnp path, where it
    used to raise NotImplementedError at config time."""
    kw = dict(mode="minibatch", chunks=8, batch_chunks=2, patience=3,
              max_iters=300, stop_when_frozen=True)
    rx = ClusteringEngine("kmeans", EngineConfig(**kw)).fit(
        blobs, c0, h_star=MB_PARITY_H_STAR)
    rk = ClusteringEngine("kmeans", EngineConfig(use_kernel=True, **kw)).fit(
        blobs, c0, h_star=MB_PARITY_H_STAR)
    assert int(rk.n_iters) == int(rx.n_iters)
    np.testing.assert_allclose(rk.params, rx.params, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(float(rk.objective), float(rx.objective),
                               rtol=1e-5)


def test_minibatch_restarts_use_kernel_compose(blobs):
    """Both new kernel axes at once: per-restart minibatch draws dynamic-
    slice per-restart chunks (batched points AND batched params on the
    kernels' restart grid)."""
    kw = dict(mode="minibatch", chunks=8, batch_chunks=2, patience=3,
              max_iters=200, stop_when_frozen=True)
    key = jax.random.PRNGKey(5)
    a = ClusteringEngine("kmeans", EngineConfig(**kw)).fit_restarts(
        blobs, key=key, k=K, restarts=3, h_star=MB_PARITY_H_STAR)
    b = ClusteringEngine("kmeans", EngineConfig(
        use_kernel=True, **kw)).fit_restarts(
        blobs, key=key, k=K, restarts=3, h_star=MB_PARITY_H_STAR)
    assert int(a.best_index) == int(b.best_index)
    np.testing.assert_array_equal(np.asarray(a.n_iters),
                                  np.asarray(b.n_iters))
    np.testing.assert_allclose(a.objectives, b.objectives, rtol=1e-4)


# --------------------------------------------------------------------------
# mode="full" is bit-identical to the pre-PR engine (regression guard)
# --------------------------------------------------------------------------

# Goldens recorded from the engine at 7a77552 (pre-minibatch), CPU f32.
_GOLD_KM_ITERS = 2
# Re-pinned for jax 0.9: its CPU backend sums J's 1000 rows in another fp32
# order than jax 0.4 did (3033.8115234375 then).  Both lie ~7e-7 from the
# float64 J at the same final centroids (3033.8094), on either side of it.
_GOLD_KM_J = 3033.80712890625
_GOLD_EM_ITERS = 6
_GOLD_EM_LL = -5653.07080078125


def _golden_blobs():
    rng = np.random.default_rng(42)
    centers = np.array([[0, 0, 0], [8, 8, 8], [-8, 8, 0], [8, -8, 4]], float)
    x = np.concatenate([c + rng.normal(0, 1.0, (250, 3)) for c in centers])
    return jnp.asarray(x.astype(np.float32))


@pytest.mark.skipif(bool(os.environ.get("REPRO_FORCE_KERNEL_BACKEND")),
                    reason="goldens pin the jnp sweep's fp32 reduction "
                           "order; the forced kernel path accumulates "
                           "block-wise")
def test_full_mode_matches_pre_minibatch_goldens():
    """Adding mode/batch_chunks/decay/seed/ema to the engine state must not
    perturb the full-batch path: same iteration counts and (to fp32 ulp)
    the same objectives as the pre-PR engine on a pinned input."""
    x = _golden_blobs()
    c0 = jnp.asarray([[1., 1., 1.], [7., 7., 7.],
                      [-7., 7., 0.], [7., -7., 3.]], jnp.float32)
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, use_h_stop=True, stop_when_frozen=True))
    r = eng.fit(x, c0, h_star=1e-4)
    assert int(r.n_iters) == _GOLD_KM_ITERS
    np.testing.assert_allclose(float(r.objective), _GOLD_KM_J, rtol=1e-6)

    p0 = em_gmm.init_from_kmeans(x, c0)
    enge = ClusteringEngine("em", EngineConfig(max_iters=60))
    re_ = enge.fit(x, p0, h_star=1e-5)
    assert int(re_.n_iters) == _GOLD_EM_ITERS
    np.testing.assert_allclose(float(re_.objective), _GOLD_EM_LL, rtol=1e-6)


# --------------------------------------------------------------------------
# kmeans_fit_full: stop only when the centroids freeze (regression)
# --------------------------------------------------------------------------

def test_kmeans_full_runs_until_frozen():
    """fp32 J plateaus bit-for-bit (ΔJ < ulp(J) with J ~ N·B²) while the
    cluster boundary is still sweeping; the old h*=0/patience=1 stop quit on
    the plateau and returned a non-fixed-point.  Pin the fix: fit_full must
    land on a true Lloyd fixed point."""
    # 2×100 points: at 2×40 rows and b = 1e4 (the jax 0.4 dataset) jax
    # 0.9's fp32 J plateaus only on the step that reaches the fixed point
    b = 3e4
    base = np.arange(100.0)
    x = np.concatenate([np.stack([base, np.full(100, b)], 1),
                        np.stack([base, np.full(100, -b)], 1)])
    xj = jnp.asarray(x.astype(np.float32))
    c0 = jnp.asarray([[0.0, 0.0], [1.0, 0.0]], jnp.float32)

    # the plateau is real: the h-based path stops while centroids still move
    c_h, _, _, it_h = core.kmeans_fit_earlystop(xj, c0, 0.0, max_iters=500)
    c_h2, _, _ = core.kmeans_step(xj, c_h)
    assert not bool(jnp.all(c_h2 == c_h)), \
        "plateau scenario lost its teeth — rebuild the dataset"

    c_f, _, _, it_f = core.kmeans_fit_full(xj, c0, max_iters=500)
    c_f2, _, _ = core.kmeans_step(xj, c_f)
    assert bool(jnp.all(c_f2 == c_f)), "fit_full returned a non-fixed-point"
    assert int(it_f) > int(it_h)
    assert int(it_f) < 500                    # still terminates by freezing
