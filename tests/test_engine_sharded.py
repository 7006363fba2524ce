"""Sharded engine drivers (ISSUE 3): fit_sharded / fit_restarts_sharded
under an in-process 8-device ("data",) mesh must reproduce the
single-device engine — the globally-chunked layout makes every shard's
local chunk a row-slice of the global chunk, so the seeded draw selects
the same subsample and the whole trajectory matches up to fp32 reduction
order (params within tolerance, identical stop iteration).  Since ISSUE 4
the same drivers serve use_kernel=True (per-chunk masked kernel calls
through the backend registry) — parity-tested below for full, minibatch
and vmapped-restart fleets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import em_gmm
from repro.core.engine import ClusteringEngine, EngineConfig

K = 4

# one minibatch recipe for the whole file: 2-of-8 chunks per iteration
MB = dict(mode="minibatch", chunks=8, batch_chunks=2, patience=3,
          max_iters=300, seed=11)


def _data_mesh(mesh8):
    """The sharded drivers shard over the ("pod", "data") axes; mesh8 only
    asserts the 8-device substrate is up (its axis is named "d")."""
    del mesh8
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(1)
    centers = np.array([[0, 0, 0], [9, 9, 9], [-9, 9, 0], [9, -9, 5]], float)
    x = np.concatenate([c + rng.normal(0, 1.0, (512, 3)) for c in centers])
    x = x[rng.permutation(len(x))]             # unbias the chunk contents
    return jnp.asarray(x.astype(np.float32))   # N=2048 = 8 devices · 256


@pytest.fixture(scope="module")
def c0(blobs):
    return core.kmeans_plus_plus_init(jax.random.PRNGKey(0), blobs, K)


# --------------------------------------------------------------------------
# Single-fit parity: sharded minibatch == single-device minibatch
# --------------------------------------------------------------------------

def test_sharded_minibatch_kmeans_matches_single_device(blobs, c0, mesh8):
    eng = ClusteringEngine("kmeans", EngineConfig(stop_when_frozen=True,
                                                  **MB))
    ref = eng.fit(blobs, c0, h_star=1e-4)
    res = eng.fit_sharded(blobs, c0, _data_mesh(mesh8), h_star=1e-4)
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params, ref.params, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(res.objective), float(ref.objective),
                               rtol=1e-5)
    assert res.labels.shape == ref.labels.shape
    assert float((res.labels == ref.labels).mean()) > 0.999


def test_sharded_minibatch_em_matches_single_device(blobs, c0, mesh8):
    p0 = em_gmm.init_from_kmeans(blobs, c0)
    eng = ClusteringEngine("em", EngineConfig(**MB))
    ref = eng.fit(blobs, p0, h_star=1e-4)
    res = eng.fit_sharded(blobs, p0, _data_mesh(mesh8), h_star=1e-4)
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params.means, ref.params.means,
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(res.params.var, ref.params.var,
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(float(res.objective), float(ref.objective),
                               rtol=1e-5)
    assert float((res.labels == ref.labels).mean()) > 0.999


def test_sharded_minibatch_uneven_rows(mesh8):
    """N not divisible by chunks x devices: the padded chunk layout must
    keep every real row (no shard_points-style truncation) and still match
    the single-device fit."""
    rng = np.random.default_rng(2)
    x = np.concatenate([c + rng.normal(0, 0.8, (333, 2))
                        for c in ([0, 0], [10, 10], [-10, 6], [9, -9])])
    x = jnp.asarray(x[rng.permutation(len(x))].astype(np.float32))  # N=1332
    c0u = core.kmeans_plus_plus_init(jax.random.PRNGKey(3), x, K)
    eng = ClusteringEngine("kmeans", EngineConfig(stop_when_frozen=True,
                                                  **MB))
    ref = eng.fit(x, c0u, h_star=1e-4)
    res = eng.fit_sharded(x, c0u, _data_mesh(mesh8), h_star=1e-4)
    assert res.labels.shape[0] == x.shape[0]
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params, ref.params, rtol=1e-4, atol=1e-4)
    assert float((res.labels == ref.labels).mean()) > 0.999


def test_sharded_full_mode_matches_single_device(blobs, c0, mesh8):
    """fit_sharded is mode-agnostic: full-batch chunk sweeps under the same
    layout agree with the flat single-device path."""
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=4, stop_when_frozen=True))
    ref = eng.fit(blobs, c0, h_star=1e-4)
    res = eng.fit_sharded(blobs, c0, _data_mesh(mesh8), h_star=1e-4)
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params, ref.params, rtol=1e-5, atol=1e-4)
    assert float((res.labels == ref.labels).mean()) > 0.999


# --------------------------------------------------------------------------
# Multi-restart parity: vmapped restarts inside shard_map (vmap-of-psum)
# --------------------------------------------------------------------------

def test_sharded_restarts_minibatch_best_j_parity(blobs, mesh8):
    """--restarts 4 --shard: per-restart chunk streams + stop masks under
    shard_map must reproduce the unsharded fit_restarts fleet — same best
    index, objectives within fp tolerance, stop iterations within the one
    boundary step fp reduction order can flip."""
    eng = ClusteringEngine("kmeans", EngineConfig(stop_when_frozen=True,
                                                  **MB))
    params0 = eng.init_restarts(jax.random.PRNGKey(9), blobs, K, 4)
    ref = eng.fit_restarts(blobs, params0, h_star=1e-4)
    rr = eng.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                  h_star=1e-4)
    assert rr.objectives.shape == (4,)
    assert int(rr.best_index) == int(ref.best_index)
    np.testing.assert_allclose(rr.objectives, ref.objectives, rtol=1e-3)
    np.testing.assert_allclose(float(rr.best.objective),
                               float(ref.best.objective), rtol=1e-4)
    assert np.max(np.abs(np.asarray(rr.n_iters, np.int64)
                         - np.asarray(ref.n_iters, np.int64))) <= 1
    np.testing.assert_allclose(rr.best.params, ref.best.params,
                               rtol=1e-3, atol=1e-2)
    assert float((rr.best.labels == ref.best.labels).mean()) > 0.999


def test_sharded_restarts_full_mode_parity(blobs, mesh8):
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=4, stop_when_frozen=True))
    params0 = eng.init_restarts(jax.random.PRNGKey(2), blobs, K, 3)
    ref = eng.fit_restarts(blobs, params0, h_star=1e-4)
    rr = eng.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                  h_star=1e-4)
    assert int(rr.best_index) == int(ref.best_index)
    np.testing.assert_array_equal(np.asarray(rr.n_iters),
                                  np.asarray(ref.n_iters))
    np.testing.assert_allclose(rr.objectives, ref.objectives, rtol=1e-5)
    np.testing.assert_allclose(rr.best.params, ref.best.params,
                               rtol=1e-5, atol=1e-4)


def test_sharded_restarts_em_runs(blobs, c0, mesh8):
    """EM restarts under shard_map: pytree (GMMParams) specs + soft-count
    stepwise updates compose; the best restart must carry the max loglik."""
    eng = ClusteringEngine("em", EngineConfig(**MB))
    rr = eng.fit_restarts_sharded(blobs, mesh=_data_mesh(mesh8),
                                  key=jax.random.PRNGKey(4), k=K, restarts=3,
                                  h_star=1e-4)
    best = int(np.argmax(np.asarray(rr.objectives)))
    assert int(rr.best_index) == best
    np.testing.assert_allclose(float(rr.best.objective),
                               float(rr.objectives[best]))
    assert rr.best.labels.shape[0] == blobs.shape[0]


# --------------------------------------------------------------------------
# Guard rails
# --------------------------------------------------------------------------

def test_fit_sharded_use_kernel_matches_single_device(blobs, c0, mesh8):
    """ISSUE 4: the sharded chunk layout streams through the dispatched
    kernel ops (the chunk mask rides the kernels' weight operand), where it
    used to raise NotImplementedError — full-mode parity with the unsharded
    kernel fit."""
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=4, stop_when_frozen=True, use_kernel=True))
    ref = eng.fit(blobs, c0, h_star=1e-4)
    res = eng.fit_sharded(blobs, c0, _data_mesh(mesh8), h_star=1e-4)
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params, ref.params, rtol=1e-4, atol=1e-4)
    assert float((res.labels == ref.labels).mean()) > 0.999


def test_fit_sharded_minibatch_use_kernel_matches_single_device(
        blobs, c0, mesh8):
    """Minibatch + kernel + shard_map: the replicated draw dynamic-slices
    the same global chunks on every shard and the psum'd kernel stats drive
    the paired stop — same trajectory as the unsharded kernel fit."""
    eng = ClusteringEngine("kmeans", EngineConfig(
        stop_when_frozen=True, use_kernel=True, **MB))
    ref = eng.fit(blobs, c0, h_star=1e-4)
    res = eng.fit_sharded(blobs, c0, _data_mesh(mesh8), h_star=1e-4)
    assert int(res.n_iters) == int(ref.n_iters)
    np.testing.assert_allclose(res.params, ref.params, rtol=1e-4, atol=1e-4)


def test_sharded_restarts_use_kernel_parity(blobs, mesh8):
    """vmap-of-psum over per-chunk kernel calls inside shard_map: the
    restart fleet's custom_vmap routing survives the mesh."""
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=4, stop_when_frozen=True, use_kernel=True))
    params0 = eng.init_restarts(jax.random.PRNGKey(2), blobs, K, 3)
    ref = eng.fit_restarts(blobs, params0, h_star=1e-4)
    rr = eng.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                  h_star=1e-4)
    assert int(rr.best_index) == int(ref.best_index)
    np.testing.assert_array_equal(np.asarray(rr.n_iters),
                                  np.asarray(ref.n_iters))
    np.testing.assert_allclose(rr.objectives, ref.objectives, rtol=1e-4)


def test_fit_sharded_needs_data_axis(blobs, c0, mesh8):
    mesh = jax.make_mesh((8,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    eng = ClusteringEngine("kmeans", EngineConfig(max_iters=10, chunks=4))
    with pytest.raises(ValueError, match="no data axis"):
        eng.fit_sharded(blobs, c0, mesh)


# --------------------------------------------------------------------------
# int8-EF compressed stats reductions (ISSUE 7): the sharded drivers with
# stats_compression="int8_ef" ride the ppermute ring + error feedback in
# the centred compression basis — the Eq. 7 stop must track the fp32 psum
# trajectory (the tentpole parity claim)
# --------------------------------------------------------------------------

MB_INT8 = dict(MB, stats_compression="int8_ef")


def test_sharded_int8_minibatch_kmeans_stop_parity(blobs, c0, mesh8):
    """int8 ring vs fp32 psum on the same sharded minibatch fit: identical
    stop iteration (the centred basis shrinks the quantisation error with
    the residual parameter motion, so h stays on the fp32 trajectory)."""
    ref = ClusteringEngine("kmeans", EngineConfig(**MB)).fit_sharded(
        blobs, c0, _data_mesh(mesh8), h_star=1e-3)
    res = ClusteringEngine("kmeans", EngineConfig(**MB_INT8)).fit_sharded(
        blobs, c0, _data_mesh(mesh8), h_star=1e-3)
    assert abs(int(res.n_iters) - int(ref.n_iters)) <= 1, \
        (int(res.n_iters), int(ref.n_iters))
    np.testing.assert_allclose(res.params, ref.params, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(float(res.objective), float(ref.objective),
                               rtol=1e-2)
    assert float((res.labels == ref.labels).mean()) > 0.99


def test_sharded_int8_em_close(blobs, c0, mesh8):
    """EM's variance stats are the catastrophic-cancellation case the
    centred basis exists for: raw int8 second moments turn 1% wire error
    into ~80% variance error; centred, the fit stays within a couple of
    boundary iterations and the loglik matches to fp noise."""
    p0 = em_gmm.init_from_kmeans(blobs, c0)
    ref = ClusteringEngine("em", EngineConfig(**MB)).fit_sharded(
        blobs, p0, _data_mesh(mesh8), h_star=1e-3)
    res = ClusteringEngine("em", EngineConfig(**MB_INT8)).fit_sharded(
        blobs, p0, _data_mesh(mesh8), h_star=1e-3)
    assert abs(int(res.n_iters) - int(ref.n_iters)) <= 2, \
        (int(res.n_iters), int(ref.n_iters))
    np.testing.assert_allclose(float(res.objective), float(ref.objective),
                               rtol=1e-3)


def test_sharded_int8_em_full_mode_close(blobs, c0, mesh8):
    """Full-batch EM under the int8 ring: one int8 scale per component row
    (a leaf-wide scale rounded the small components' moments to zero and
    collapsed them to zero weight and variance) and second moments
    centred on the current variances (which lets the rounding shrink as
    the fit converges).  The compressed fit's h decays a few iterations
    behind the fp32 fit's below ~1e-4 (the error-feedback residual is
    still draining), so the stop is compared at h* = 1e-3."""
    p0 = em_gmm.init_from_kmeans(blobs, c0)
    cfg = dict(max_iters=100, chunks=8)
    ref = ClusteringEngine("em", EngineConfig(**cfg)).fit_sharded(
        blobs, p0, _data_mesh(mesh8), h_star=1e-3)
    res = ClusteringEngine("em", EngineConfig(
        stats_compression="int8_ef", **cfg)).fit_sharded(
        blobs, p0, _data_mesh(mesh8), h_star=1e-3)
    assert abs(int(res.n_iters) - int(ref.n_iters)) <= 1, \
        (int(res.n_iters), int(ref.n_iters))
    np.testing.assert_allclose(float(res.objective), float(ref.objective),
                               rtol=1e-3)
    # no component collapsed (a leaf-wide scale left two at weight 0)
    assert float(np.exp(np.asarray(res.params.log_w)).min()) > 0.01


def test_sharded_int8_restarts_best_agree(blobs, mesh8):
    """Per-restart EF state threads through the vmapped while_loop carry:
    the compressed fleet picks the same winner as the fp32 fleet."""
    eng = ClusteringEngine("kmeans", EngineConfig(**MB))
    eng8 = ClusteringEngine("kmeans", EngineConfig(**MB_INT8))
    params0 = eng.init_restarts(jax.random.PRNGKey(9), blobs, K, 4)
    ref = eng.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                   h_star=1e-3)
    rr = eng8.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                   h_star=1e-3)
    assert int(rr.best_index) == int(ref.best_index)
    np.testing.assert_allclose(rr.objectives, ref.objectives, rtol=1e-2)
    assert np.max(np.abs(np.asarray(rr.n_iters, np.int64)
                         - np.asarray(ref.n_iters, np.int64))) <= 2


def test_sharded_int8_full_mode_runs(blobs, c0, mesh8):
    """Full-sweep mode under compression: the whole-dataset stats ride the
    ring too (not just minibatch draws)."""
    cfg = EngineConfig(max_iters=100, chunks=4,
                       stats_compression="int8_ef")
    ref = ClusteringEngine("kmeans", EngineConfig(
        max_iters=100, chunks=4)).fit_sharded(
        blobs, c0, _data_mesh(mesh8), h_star=1e-3)
    res = ClusteringEngine("kmeans", cfg).fit_sharded(
        blobs, c0, _data_mesh(mesh8), h_star=1e-3)
    assert abs(int(res.n_iters) - int(ref.n_iters)) <= 1
    assert float((res.labels == ref.labels).mean()) > 0.99


def test_sharded_int8_wire_is_int8(mesh8):
    """The compiled reduction moves s8 through collective-permute — the
    compression must survive jit/while_loop staging, not silently promote
    back to f32 psum."""
    import re
    from functools import partial
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core.engine import _stats_reducer, get_algorithm

    alg = get_algorithm("kmeans")
    cfg = EngineConfig(axis_name="d", stats_axis_size=8,
                       stats_compression="int8_ef")
    init_ef, reduce_stats = _stats_reducer(alg, cfg)
    params = jnp.zeros((K, 3), jnp.float32)
    stats = alg.zero_stats(params)
    ef = init_ef(stats)

    def f(stats, ef):
        return reduce_stats(stats, ef, params)

    g = shard_map(f, mesh=mesh8,
                  in_specs=(jax.tree.map(lambda _: P(), stats),
                            jax.tree.map(lambda _: P(), ef)),
                  out_specs=(jax.tree.map(lambda _: P(), stats),
                             jax.tree.map(lambda _: P(), ef)),
                  check_vma=False)
    hlo = jax.jit(g).lower(stats, ef).compile().as_text()
    assert "collective-permute" in hlo
    assert re.search(r"s8\[[^\]]*\][^=\n]*collective-permute", hlo) \
        or re.search(r"collective-permute[^\n]*s8\[", hlo), \
        "no s8 collective-permute in compiled reduction"


def test_sharded_prefetch_bit_identical(blobs, c0, mesh8):
    """prefetch=True only reorders loads (same chunk order, same adds):
    the sharded fit must be bit-identical, full and minibatch."""
    for base in (dict(max_iters=60, chunks=4, stop_when_frozen=True), MB):
        a = ClusteringEngine("kmeans", EngineConfig(**base)).fit_sharded(
            blobs, c0, _data_mesh(mesh8), h_star=1e-4)
        b = ClusteringEngine("kmeans", EngineConfig(
            prefetch=True, **base)).fit_sharded(
            blobs, c0, _data_mesh(mesh8), h_star=1e-4)
        assert int(a.n_iters) == int(b.n_iters)
        np.testing.assert_array_equal(np.asarray(a.params),
                                      np.asarray(b.params))
        np.testing.assert_array_equal(np.asarray(a.labels),
                                      np.asarray(b.labels))


# --------------------------------------------------------------------------
# Trace harvesting under shard_map (ISSUE 5): psum'd stats make the
# recorded (J, h, params) history replicated and device-count invariant
# --------------------------------------------------------------------------

def test_sharded_trace_matches_single_device(blobs, c0, mesh8):
    eng = ClusteringEngine("kmeans", EngineConfig(stop_when_frozen=True,
                                                  trace=True, **MB))
    ref = eng.fit(blobs, c0, h_star=1e-4)
    res = eng.fit_sharded(blobs, c0, _data_mesh(mesh8), h_star=1e-4)
    n = int(ref.n_iters)
    assert int(res.n_iters) == n
    np.testing.assert_array_equal(np.asarray(res.trace.mask),
                                  np.asarray(ref.trace.mask))
    np.testing.assert_allclose(np.asarray(res.trace.objectives)[:n],
                               np.asarray(ref.trace.objectives)[:n],
                               rtol=1e-4)
    # h is a difference of nearly-equal J's over J: fp32 psum reduction
    # order shows up as absolute noise around 1e-6, so bound it absolutely
    np.testing.assert_allclose(np.asarray(res.trace.h)[:n],
                               np.asarray(ref.trace.h)[:n],
                               rtol=0.05, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res.trace.params)[:n],
                               np.asarray(ref.trace.params)[:n],
                               rtol=1e-4, atol=1e-4)


def test_sharded_restart_traces_replicated(blobs, mesh8):
    eng = ClusteringEngine("kmeans", EngineConfig(
        max_iters=60, chunks=4, stop_when_frozen=True, trace=True))
    params0 = eng.init_restarts(jax.random.PRNGKey(3), blobs, K, 3)
    ref = eng.fit_restarts(blobs, params0, h_star=1e-4)
    rr = eng.fit_restarts_sharded(blobs, params0, _data_mesh(mesh8),
                                  h_star=1e-4)
    np.testing.assert_array_equal(np.asarray(rr.traces.mask.sum(axis=1),
                                             np.int32),
                                  np.asarray(rr.n_iters))
    np.testing.assert_allclose(np.asarray(rr.traces.objectives),
                               np.asarray(ref.traces.objectives),
                               rtol=1e-4, atol=1e-4)
