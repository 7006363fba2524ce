"""Kernel dispatch layer (ISSUE 4): registry resolution and override
hooks, kernel-vs-reference parity goldens on every backend available in
CI (interpret + xla at minimum), vmapped-restarts kernel vs ``vmap`` of
the reference, the GPU split-reduction grid checked under the
interpreter, and minibatch+kernel vs minibatch+XLA producing identical
stop iterations on the seeded blobs fixture."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.engine import ClusteringEngine, EngineConfig
from repro.kernels import dispatch, layout
from repro.kernels.kmeans_assign import ops as kops
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref
from repro.kernels.gmm_estep import ops as gops
from repro.kernels.gmm_estep.ref import gmm_estep_ref

K = 4

# every backend the CI host can actually execute (tpu/gpu need hardware)
CI_BACKENDS = [b for b in ("interpret", "xla")]


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(3)
    centers = np.array([[0, 0, 0], [8, 8, 8], [-8, 8, 0], [8, -8, 4]], float)
    x = np.concatenate([c + rng.normal(0, 1.0, (400, 3)) for c in centers])
    x = x[rng.permutation(len(x))]
    return jnp.asarray(x.astype(np.float32))


# --------------------------------------------------------------------------
# Registry mechanics
# --------------------------------------------------------------------------

def test_registry_lists_all_ops_and_backends():
    ops = dispatch.registered_ops()
    for name in ("kmeans_assign", "gmm_estep"):
        assert set(dispatch.KNOWN_BACKENDS) <= set(ops[name]), ops
    # every registered name resolves to that backend's own implementation
    op = dispatch.get_op("gmm_estep")
    for b in dispatch.KNOWN_BACKENDS:
        assert op.impl(b)[0] == b


def test_default_backend_resolution():
    # this suite runs on CPU (or any non-accelerator host): auto → interpret
    assert dispatch.resolve_backend(None, None) == dispatch.default_backend()
    assert dispatch.resolve_backend("xla") == "xla"
    assert dispatch.resolve_backend(None, interpret=True) == "interpret"
    # a name no op registered fails at the per-op lookup, with guidance
    with pytest.raises(NotImplementedError, match="no 'mosaic' backend"):
        dispatch.get_op("kmeans_assign").impl("mosaic")


def test_force_backend_context():
    before = dispatch.default_backend()
    with dispatch.force_backend("xla"):
        assert dispatch.default_backend() == "xla"
        with dispatch.force_backend("interpret"):
            assert dispatch.default_backend() == "interpret"
        assert dispatch.default_backend() == "xla"
    assert dispatch.default_backend() == before


def test_register_backend_hook_forces_any_path(monkeypatch):
    """Tests can route a public op through an arbitrary implementation."""
    calls = []
    # a backend brings its own tile policy; the fake borrows the xla one
    monkeypatch.setitem(layout.TILE_POLICIES, "fake",
                        layout.TILE_POLICIES["xla"])

    def fake(x, w, c, *, block_n):
        calls.append(block_n)
        return dispatch.get_op("kmeans_assign").impl("xla")[1](
            x, w, c, block_n=block_n)

    dispatch.register_backend("kmeans_assign", "fake", fake)
    try:
        x = jnp.ones((32, 3), jnp.float32)
        c = jnp.asarray([[0.0, 0, 0], [2, 2, 2]], jnp.float32)
        labels, _, counts, _ = kops.kmeans_assign(x, c, backend="fake")
        assert calls, "registered hook was not dispatched to"
        assert float(jnp.sum(counts)) == 32
    finally:
        dispatch.get_op("kmeans_assign")._impls.pop("fake")
    with pytest.raises(NotImplementedError, match="no 'fake' backend"):
        kops.kmeans_assign(x, c, backend="fake")


@pytest.mark.skipif(bool(os.environ.get("REPRO_FORCE_KERNEL_BACKEND")),
                    reason="the env hook pins the backend before the "
                           "force_backend context can")
def test_engine_config_resolves_backend_eagerly():
    """The concrete backend is baked into the static config at
    construction — a dispatch.force_backend() active NOW is honoured, and
    the jit caches (keyed on the config) can never cross backends."""
    with dispatch.force_backend("xla"):
        cfg = EngineConfig(use_kernel=True)
    assert cfg.kernel_backend == "xla"
    cfg2 = EngineConfig(use_kernel=True)
    assert cfg2.kernel_backend == dispatch.default_backend()
    assert cfg != cfg2          # distinct jit cache entries


# --------------------------------------------------------------------------
# Parity goldens: op vs reference on every CI-runnable backend
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", CI_BACKENDS)
@pytest.mark.parametrize("n,d,k", [(777, 11, 10), (64, 2, 2), (1024, 3, 6)])
def test_kmeans_assign_backend_parity(backend, n, d, k):
    rng = np.random.default_rng(n + d)
    x = jnp.asarray(rng.normal(0, 10, (n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 10, (k, d)).astype(np.float32))
    l1, s1, n1, j1 = kops.kmeans_assign(x, c, backend=backend)
    l2, s2, n2, j2 = kmeans_assign_ref(x, c)
    assert (l1 == l2).all()
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=1e-2)
    np.testing.assert_allclose(n1, n2, rtol=0)
    np.testing.assert_allclose(j1, j2[0], rtol=2e-5)


@pytest.mark.parametrize("backend", CI_BACKENDS)
def test_gmm_estep_backend_parity(backend):
    rng = np.random.default_rng(0)
    n, d, k = 1000, 4, 8
    x = jnp.asarray(rng.normal(0, 3, (n, d)).astype(np.float32))
    mu = jnp.asarray(rng.normal(0, 3, (k, d)).astype(np.float32))
    var = jnp.asarray(rng.uniform(0.5, 4, (k, d)).astype(np.float32))
    lw = jnp.log(jnp.full((k,), 1.0 / k, jnp.float32))
    o1 = gops.gmm_estep(x, mu, var, lw, backend=backend)
    o2 = gmm_estep_ref(x, mu, var, lw)
    assert (o1[0] == o2[0]).all()
    np.testing.assert_allclose(o1[1], o2[1][0], rtol=1e-5)
    np.testing.assert_allclose(o1[2], o2[2], rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(o1[3], o2[3], rtol=2e-4, atol=2e-2)


@pytest.mark.parametrize("backend", CI_BACKENDS)
def test_masked_rows_drop_from_stats(backend):
    """The mask operand (engine chunk padding / subsample weighting): rows
    with weight 0 are labelled -1 and contribute nothing."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 5, (200, 3)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 5, (4, 3)).astype(np.float32))
    m = jnp.asarray((np.arange(200) < 150).astype(np.float32))
    lm, sm, nm, jm = kops.kmeans_assign(x, c, mask=m, backend=backend)
    lt, st, nt, jt = kops.kmeans_assign(x[:150], c, backend=backend)
    assert (np.asarray(lm)[150:] == -1).all()
    assert (np.asarray(lm)[:150] == np.asarray(lt)).all()
    np.testing.assert_allclose(sm, st, rtol=2e-4, atol=1e-2)
    np.testing.assert_allclose(nm, nt, rtol=0)
    np.testing.assert_allclose(jm, jt, rtol=1e-5)


# --------------------------------------------------------------------------
# Restart axis: vmapped kernel vs vmap of the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", CI_BACKENDS)
def test_vmapped_restarts_kernel_vs_vmapped_reference(backend):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 8, (513, 5)).astype(np.float32))
    cr = jnp.asarray(rng.normal(0, 8, (3, 6, 5)).astype(np.float32))
    vm = jax.vmap(lambda c: kops.kmeans_assign(x, c, backend=backend))(cr)
    rf = jax.vmap(lambda c: kmeans_assign_ref(x, c))(cr)
    assert (vm[0] == rf[0]).all()
    np.testing.assert_allclose(vm[1], rf[1], rtol=2e-4, atol=1e-2)
    np.testing.assert_allclose(vm[3], rf[3][:, 0], rtol=2e-5)

    mu = jnp.asarray(rng.normal(0, 2, (3, 6, 5)).astype(np.float32))
    var = jnp.asarray(rng.uniform(0.5, 2, (3, 6, 5)).astype(np.float32))
    lw = jnp.broadcast_to(jnp.log(jnp.full((6,), 1 / 6, jnp.float32)), (3, 6))
    gv = jax.vmap(lambda m, v, w: gops.gmm_estep(x, m, v, w,
                                                 backend=backend))(mu, var, lw)
    gr = jax.vmap(lambda m, v, w: gmm_estep_ref(x, m, v, w))(mu, var, lw)
    assert (gv[0] == gr[0]).all()
    np.testing.assert_allclose(gv[1], gr[1][:, 0], rtol=1e-5)


def test_vmapped_points_and_params():
    """Per-restart minibatch draws batch the points too: both x and the
    params ride the restart grid."""
    rng = np.random.default_rng(2)
    xr = jnp.asarray(rng.normal(0, 5, (2, 100, 3)).astype(np.float32))
    cr = jnp.asarray(rng.normal(0, 5, (2, 4, 3)).astype(np.float32))
    vm = jax.vmap(kops.kmeans_assign)(xr, cr)
    for r in range(2):
        lr, sr, nr, jr = kmeans_assign_ref(xr[r], cr[r])
        assert (vm[0][r] == lr).all()
        np.testing.assert_allclose(vm[3][r], jr[0], rtol=2e-5)


def test_gpu_split_reduction_grid_matches_reference():
    """The GPU backend's parallel-grid variant (per-step partials, no
    cross-step accumulation) — its math checked under the interpreter with
    the GPU tile policy, since CI has no GPU."""
    from repro.kernels.kmeans_assign.kernel import kmeans_assign_kernel
    rng = np.random.default_rng(4)
    n, d, k = 700, 5, 6
    x = jnp.asarray(rng.normal(0, 5, (n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 5, (k, d)).astype(np.float32))
    pol = layout.tile_policy("gpu")
    bn = pol.block_for(n)
    npad = layout.round_up(n, bn)
    dpad = pol.align_d(d)
    kpad = pol.align_k(k)
    # Triton block shapes must be powers of two — the gpu policy's padded
    # dims must come out pow2 even for awkward inputs
    assert all(v & (v - 1) == 0 for v in (bn, dpad, kpad)), (bn, dpad, kpad)
    xp = jnp.pad(x, ((0, npad - n), (0, dpad - d)))[None]
    wp = jnp.pad(jnp.ones((n,), jnp.float32), (0, npad - n))[None]
    cp = jnp.pad(c, ((0, kpad - k), (0, dpad - d)))
    cp = cp.at[k:, :].set(1e9)[None]
    lab, sums, counts, j = kmeans_assign_kernel(
        xp, wp, cp, block_n=bn, interpret=True, accumulate=False)
    assert sums.shape[1] == npad // bn        # one partial per grid step
    l2, s2, n2, j2 = kmeans_assign_ref(x, c)
    assert (lab[0, :n] == l2).all()
    np.testing.assert_allclose(jnp.sum(sums, 1)[0, :k, :d], s2,
                               rtol=2e-4, atol=1e-2)
    np.testing.assert_allclose(jnp.sum(counts, 1)[0, :k], n2, rtol=0)
    np.testing.assert_allclose(jnp.sum(j, 1)[0, 0], j2[0], rtol=2e-5)


def test_gpu_split_reduction_grid_gmm_matches_reference():
    """Same guard for the gmm_estep accumulate=False variant: per-step
    partials + the wrapper's sum must reproduce the reference."""
    from repro.kernels.gmm_estep.kernel import gmm_estep_kernel
    rng = np.random.default_rng(7)
    n, d, k = 700, 5, 6
    x = jnp.asarray(rng.normal(0, 3, (n, d)).astype(np.float32))
    mu = jnp.asarray(rng.normal(0, 3, (k, d)).astype(np.float32))
    var = jnp.asarray(rng.uniform(0.5, 4, (k, d)).astype(np.float32))
    lw = jnp.log(jnp.full((k,), 1.0 / k, jnp.float32))
    pol = layout.tile_policy("gpu")
    bn = pol.block_for(n)
    npad = layout.round_up(n, bn)
    dpad = pol.align_d(d)
    kpad = pol.align_k(k)
    inv_var = 1.0 / var
    b_op = mu * inv_var
    const = (lw - 0.5 * (jnp.sum(mu ** 2 * inv_var, -1)
                         + jnp.sum(jnp.log(var), -1)
                         + d * 1.8378770664093453))
    xp = jnp.pad(x, ((0, npad - n), (0, dpad - d)))[None]
    wp = jnp.pad(jnp.ones((n,), jnp.float32), (0, npad - n))[None]
    ap = jnp.pad(inv_var, ((0, kpad - k), (0, dpad - d)))[None]
    bp = jnp.pad(b_op, ((0, kpad - k), (0, dpad - d)))[None]
    cp = jnp.pad(const, (0, kpad - k), constant_values=-1e30)[None]
    lab, ll, rs, rx, rx2 = gmm_estep_kernel(
        xp, wp, ap, bp, cp, block_n=bn, interpret=True, accumulate=False)
    assert ll.shape[1] == npad // bn          # one partial per grid step
    o2 = gmm_estep_ref(x, mu, var, lw)
    assert (lab[0, :n] == o2[0]).all()
    np.testing.assert_allclose(jnp.sum(ll, 1)[0, 0], o2[1][0], rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(rs, 1)[0, :k], o2[2],
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(jnp.sum(rx, 1)[0, :k, :d], o2[3],
                               rtol=2e-4, atol=2e-2)
    np.testing.assert_allclose(jnp.sum(rx2, 1)[0, :k, :d], o2[4],
                               rtol=2e-4, atol=2e-1)


# --------------------------------------------------------------------------
# Engine-level: minibatch+kernel vs minibatch+XLA identical stop iterations
# --------------------------------------------------------------------------

def test_minibatch_kernel_vs_xla_identical_stop(blobs):
    c0 = core.kmeans_plus_plus_init(jax.random.PRNGKey(0), blobs, K)
    kw = dict(mode="minibatch", chunks=8, batch_chunks=2, patience=3,
              max_iters=300, stop_when_frozen=True, use_kernel=True)
    ri = ClusteringEngine("kmeans", EngineConfig(
        kernel_backend="interpret", **kw)).fit(blobs, c0, h_star=1e-4)
    rx = ClusteringEngine("kmeans", EngineConfig(
        kernel_backend="xla", **kw)).fit(blobs, c0, h_star=1e-4)
    assert int(ri.n_iters) == int(rx.n_iters)
    np.testing.assert_allclose(ri.params, rx.params, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(float(ri.objective), float(rx.objective),
                               rtol=1e-5)


def test_chunked_entry_points_dispatch_per_backend(blobs):
    c = jnp.asarray(np.random.default_rng(6).normal(0, 5, (K, 3)),
                    jnp.float32)
    a = kops.kmeans_assign_chunked(blobs, c, chunks=3, backend="interpret")
    b = kops.kmeans_assign_chunked(blobs, c, chunks=3, backend="xla")
    assert (a[0] == b[0]).all()
    np.testing.assert_allclose(a[3], b[3], rtol=1e-5)
