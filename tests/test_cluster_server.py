"""Cluster assignment server (ISSUE 6): bucket-padded continuous batching,
strict provenance admission, and bit-for-bit parity with the engine."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClusterArtifact, ClusteringEngine, EngineConfig,
                        GMMParams, ProvenanceMismatchError, fit_longtail)
from repro.core.longtail_train import config_fingerprint
from repro.kernels.layout import bucket_for, pad_to_bucket
from repro.serving import (AssignRequest, ClusterServer, FitRequest,
                           ModelRegistry)

K, D = 3, 4
BUCKETS = (32, 128, 512)


def _model_for(cfg, algorithm="kmeans"):
    """A cheap stop-model with real provenance (synthetic quadratic tail)."""
    r = np.linspace(0.3, 1.0, 50)
    h = 1.8 - 3.6 * r + 1.8 * r * r
    return fit_longtail([(r, h)], algorithm=algorithm, dataset="t",
                        family="quadratic",
                        engine_config=config_fingerprint(cfg))


def _kmeans_artifact(name, cfg, seed=0):
    rng = np.random.default_rng(seed)
    return ClusterArtifact(
        name=name, algorithm="kmeans",
        params=rng.normal(0, 4, (K, D)).astype(np.float32),
        model=_model_for(cfg, "kmeans"))


MB_CFG = EngineConfig(mode="minibatch", chunks=8, batch_chunks=2, patience=3,
                      max_iters=40)
FULL_CFG = EngineConfig(max_iters=40)


@pytest.fixture()
def server():
    registry = ModelRegistry(fit_steps=10)
    k1 = registry.register(_kmeans_artifact("mb", MB_CFG, seed=0))
    k2 = registry.register(_kmeans_artifact("full", FULL_CFG, seed=1))
    return ClusterServer(registry, buckets=BUCKETS), k1, k2


def _batch(n, seed):
    return np.random.default_rng(seed).normal(0, 4, (n, D)).astype(np.float32)


def test_served_labels_match_engine_bit_for_bit(server):
    srv, k1, k2 = server
    for key, cfg, seed in ((k1, MB_CFG, 3), (k2, FULL_CFG, 4)):
        x = _batch(77, seed)
        srv.submit(AssignRequest(x=x, model_key=key, rid=seed))
        out = srv.drain()
        entry = srv.registry[key]
        eng = ClusteringEngine("kmeans", cfg)
        _, ref_labels, _ = eng.step(x, entry.params)
        np.testing.assert_array_equal(out[seed], np.asarray(ref_labels))
        assert out[seed].shape == (77,)          # padding stripped


def test_mixed_sizes_pack_into_one_bucket_batch(server):
    """Several small requests across two models drain correctly: each rid
    gets its own slice back, equal to serving it alone."""
    srv, k1, k2 = server
    sizes = [5, 31, 12, 64, 3]
    for i, n in enumerate(sizes):
        srv.submit(AssignRequest(x=_batch(n, 100 + i),
                                 model_key=(k1 if i % 2 == 0 else k2),
                                 rid=i))
    out = srv.drain()
    assert set(out) == set(range(len(sizes)))
    for i, n in enumerate(sizes):
        key = k1 if i % 2 == 0 else k2
        entry = srv.registry[key]
        x = _batch(n, 100 + i)
        bucket = bucket_for(n, BUCKETS)
        xp, mask = pad_to_bucket(x, bucket)
        solo, _ = entry.assign(xp, mask, entry.params)
        np.testing.assert_array_equal(out[i], np.asarray(solo)[:n])


def test_bucket_padding_never_changes_compiled_shapes(server):
    """The compile-count probe: many distinct batch sizes, but the jit
    cache only grows with the number of distinct BUCKETS served."""
    srv, k1, _ = server
    entry = srv.registry[k1]
    assert entry.assign._cache_size() == 0
    buckets_used = set()
    for i, n in enumerate([3, 9, 17, 30, 32, 40, 100, 128, 200, 500]):
        srv.submit(AssignRequest(x=_batch(n, 200 + i), model_key=k1,
                                 rid=1000 + i))
        srv.drain()                    # one batch per drain: bucket_for(n)
        buckets_used.add(bucket_for(n, BUCKETS))
        assert entry.assign._cache_size() == len(buckets_used)
    assert buckets_used == set(BUCKETS)     # the probe exercised all three


def test_provenance_mismatch_is_rejected_loudly():
    registry = ModelRegistry()
    art = _kmeans_artifact("mb", MB_CFG)
    with pytest.raises(ProvenanceMismatchError) as ei:
        registry.register(art, overrides={"mode": "full"})
    assert "mode" in ei.value.diff
    assert registry.keys() == []            # nothing half-registered
    # the same artifact registers cleanly under its stamped regime
    registry.register(art)
    assert len(registry.keys()) == 1


def test_kernel_artifact_serves_on_the_platform_backend():
    """An artifact fitted with use_kernel on one platform is served by the
    compiled kernel of another: its stamped backend (here "tpu") is not a
    matched field, and the registry resolves the backend where it runs."""
    from repro.kernels import dispatch
    cfg = EngineConfig(max_iters=40, use_kernel=True, kernel_backend="tpu")
    art = _kmeans_artifact("kern", cfg)
    assert art.model.engine_config["use_kernel"] is True
    registry = ModelRegistry()
    key = registry.register(art)
    entry = registry[key]
    assert entry.backend == dispatch.default_backend()
    srv = ClusterServer(registry, buckets=BUCKETS)
    x = _batch(50, 9)
    srv.submit(AssignRequest(x=x, model_key=key, rid=0))
    _, ref_labels, _ = ClusteringEngine(
        "kmeans", EngineConfig(max_iters=40)).step(x, entry.params)
    np.testing.assert_array_equal(srv.drain()[0], np.asarray(ref_labels))


def test_from_longtail_strict_raises_not_warns():
    model = _model_for(MB_CFG, "kmeans")
    with pytest.raises(ProvenanceMismatchError):
        EngineConfig.from_longtail(model, 0.95, strict=True, max_iters=40)
    with pytest.warns(UserWarning, match="mode-matched"):
        EngineConfig.from_longtail(model, 0.95, max_iters=40)


def test_admission_rejects_malformed_requests(server):
    srv, k1, _ = server
    with pytest.raises(ValueError, match="unknown model"):
        srv.submit(AssignRequest(x=_batch(5, 0), model_key="nope", rid=0))
    with pytest.raises(ValueError, match="feature width"):
        srv.submit(AssignRequest(x=np.zeros((5, D + 2), np.float32),
                                 model_key=k1, rid=1))
    with pytest.raises(ValueError, match="largest bucket"):
        srv.submit(AssignRequest(x=_batch(BUCKETS[-1] + 1, 0),
                                 model_key=k1, rid=2))
    with pytest.raises(ValueError, match="n >= 1"):
        srv.submit(AssignRequest(x=np.zeros((0, D), np.float32),
                                 model_key=k1, rid=3))
    srv.submit(AssignRequest(x=_batch(5, 0), model_key=k1, rid=4))
    with pytest.raises(ValueError, match="already pending"):
        srv.submit(AssignRequest(x=_batch(5, 1), model_key=k1, rid=4))
    assert 4 in srv.drain()                 # the queue survived the rejects


def test_fit_request_advances_registered_params(server):
    srv, k1, _ = server
    entry = srv.registry[k1]
    before = np.asarray(entry.params).copy()
    x = _batch(300, 7)
    srv.submit(FitRequest(x=x, model_key=k1, rid=50))
    out = srv.drain()
    assert np.isfinite(out[50]["objective"])
    assert 1 <= out[50]["n_iters"] <= 10    # registry fit_steps budget
    after = np.asarray(entry.params)
    assert not np.array_equal(before, after)
    # subsequent assignments are served under the advanced parameters
    srv.submit(AssignRequest(x=x[:20], model_key=k1, rid=51))
    labels = srv.drain()[51]
    from repro.kernels.kmeans_assign import ops as kops
    ref_labels, _, _, _ = kops.kmeans_assign(
        jnp.asarray(x[:20]), entry.params, backend=entry.backend)
    np.testing.assert_array_equal(labels, np.asarray(ref_labels))


def test_metrics_and_summary(server):
    srv, k1, _ = server
    for i, n in enumerate([10, 40, 90]):
        srv.submit(AssignRequest(x=_batch(n, i), model_key=k1, rid=i))
    srv.drain()
    m = srv.metrics.summary()[k1]
    assert m["requests"] == 3 and m["points"] == 140
    assert m["p50_latency_ms"] > 0 and m["p99_latency_ms"] > 0
    assert m["throughput_points_per_s"] > 0 and m["qps"] > 0


def test_em_artifact_roundtrip_and_serving():
    rng = np.random.default_rng(2)
    gmm = GMMParams(means=rng.normal(0, 4, (K, D)).astype(np.float32),
                    var=np.ones((K, D), np.float32),
                    log_w=np.full((K,), -np.log(K), np.float32))
    art = ClusterArtifact(name="em", algorithm="em", params=gmm,
                          model=_model_for(FULL_CFG, "em"))
    again = ClusterArtifact.from_json(art.to_json())
    assert again.algorithm == "em" and again.k == K and again.d == D
    np.testing.assert_array_equal(again.params.means, gmm.means)
    assert json.loads(again.to_json()) == json.loads(art.to_json())

    registry = ModelRegistry()
    key = registry.register(again)
    srv = ClusterServer(registry, buckets=BUCKETS)
    x = _batch(25, 9)
    srv.submit(AssignRequest(x=x, model_key=key, rid=0))
    labels = srv.drain()[0]
    eng = ClusteringEngine("em", FULL_CFG)
    _, ref, _ = eng.step(x, registry[key].params)
    np.testing.assert_array_equal(labels, np.asarray(ref))


def test_warmup_precompiles_every_bucket(server):
    srv, k1, _ = server
    srv.warmup(k1)
    entry = srv.registry[k1]
    assert entry.assign._cache_size() == len(BUCKETS)
    srv.submit(AssignRequest(x=_batch(200, 0), model_key=k1, rid=0))
    srv.drain()
    assert entry.assign._cache_size() == len(BUCKETS)   # no new programs


def test_registry_key_is_provenance_fingerprint():
    registry = ModelRegistry()
    key = registry.register(_kmeans_artifact("mb", MB_CFG))
    assert key.startswith("mb@") and "mode=minibatch" in key
    with pytest.raises(ValueError, match="already registered"):
        registry.register(_kmeans_artifact("mb", MB_CFG))
