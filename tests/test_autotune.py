"""Kernel autotuner (ISSUE 9): versioned winner cache round-trip and
schema gates, scoped activation and the ops' resolution order (explicit
override > active cache > TilePolicy default), cache-hit short-circuit,
deterministic winner selection under a scripted clock, the shared timing
methodology, the ``bucket_for`` round-up contract above the ladder, and
tuned-vs-untuned engine stop-iteration parity across mode × backend."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.engine import ClusteringEngine, EngineConfig
from repro.kernels import autotune, dispatch, layout
from repro.kernels.kmeans_assign.ops import kmeans_assign
from repro.kernels.timing import REDUCERS, time_callable


class ScriptedTimer:
    """Deterministic clock: each timed rep elapses the next scripted
    duration (time_callable brackets fn with exactly two clock calls)."""

    def __init__(self, durations):
        self.durations = list(durations)
        self.t = 0.0
        self._open = False
        self.calls = 0

    def __call__(self):
        self.calls += 1
        if not self._open:
            self._open = True
            return self.t
        self._open = False
        self.t += self.durations.pop(0)
        return self.t


# --------------------------------------------------------------------------
# Cache: round trip, schema version, malformed payloads
# --------------------------------------------------------------------------

def test_cache_json_round_trip(tmp_path):
    cache = autotune.AutotuneCache()
    cache.put("kmeans_assign", "interpret", n=4096, k=8, d=16,
              blocks={"block_n": 512}, median_s=0.001)
    cache.put("flash_attention", "interpret", n=512, k=512, d=64,
              blocks={"block_q": 64, "block_k": 128})
    path = tmp_path / "cache.json"
    cache.save(str(path))
    loaded = autotune.AutotuneCache.load(str(path))
    assert loaded.entries == cache.entries
    assert loaded.lookup("kmeans_assign", "interpret",
                         n=4096, k=8, d=16) == {"block_n": 512}
    assert loaded.lookup("flash_attention", "interpret", n=512, k=512,
                         d=64) == {"block_q": 64, "block_k": 128}
    # the n key is bucketed: any n padding to the same bucket hits
    assert loaded.lookup("kmeans_assign", "interpret",
                         n=2000, k=8, d=16) == {"block_n": 512}
    # a different (k, d) is a different cell
    assert loaded.lookup("kmeans_assign", "interpret",
                         n=4096, k=8, d=8) is None


def test_cache_rejects_stale_schema_version(tmp_path):
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({"schema_version": 0, "entries": {}}))
    with pytest.raises(autotune.StaleCacheError, match="schema_version=0"):
        autotune.AutotuneCache.load(str(path))


def test_cache_rejects_malformed_payloads():
    with pytest.raises(ValueError, match="no 'entries'"):
        autotune.AutotuneCache.from_payload(
            {"schema_version": autotune.SCHEMA_VERSION, "entries": []})
    for blocks in ({"block_n": 0}, {"block_n": "512"}, None):
        with pytest.raises(ValueError, match="malformed"):
            autotune.AutotuneCache.from_payload({
                "schema_version": autotune.SCHEMA_VERSION,
                "entries": {"cell": {"blocks": blocks}}})


# --------------------------------------------------------------------------
# Scoped activation + resolution order at the op call sites
# --------------------------------------------------------------------------

def _cache_with(op, backend, *, n, k, d, blocks):
    cache = autotune.AutotuneCache()
    cache.put(op, backend, n=n, k=k, d=d, blocks=blocks)
    return cache


def test_tuned_blocks_needs_an_active_scope():
    cache = _cache_with("kmeans_assign", "interpret", n=4096, k=8, d=16,
                        blocks={"block_n": 256})
    assert autotune.tuned_blocks("kmeans_assign", "interpret",
                                 n=4096, k=8, d=16) is None
    with autotune.tuning(cache):
        assert autotune.tuned_blocks(
            "kmeans_assign", "interpret",
            n=4096, k=8, d=16) == {"block_n": 256}
        # no entry for this backend → None (defaults apply)
        assert autotune.tuned_blocks("kmeans_assign", "xla",
                                     n=4096, k=8, d=16) is None
    assert autotune.tuned_blocks("kmeans_assign", "interpret",
                                 n=4096, k=8, d=16) is None


def test_cache_from_other_device_kind_never_matches():
    cache = autotune.AutotuneCache()
    key = autotune.AutotuneCache.key("kmeans_assign", "interpret",
                                     n=4096, k=8, d=16, kind="TPU_v4")
    cache.entries[key] = {"blocks": {"block_n": 256}}
    with autotune.tuning(cache):
        assert autotune.tuned_blocks("kmeans_assign", "interpret",
                                     n=4096, k=8, d=16) is None


def test_resolution_order_at_the_op_call_site(monkeypatch):
    """explicit block_n > active cache > TilePolicy default, observed
    through a fake registered backend that records the resolved block."""
    seen = []
    # a backend brings its own tile policy; the spy borrows the TPU one
    monkeypatch.setitem(layout.TILE_POLICIES, "spybk",
                        layout.TILE_POLICIES["tpu"])

    @dispatch.register_backend("kmeans_assign", "spybk")
    def _spy(x, w, c, *, block_n):
        seen.append(block_n)
        n, d = x.shape
        k = c.shape[0]
        return (jnp.zeros((n,), jnp.int32), jnp.zeros((k, d)),
                jnp.zeros((k,)), jnp.zeros(()))

    x = jnp.zeros((4096, 16), jnp.float32)
    c = jnp.zeros((8, 16), jnp.float32)
    pol = layout.tile_policy("spybk")
    cache = _cache_with("kmeans_assign", "spybk", n=4096, k=8, d=16,
                        blocks={"block_n": 256})
    try:
        kmeans_assign(x, c, backend="spybk")
        assert seen[-1] == pol.block_for(4096)           # untuned default
        with autotune.tuning(cache):
            kmeans_assign(x, c, backend="spybk")
            assert seen[-1] == 256                       # cache consulted
            kmeans_assign(x, c, backend="spybk", block_n=512)
            assert seen[-1] == 512                       # override wins
    finally:
        dispatch.get_op("kmeans_assign")._impls.pop("spybk")


# --------------------------------------------------------------------------
# Sweep + tune: determinism, short-circuit, winner ≥ default by construction
# --------------------------------------------------------------------------

def test_sweep_winner_is_deterministic_under_scripted_clock():
    cands = autotune.candidate_blocks("kmeans_assign", "interpret",
                                      n=4096, k=8, d=16)
    assert len(cands) > 2 and cands[0] == {"block_n": 1024}  # default first
    # candidate at index 2 gets the smallest duration → must win, twice
    durations = [3.0, 2.0, 1.0, 4.0, 5.0][:len(cands)]
    for _ in range(2):
        sw = autotune.sweep_op(
            "kmeans_assign", "interpret", n=4096, k=8, d=16,
            reps=1, warmup=0, timer=ScriptedTimer(durations),
            call_factory=lambda blocks: (lambda: None), include_cost=False)
        assert sw["winner"]["blocks"] == cands[2]
        assert sw["default"]["blocks"] == cands[0]
        assert sw["default"]["median_s"] >= sw["winner"]["median_s"]


def test_winner_ties_resolve_to_the_default():
    cands = autotune.candidate_blocks("kmeans_assign", "interpret",
                                      n=4096, k=8, d=16)
    sw = autotune.sweep_op(
        "kmeans_assign", "interpret", n=4096, k=8, d=16,
        reps=1, warmup=0, timer=ScriptedTimer([1.0] * len(cands)),
        call_factory=lambda blocks: (lambda: None), include_cost=False)
    assert sw["winner"]["blocks"] == cands[0]  # argmin is first on ties


def test_tune_cache_hit_short_circuits_retiming():
    shapes = [(64, 4, 4)]
    timer = ScriptedTimer([1.0] * 64)
    cache = autotune.tune(
        ops=["kmeans_assign"], backends=["interpret"], shapes=shapes,
        reps=1, warmup=0, timer=timer, include_cost=False,
        call_factory=lambda blocks: (lambda: None))
    assert cache.lookup("kmeans_assign", "interpret", n=64, k=4, d=4)
    first_calls = timer.calls
    assert first_calls > 0
    # same cells, same cache → no candidate is ever re-timed
    autotune.tune(
        ops=["kmeans_assign"], backends=["interpret"], shapes=shapes,
        reps=1, warmup=0, timer=timer, include_cost=False, cache=cache,
        call_factory=lambda blocks: (lambda: None))
    assert timer.calls == first_calls


def test_candidate_grids_respect_backend_policy():
    # xla ignores blocks entirely → a sweep would time one program N ways
    assert autotune.candidate_blocks("kmeans_assign", "xla",
                                     n=4096, k=8, d=16) == \
        [{"block_n": 1024}]
    # gpu (Triton): every candidate must satisfy the pow2 rule
    for cand in autotune.candidate_blocks("kmeans_assign", "gpu",
                                          n=4096, k=8, d=16):
        bn = cand["block_n"]
        assert bn & (bn - 1) == 0, cand
    # flash: pairs capped to the aligned sequence lengths, default first
    fl = autotune.candidate_blocks("flash_attention", "interpret",
                                   n=128, k=512, d=64)
    assert fl[0] == {"block_q": 128, "block_k": 128}
    assert all(c["block_q"] <= 128 for c in fl)


def test_roofline_point_geometry():
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e10}
    low = autotune.roofline_point(1e9, 1e9, 1e-3, peaks)   # intensity 1
    assert low["bound"] == "memory"
    assert low["roofline_ceiling_flops_per_s"] == pytest.approx(1e10)
    assert low["achieved_flops_per_s"] == pytest.approx(1e12)
    high = autotune.roofline_point(1e12, 1e9, 1.0, peaks)  # intensity 1e3
    assert high["bound"] == "compute"
    assert high["roofline_ceiling_flops_per_s"] == pytest.approx(1e12)
    assert high["ceiling_fraction"] == pytest.approx(1.0)


# --------------------------------------------------------------------------
# Shared timing methodology
# --------------------------------------------------------------------------

def test_time_callable_reducers_with_scripted_clock():
    samples = [3.0, 1.0, 2.0]
    for reduce, want in (("median", 2.0), ("min", 1.0), ("mean", 2.0)):
        t = time_callable(lambda: None, reps=3, warmup=0, reduce=reduce,
                          timer=ScriptedTimer(samples))
        assert t == pytest.approx(want), reduce
    assert set(REDUCERS) == {"median", "min", "mean"}


def test_time_callable_validates_arguments():
    with pytest.raises(ValueError, match="reduce"):
        time_callable(lambda: None, reduce="p99")
    with pytest.raises(ValueError, match="reps"):
        time_callable(lambda: None, reps=0)


def test_time_callable_warmup_is_untimed():
    calls = []
    timer = ScriptedTimer([1.0, 1.0])
    time_callable(lambda: calls.append(1), reps=2, warmup=3, timer=timer)
    assert len(calls) == 5                   # 3 warmup + 2 timed
    assert timer.calls == 4                  # clock brackets timed reps only


# --------------------------------------------------------------------------
# bucket_for: the ISSUE 9 round-up contract above the ladder
# --------------------------------------------------------------------------

def test_bucket_for_boundary_regression():
    top = layout.DEFAULT_BUCKETS[-1]
    assert layout.bucket_for(1) == layout.DEFAULT_BUCKETS[0]
    assert layout.bucket_for(top) == top          # exact top: in-ladder
    assert layout.bucket_for(top + 1) == 2 * top  # just above: rounds up
    assert layout.bucket_for(3 * top - 1) == 3 * top
    assert layout.bucket_for(3 * top) == 3 * top  # policy-aligned multiple


def test_bucket_for_impossible_padding_fails_loud():
    with pytest.raises(ValueError, match="cannot pad"):
        layout.bucket_for(0)
    with pytest.raises(ValueError, match="non-empty bucket ladder"):
        layout.bucket_for(100, buckets=())


# --------------------------------------------------------------------------
# Engine integration: autotuned fits reproduce untuned stop iterations
# --------------------------------------------------------------------------

def test_engine_config_autotune_requires_kernel_path():
    with pytest.raises(ValueError, match="use_kernel"):
        EngineConfig(autotune=True)
    EngineConfig(autotune=True, use_kernel=True)   # valid combination


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.default_rng(7)
    k, d, n = 8, 8, 2048
    centers = rng.normal(0, 6.0, (k, d))
    x = np.concatenate([c + rng.normal(0, 1.5, (n // k, d))
                        for c in centers])
    x = jnp.asarray(x[rng.permutation(n)].astype(np.float32))
    return x, core.random_init(jax.random.PRNGKey(0), x, k)


@pytest.fixture()
def pinned_cache():
    """A process-default cache pinning a NON-default block_n for every
    bucket the engine fits below can hit, on both CI backends."""
    cache = autotune.AutotuneCache()
    for backend in ("interpret", "xla"):
        for n in (256, 1024, 4096):
            cache.put("kmeans_assign", backend, n=n, k=8, d=8,
                      blocks={"block_n": 256})
    autotune.set_default_cache(cache)
    try:
        yield cache
    finally:
        autotune.set_default_cache(None)
        jax.clear_caches()   # drop traces that baked in the pinned blocks


@pytest.mark.parametrize("mode", ["full", "minibatch"])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_autotuned_fit_matches_untuned_stop_exactly(blobs, pinned_cache,
                                                    mode, backend):
    # h* = 3e-3 crosses while h is in steep decay, so the stop margin
    # dwarfs the fp32 reduction-order noise a different block_n regroups
    # (the PR 7 parity-threshold precedent)
    x, c0 = blobs
    kw = dict(max_iters=60, use_kernel=True, kernel_backend=backend, seed=0)
    if mode == "minibatch":
        kw.update(mode="minibatch", chunks=4, batch_chunks=2, patience=3,
                  decay=0.95)
    base = ClusteringEngine("kmeans", EngineConfig(**kw)).fit(
        x, c0, h_star=3e-3)
    tuned = ClusteringEngine("kmeans", EngineConfig(autotune=True, **kw)) \
        .fit(x, c0, h_star=3e-3)
    assert int(base.n_iters) == int(tuned.n_iters), (mode, backend)
    # a different block_n regroups fp32 accumulation, so the objectives
    # agree to reduction-order noise, not bit-for-bit
    assert float(tuned.objective) == pytest.approx(
        float(base.objective), rel=1e-4)


def test_default_cache_env_lookup(tmp_path, monkeypatch):
    cache = _cache_with("kmeans_assign", "interpret", n=4096, k=8, d=16,
                        blocks={"block_n": 512})
    path = tmp_path / "env_cache.json"
    cache.save(str(path))
    autotune.set_default_cache(None)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    try:
        loaded = autotune.default_cache()
        assert loaded is not None and loaded.lookup(
            "kmeans_assign", "interpret", n=4096, k=8, d=16) == \
            {"block_n": 512}
    finally:
        autotune.set_default_cache(None)
