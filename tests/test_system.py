"""End-to-end behaviour of the paper's system: sample → fit regression →
early stop → accuracy/cost validation; plus the LM-loop generalisation and
the distributed clustering path (in-process 8-device session; only the CLI
smoke tests still spawn subprocesses — they test the CLI itself)."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.data import load, spacenet_pixels
from repro.launch.cluster import train_regression, run_production


@pytest.mark.parametrize("algorithm", ["kmeans", "em"])
def test_paper_pipeline_end_to_end(algorithm):
    """§4 pipeline on the skin-like dataset, k=2 (paper's Skin_Seg setup)."""
    k = 2
    data = load("skin", n=24_000, seed=0)
    groups = core.random_groups(data, 6000, max_groups=4)
    model, t_train = train_regression(groups[:3], k, algorithm,
                                      max_iters=150, family="quadratic")
    # EM's pooled (r, h) cloud on the reduced 6k-point groups is noisier
    # than k-means' (mirrors the paper, where EM's fit quality also trails);
    # 0.45 keeps the "fit is meaningful" intent without flaking on backends
    # whose fp reductions land R² within noise of 0.5.
    assert model.regression.metrics.r2 > 0.45
    h_star = model.threshold_for(0.99)
    assert h_star > 0

    val = groups[3]
    labels, _, iters, t_act = run_production(val, k, algorithm, h_star,
                                             max_iters=150, seed=9)
    labels_f, _, iters_f, t_full = run_production(
        val, k, algorithm, 0.0 if algorithm == "kmeans" else 1e-12,
        max_iters=400, seed=9)
    acc = float(core.rand_index(labels, labels_f, k, k))
    assert int(iters) <= int(iters_f)
    assert acc >= 0.95, f"{algorithm}: achieved {acc} for desired 0.99"


def test_spacenet_image_groups():
    """SpaceNet-style flow: image = sampling group (§5.2), k=6."""
    pix = spacenet_pixels(n_images=3, k_true=6, seed=0,
                          shape=(64, 64, 3))      # reduced resolution
    model, _ = train_regression(pix[:2], 6, "kmeans", max_iters=120,
                                family="quadratic")
    h_star = model.threshold_for(0.99)
    labels, _, iters, _ = run_production(pix[2], 6, "kmeans", h_star,
                                         max_iters=200)
    labels_f, _, iters_f, _ = run_production(pix[2], 6, "kmeans", 0.0,
                                             max_iters=400)
    acc = float(core.rand_index(labels, labels_f, 6, 6))
    assert acc > 0.9


def test_lm_longtail_generalisation():
    """Beyond-paper: the controller stops LM training near a target fraction
    of final quality (pilot run fits the regression, main run early-stops)."""
    from repro.configs import get_config
    from repro.training import Trainer, TrainConfig, OptimizerConfig

    cfg = get_config("qwen3-8b", reduced=True)
    tc = TrainConfig(opt=OptimizerConfig(peak_lr=5e-3, warmup_steps=5,
                                         total_steps=120))

    def data():
        rng = np.random.default_rng(7)
        while True:
            start = rng.integers(0, cfg.vocab, size=(4, 1))
            yield {"tokens": jnp.asarray((start + np.arange(32)) % cfg.vocab,
                                         jnp.int32)}

    # pilot: run to (near-)convergence, harvest (r, h) from the loss curve
    pilot = Trainer(cfg, tc, data(), seed=1)
    pilot.run(100)
    losses = np.array([m["loss"] for m in pilot.metrics_log])
    final, first = losses[-5:].mean(), losses[:3].mean()
    # quality proxy r_i = relative progress toward final loss
    sm = np.convolve(losses, np.ones(5) / 5, mode="valid")
    r = np.clip((first - sm) / max(first - final, 1e-9), 0, 1)
    h = np.abs(np.diff(sm)) / np.maximum(np.abs(sm[:-1]), 1e-9)
    model = core.fit_longtail([(r[1:], h)], algorithm="lm_train",
                              dataset="markov", family="quadratic")
    hook = core.EarlyStopHook(model, desired_accuracy=0.95, ema=0.8,
                              patience=5, min_steps=20)
    main = Trainer(cfg, tc, data(), earlystop=hook, seed=1)
    rep = main.run(100)
    if rep["stopped_early"]:
        assert rep["final_step"] < 100
        stopped_loss = main.metrics_log[-1]["loss"]
        # must have realised most of the achievable improvement
        progress = (first - stopped_loss) / max(first - final, 1e-9)
        assert progress > 0.6, progress


def test_distributed_clustering_matches_single_device(mesh8):
    """Sharded early-stopped run vs single-device run: identical stop point.
    Runs against the session's in-process 8-device view (``run_production``
    builds its own data-axis mesh from ``jax.devices()``; ``mesh8`` asserts
    the multi-device substrate is up)."""
    data = load("skin", n=16000, seed=3)
    l1, j1, i1, _ = run_production(data, 2, "kmeans", 1e-4, max_iters=100,
                                   seed=5, shard=True)
    l2, j2, i2, _ = run_production(np.asarray(data)[:l1.shape[0]], 2,
                                   "kmeans", 1e-4, max_iters=100, seed=5,
                                   shard=False)
    acc = float(core.rand_index(l1, l2, 2, 2))
    assert int(i1) == int(i2), (i1, i2)
    assert acc > 0.9999, acc


def test_distributed_minibatch_matches_single_device(mesh8):
    """--mode minibatch --shard (ISSUE 3 tentpole): the sharded chunk-draw
    path keeps every row (no truncation) and reproduces the single-device
    minibatch run — same seeded draws, same stop iteration."""
    data = load("skin", n=8192, seed=4)
    l1, j1, i1, _ = run_production(data, 2, "kmeans", 1e-3, max_iters=80,
                                   seed=5, shard=True, mode="minibatch",
                                   chunks=8, batch_chunks=2)
    l2, j2, i2, _ = run_production(data, 2, "kmeans", 1e-3, max_iters=80,
                                   seed=5, shard=False, mode="minibatch",
                                   chunks=8, batch_chunks=2)
    assert l1.shape[0] == 8192                # padded layout, not truncated
    # the chunk draws are identical; fp32 psum reduction order can still
    # flip one boundary stop step when h lands on the threshold (the strict
    # n_iters check lives in test_engine_sharded on a controlled fixture)
    assert abs(int(i1) - int(i2)) <= 1, (i1, i2)
    acc = float(core.rand_index(l1, l2, 2, 2))
    assert acc > 0.9999, acc


def test_distributed_restarts_match_unsharded(mesh8):
    """--restarts 4 --shard (ISSUE 3): the vmap-inside-shard_map fleet
    agrees with the unsharded vmapped fleet on the best objective."""
    data = load("skin", n=8192, seed=6)
    l1, j1, i1, _ = run_production(data, 2, "kmeans", 1e-4, max_iters=60,
                                   seed=5, shard=True, restarts=4)
    l2, j2, i2, _ = run_production(data, 2, "kmeans", 1e-4, max_iters=60,
                                   seed=5, shard=False, restarts=4)
    assert abs(int(i1) - int(i2)) <= 1, (i1, i2)   # see minibatch test above
    np.testing.assert_allclose(j1, j2, rtol=1e-5)
    acc = float(core.rand_index(l1, l2, 2, 2))
    assert acc > 0.9999, acc


def test_shard_fallback_helper_is_loud(capsys):
    """--shard on a 1-device host must fail, not silently run replicated
    while the user believes the distributed path ran."""
    from repro.launch.cluster import _resolve_shard
    with pytest.raises(ValueError, match="only 1 is visible") as ei:
        _resolve_shard(True, 1)
    assert "--shard" in str(ei.value)
    assert "xla_force_host_platform_device_count" in str(ei.value)  # the fix
    assert _resolve_shard(True, 8) is True
    assert _resolve_shard(False, 1) is False
    assert capsys.readouterr().out == ""                   # quiet otherwise


@pytest.mark.skipif(jax.device_count() != 1,
                    reason="exercises the forced-1-device CI leg")
def test_shard_single_device_end_to_end_warns():
    """On the 1-device CI leg the production path refuses --shard before
    any device work."""
    data = load("skin", n=2000, seed=0)
    with pytest.raises(ValueError, match="only 1 is visible"):
        run_production(data, 2, "kmeans", 1e-3, max_iters=30, seed=1,
                       shard=True)


def _cli_env():
    """Stock environment for CLI smokes: undo conftest's session-wide
    8-device flag so the CLI is exercised the way a user runs it."""
    import os
    import conftest
    return {**os.environ, "PYTHONPATH": "src",
            "XLA_FLAGS": conftest.ORIG_XLA_FLAGS}


def test_cluster_cli_smoke(tmp_path):
    out = tmp_path / "rep.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.cluster", "--dataset", "skin",
         "--k", "2", "--n", "12000", "--group-size", "3000",
         "--train-groups", "2", "--desired-accuracy", "0.99",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=_cli_env())
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rep = json.loads(out.read_text())
    assert rep["achieved_accuracy"] > 0.9
    assert rep["iters_earlystop"] <= rep["iters_full"]


def test_cluster_save_artifact_serves(tmp_path):
    """ISSUE 7 satellite, fit → save → serve: the cluster CLI's
    --save-artifact JSON must round-trip through serve_cluster --registry
    (the registry layout the assignment server consumes)."""
    from repro.core import ClusterArtifact
    registry = tmp_path / "registry"
    registry.mkdir()
    art_path = registry / "skin-kmeans-k2.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.cluster", "--dataset", "skin",
         "--k", "2", "--n", "9000", "--group-size", "3000",
         "--train-groups", "2", "--prod-groups", "1", "--max-iters", "60",
         "--save-artifact", str(art_path)],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=_cli_env())
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    art = ClusterArtifact.load(str(art_path))     # well-formed on disk
    assert art.algorithm == "kmeans" and art.k == 2 and art.d == 4
    assert art.model.threshold_for(0.99) > 0      # stop-model rides along

    out = tmp_path / "serve.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_cluster",
         "--registry", str(registry), "--requests", "8",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=_cli_env())
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rep = json.loads(out.read_text())
    assert rep["n_results"] == 8


def test_run_production_return_params_opt_in():
    """The 4-tuple contract at every existing call site stays; the 5th
    element appears only on request, on each of the three return paths."""
    data = load("skin", n=2000, seed=1)
    out = run_production(data, 2, "kmeans", 1e-3, max_iters=20, seed=1)
    assert len(out) == 4
    for kw in (dict(), dict(restarts=2)):
        out = run_production(data, 2, "kmeans", 1e-3, max_iters=20, seed=1,
                             return_params=True, **kw)
        assert len(out) == 5
        assert np.shape(out[4]) == (2, 4)         # centroids [K, D]


def test_run_production_compression_guards():
    """stats_compression must not silently corrupt the frozen-stop
    full-convergence reference (h*=0 kmeans baseline)."""
    data = load("skin", n=2000, seed=1)
    with pytest.raises(ValueError, match="full-convergence"):
        run_production(data, 2, "kmeans", 0.0, max_iters=20,
                       stats_compression="int8_ef")


def test_sharded_compressed_production(mesh8):
    """--shard --stats-compression int8_ef end-to-end: the compressed run
    stops within a boundary iteration of the fp32 psum run and agrees on
    the partition."""
    data = load("skin", n=8192, seed=4)
    kw = dict(max_iters=80, seed=5, shard=True, mode="minibatch",
              chunks=8, batch_chunks=2)
    l1, j1, i1, _ = run_production(data, 2, "kmeans", 1e-3, **kw)
    l2, j2, i2, _ = run_production(data, 2, "kmeans", 1e-3,
                                   stats_compression="int8_ef",
                                   prefetch=True, **kw)
    assert abs(int(i1) - int(i2)) <= 1, (i1, i2)
    assert float(core.rand_index(l1, l2, 2, 2)) > 0.999


def test_train_cli_smoke(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-8b",
         "--steps", "8", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path / "ck"),
         "--out", str(tmp_path / "train.json")],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=_cli_env())
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rep = json.loads((tmp_path / "train.json").read_text())
    assert rep["final_step"] == 8
