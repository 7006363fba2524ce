"""The harness finds every cell, configuration, traffic mix, metric reader,
limit and peak by name, and refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, traffic
from bench.spec import BENCH, ROOT, Spec, quantity

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in Spec().doc["workloads"]]


def test_benchmark_json_names_and_keys():
    doc = Spec().doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"]
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in doc[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in doc["end_to_end"]:
        assert m["source"] in ("device_trace", "host_clock")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    spec = Spec()
    w = spec.cell(cell)
    config = spec.config(w["config"])
    tr = spec.traffic(w["traffic"])
    assert {"datasets", "group_size", "rstar_jobs", "check_jobs"} <= set(tr)
    assert {"k", "algorithm", "data", "stop_model", "desired_accuracy",
            "max_iters", "patience", "regime", "reference"} <= set(config)
    assert callable(spec.generator(config["data"]["generator"]))
    assert callable(spec.reference(config["reference"]))
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    assert harness.held(limits)
    assert all(isinstance(limits[n], (int, float)) for n in harness.held(limits))
    for m in spec.metrics(cell, "per_layer"):
        assert callable(spec.reader(m["name"]))
    e2e = {m["name"] for m in spec.metrics(cell, "end_to_end")}
    assert "setup_s" in e2e
    assert [quantity(n) for n in e2e - {"setup_s"}] == ["fit_s"]
    for m in spec.metrics(cell, "per_layer"):
        assert m["moves"] in e2e


def test_config_files_lie_under_paths_and_match_their_entries():
    spec = Spec()
    files = [c["file"] for c in spec.doc["configs"]]
    assert len(set(files)) == len(files)
    for entry in spec.doc["configs"]:
        assert entry["file"].startswith("bench/")
        config = spec.config(entry["name"])
        assert config["name"] == entry["name"]
        assert config["reduced"] == entry["reduced"]


def test_peaks_table_has_its_source_and_refuses_unknown_kinds():
    spec = Spec()
    v5e = spec.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        spec.peak("cpu")


def test_a_new_cell_is_found_from_files_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "poker-km.pairs", "config": "poker-km",
                             "traffic": "pairs", "chips": 1,
                             "why": "two data sets"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "poker-km.groups" in m.get("workloads", []):
            m["workloads"].append("poker-km.pairs")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    (tmp_path / "bench" / "traffic" / "pairs.json").write_text(json.dumps(
        {"datasets": 2, "group_size": None, "rstar_jobs": 2,
         "check_jobs": 1}))
    spec = Spec(tmp_path, tmp_path / "bench")
    w = spec.cell("poker-km.pairs")
    assert spec.traffic(w["traffic"])["datasets"] == 2
    assert spec.config(w["config"])["k"] == 10
    names = {m["name"] for m in spec.metrics("poker-km.pairs", "per_layer")}
    assert {"iters_per_job.small", "train_s"} <= names
    assert {m["name"] for m in spec.metrics("poker-km.pairs", "end_to_end")} \
        == {"fit_s.small", "setup_s"}


def test_a_split_metric_is_read_by_its_quantitys_reader():
    spec = Spec()
    assert quantity("fit_hbm_roofline.small") == "fit_hbm_roofline"
    assert quantity("fit_s") == "fit_s"
    ctx = {"window_compiles": 7, "trace": {"idle_share": 0.25}}
    assert spec.reader("window_compiles.small")(ctx) == 7
    assert spec.reader("idle_share.small")(ctx) == \
        spec.reader("idle_share")(ctx) == 25.0
    with pytest.raises(KeyError):
        spec.reader("no_such_metric.small")


def test_a_new_configuration_is_found_from_files_alone(tmp_path):
    """A configuration with a data generator, a reference and a regime of
    its own needs new files and entries, and no edit to the harness."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "blobs-km", "source": "https://example.org",
                           "file": "bench/configs/blobs-km.json",
                           "reduced": [], "why": "two blobs"})
    doc["workloads"].append({"name": "blobs-km.whole", "config": "blobs-km",
                             "traffic": "whole", "chips": 1, "why": "blobs"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = tmp_path / "bench"
    (bench / "configs" / "blobs-km.json").write_text(json.dumps({
        "name": "blobs-km", "algorithm": "kmeans", "k": 2,
        "regime": {"mode": "minibatch", "chunks": 8, "batch_chunks": 2,
                   "restarts": 4, "use_kernel": True},
        "reference": "blobs_ref",
        "data": {"generator": "blobs", "n": 40, "d": 2}}))
    (bench / "generators" / "blobs.py").write_text(
        "import numpy as np\n"
        "def make(data, seed):\n"
        "    return np.full((data['n'], data['d']), seed, np.float32)\n")
    (bench / "references" / "blobs_ref.py").write_text(
        "def replay(x, init_seed, config, dist=None):\n"
        "    return 'replayed', init_seed\n")
    spec = Spec(tmp_path, bench)
    config = spec.config(spec.cell("blobs-km.whole")["config"])
    assert spec.generator(config["data"]["generator"])(config["data"],
                                                       3)[0, 0] == 3
    assert spec.reference(config["reference"])(None, 5, config) == (
        "replayed", 5)
    assert harness.regime_argv(config["regime"]) == [
        "--mode", "minibatch", "--chunks", "8", "--batch-chunks", "2",
        "--restarts", "4", "--use-kernel"]


def test_the_regime_reaches_the_programs_flags():
    from repro.launch import cluster
    regime = {"mode": "minibatch", "chunks": 8, "batch_chunks": 2,
              "restarts": 4, "decay": 0.5, "use_kernel": False}
    args = cluster.parse_args(["--k", "2", *harness.regime_argv(regime)])
    assert (args.mode, args.chunks, args.batch_chunks, args.restarts,
            args.decay, args.use_kernel) == ("minibatch", 8, 2, 4, 0.5,
                                             False)


def test_the_generator_reads_a_traffic_file():
    config = {"data": {"generator": "poker", "n": 3000, "d": 11},
              "data_seed": 0,
              "stop_model": {"data_seed": 0, "datasets": 1,
                             "group_size": 1000, "groups": 2}}
    make = Spec().generator("poker")
    pool = traffic.build(config, {"datasets": 1, "group_size": 500}, make)
    assert len(pool.jobs) == 6 and pool.jobs[0].shape == (500, 11)
    assert pool.train.shape == (2, 1000, 11)
    whole = traffic.build(config, {"datasets": 2, "group_size": None}, make)
    assert [j.shape for j in whole.jobs] == [(3000, 11)] * 2
    order = traffic.order(6, 2**31 + 17)
    assert sorted(order.tolist()) == list(range(6))
    assert (order == traffic.order(6, 2**31 + 17)).all()


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "poker-km.groups",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
