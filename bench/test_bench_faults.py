"""The output check, driven on the CPU at a small size past the harness's
look for a chip: a sound run comes out correct, and the control and each
fault planted under the timed path come out not correct."""
from __future__ import annotations

import json
import shutil

import pytest

from bench import control, harness
from bench.spec import BENCH, ROOT, Spec

SMALL = {"data": {"generator": "poker", "n": 20000, "d": 11},
         "stop_model": {"data_seed": 0, "datasets": 1, "group_size": 5000,
                        "groups": 4}}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """poker-km.whole at 20,000 points, with the cell's own limits."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    path = root / "bench" / "configs" / "poker-km.json"
    config = json.loads(path.read_text())
    config.update(SMALL)
    path.write_text(json.dumps(config))
    return harness.setup(Spec(root, root / "bench"), "poker-km.whole",
                         require_tpu=False, cache=False)


def checks_of(s, seed):
    w = harness.window(s, seed, 0.2)
    checks = harness.verify(s, seed, w["kept"])[0]
    return {k: c["pass"] for k, c in checks.items()}


def test_sound_run_is_correct(small):
    assert all(checks_of(small, 2**31 + 5).values())


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "fixed_point_gap"),
    ("half_batch", "objective_gap"),
    ("altered_answer", "objective_gap"),
])
def test_fault_is_not_correct(small, fault, caught_by):
    with control.planted(fault):
        passed = checks_of(small, 11)
    assert not passed[caught_by]
    assert not all(passed.values())


def test_control_is_not_correct(small):
    job, full = control.control_jobs(small.config, small.replay)
    passed = checks_of(small._replace(job=job, full=full), 11)
    assert not all(passed.values())
