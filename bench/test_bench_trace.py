"""Trace reduction: interval arithmetic by hand and by brute force, and the
reduction of device operations and host spans to busy time, idle share,
top operations and idle gaps."""
from __future__ import annotations

import gzip

import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.spec import BENCH

V5E_TRACE = BENCH / "testdata" / "groups_two_jobs.xplane.pb.gz"


@pytest.mark.parametrize("seed", range(5))
def test_union_and_gaps_match_a_grid(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 90, 12)
    iv = [(int(s), int(s + rng.integers(0, 15))) for s in starts]
    grid = np.zeros(120, bool)
    for s, e in iv:
        grid[s:e] = True
    u = tr.union(iv)
    assert sum(e - s for s, e in u) == grid.sum()
    assert all(u[i][1] < u[i + 1][0] for i in range(len(u) - 1))
    g = tr.gaps(u, 0, 120)
    assert sum(e - s for s, e in g) == (~grid).sum()


def test_reduce_events_by_hand():
    window = (0, 100)
    jobs = [(5, 50)]
    ops = {"/device:TPU:0": [("a", 10, 30), ("b", 20, 40), ("a", 60, 70),
                             ("c", 95, 130)]}
    red = tr.reduce_events(window, jobs, ops)
    assert red["busy_s"] == pytest.approx(45e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["idle_share"] == pytest.approx(0.55)
    assert dict(red["device_ops"]) == pytest.approx(
        {"a": 30e-9, "b": 20e-9, "c": 5e-9})
    gaps = dict(red["idle_gaps"])
    # (0, 10) lies in the job; (40, 60) and (70, 95) between jobs
    assert gaps[f"{tr.JOB} (unannotated inside the program)"] == \
        pytest.approx(10e-9)
    assert gaps[f"{tr.WINDOW} (between jobs)"] == pytest.approx(45e-9)
    assert red["jobs_traced"] == 1


def test_reduce_events_averages_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 10)]}
    red = tr.reduce_events((0, 100), [], ops)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["idle_share"] == pytest.approx(0.7)


def test_a_v5e_trace_reduces(tmp_path):
    """Two poker-km.groups jobs traced on a TPU v5e through the harness's
    profiler options: the device plane and its ops line are found by the
    names the reduction reads, and the device's operations fall inside the
    host's window span, so the two share one clock."""
    from jax.profiler import ProfileData
    path = tmp_path / "plugins" / "profile" / "run" / "v5e.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(V5E_TRACE.read_bytes()))
    assert tr.trace_file(str(tmp_path)) == str(path)
    window, jobs, ops = tr.read_events(ProfileData.from_file(str(path)))
    assert list(ops) == ["/device:TPU:0"]
    # one clock: every device op starts within 1 ms of the window span
    # (the first is the tail of a dispatch made just before it opened)
    starts = [s for _, s, _ in ops["/device:TPU:0"]]
    assert window[0] - 1e6 <= min(starts) and max(starts) <= window[1]
    assert sum(s < window[0] for s in starts) <= 1
    assert len(jobs) == 2
    assert all(window[0] <= s < e <= window[1] for s, e in jobs)
    red = tr.reduce_file(str(path))
    assert red["jobs_traced"] == 2
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    assert 0 < len(red["device_ops"]) <= tr.TOP
    gaps = dict(red["idle_gaps"])
    assert set(gaps) <= {f"{tr.JOB} (unannotated inside the program)",
                         f"{tr.WINDOW} (between jobs)"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events((0, 100), [], {})
    with pytest.raises(ValueError):
        tr.reduce_events((0, 100), [], {"/device:TPU:0": [("a", 200, 300)]})
