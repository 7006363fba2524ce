"""Idle gaps named by the program's spans (``span_reduce``), the per-layer
metrics that read the spans, and the program's lowering counter against
the harness's ``CompileCounter``."""
from __future__ import annotations

import gzip

import jax
import numpy as np
import pytest

from bench import harness, span_reduce as sr, trace_reduce as tr
from bench.spec import BENCH, Spec
from repro import spans
from repro.launch.cluster import run_production

V5E_TRACE = BENCH / "testdata" / "groups_two_jobs.xplane.pb.gz"
V5E_SPANS_TRACE = BENCH / "testdata" / "groups_two_jobs_spans.xplane.pb.gz"
READERS = ("host_path_s", "seed_s", "seed_lowerings", "harvest_s",
           "regression_s")


def unpack(gz, tmp_path):
    path = tmp_path / "plugins" / "profile" / "run" / "v5e.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(gz.read_bytes()))
    return str(path)


def lowerings():
    return sum(t["lowerings"] for t in spans.totals().values())


def test_program_lowerings_equal_the_harness_counter():
    """Every program the jobs lower is counted once, under some span of
    ``run_production``; no count per job is pinned."""
    counter = harness.CompileCounter()
    x = np.random.default_rng(2).normal(size=(1536, 5)).astype(np.float32)
    before = lowerings()
    counter.active = True
    for seed in range(3):
        run_production(x, 4, "kmeans", 1e-3, max_iters=10, seed=seed)
    run_production(x, 4, "kmeans", 1e-3, max_iters=10, seed=0, restarts=2)
    counter.active = False
    assert counter.count > 0
    assert lowerings() - before == counter.count


def test_gaps_take_the_innermost_span_and_mark_lowerings():
    window = (0, 100)
    jobs = [(0, 80)]
    ops = {"/device:TPU:0": [("a", 20, 30), ("b", 50, 60), ("c", 80, 90)]}
    spans_ = [("entry.job", 0, 80), ("entry.seed", 5, 48),
              ("lower_sharding_computation", 32, 45),
              ("entry.wait", 55, 79), ("backend_compile", 82, 99)]
    assert sr.idle_gaps(window, jobs, ops, spans_) == pytest.approx({
        "entry.seed": 20e-9,               # (0, 20): midpoint 10
        "entry.seed (lowering)": 20e-9,    # (30, 50): midpoint 40
        "entry.wait": 20e-9,               # (60, 80): midpoint 70
        sr.BETWEEN: 10e-9,                 # (90, 100): not in a job
    })
    # a gap under no program span keeps its label, lowering or not
    assert sr.label(40, jobs, [("lower_sharding_computation", 32, 45)]) \
        == sr.UNANNOTATED
    plain = tr.reduce_events(window, jobs, ops)["idle_gaps"]
    assert sr.idle_gaps(window, jobs, ops, ()) == pytest.approx(dict(plain))


def test_the_first_v5e_trace_reduces_as_before(tmp_path):
    """The trace recorded before the program had spans: only JAX's own
    lowering events, so the gap labels are ``trace_reduce``'s."""
    from jax.profiler import ProfileData
    path = unpack(V5E_TRACE, tmp_path)
    pd = ProfileData.from_file(path)
    plain = dict(tr.reduce_file(path)["idle_gaps"])
    assert sr.idle_gaps(*tr.read_events(pd), sr.read_spans(pd)) == \
        pytest.approx(plain, rel=1e-9)


def test_the_v5e_trace_names_the_idle_gaps(tmp_path):
    """Two poker-km.groups jobs traced on a TPU v5e with the program's
    spans, through the harness's profiler options."""
    from jax.profiler import ProfileData
    path = unpack(V5E_SPANS_TRACE, tmp_path)
    pd = ProfileData.from_file(path)
    window, jobs, ops = tr.read_events(pd)
    assert len(jobs) == 2
    found = sr.read_spans(pd)
    program = [(n, s, e) for n, s, e in found if n in spans.NAMES]
    assert {n for n, _, _ in program} >= {
        "entry.job", "entry.transfer", "entry.seed", "entry.config",
        "engine.dispatch", "entry.wait", "entry.readback"}
    # the program's spans lie inside the benchmark's job spans
    assert all(any(js <= s < e <= je for js, je in jobs)
               for _, s, e in program)
    # one clock: the device runs each fit between its dispatch and the
    # end of the host's wait for it (1 ms of skew allowed)
    fits = [(ev.start_ns, ev.end_ns) for plane in pd.planes
            if tr.DEVICE_PLANE.match(plane.name) for line in plane.lines
            if line.name == "XLA Modules" for ev in line.events
            if ev.name.startswith("jit__fit(")]
    dispatch = sorted((s, e) for n, s, e in program
                      if n == "engine.dispatch")
    waits = sorted((s, e) for n, s, e in program if n == "entry.wait")
    assert len(fits) == len(dispatch) == len(waits) == 2
    for (fs, fe), (ds, _), (_, we) in zip(sorted(fits), dispatch, waits):
        assert ds - 1e6 <= fs and fe <= we + 1e6
    gaps = sr.idle_gaps(window, jobs, ops, found)
    inside = sum(v for k, v in gaps.items() if k != sr.BETWEEN)
    assert inside > 0
    assert gaps.get(sr.UNANNOTATED, 0.0) <= 0.1 * inside
    plain = tr.reduce_file(path)
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in plain["idle_gaps"]), rel=1e-6)
    assert sr.job_sums(pd)["jobs"] == plain["jobs_traced"] == 2


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Two small jobs traced on the CPU as the harness traces its window,
    with the totals the program kept over them."""
    root = tmp_path_factory.mktemp("trace")
    x = np.random.default_rng(3).normal(size=(1024, 3)).astype(np.float32)
    run_production(x, 3, "kmeans", 1e-3, max_iters=10, seed=9)  # warm-up
    before = spans.totals()
    jax.profiler.start_trace(str(root / "cell"),
                             profiler_options=harness.profile_options())
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for seed in range(2):
            with jax.profiler.TraceAnnotation(tr.JOB):
                run_production(x, 3, "kmeans", 1e-3, max_iters=10,
                               seed=seed)
    jax.profiler.stop_trace()
    after = spans.totals()
    d = {name: {k: v - before[name][k] for k, v in t.items()}
         for name, t in after.items() if name in before}
    return root, d


def test_job_sums_read_the_spans_the_program_kept(cpu_trace):
    from jax.profiler import ProfileData
    root, d = cpu_trace
    sums = sr.job_sums(ProfileData.from_file(tr.trace_file(str(root))))
    assert sums["jobs"] == 2
    assert sums["seed_lowerings"] == d["entry.seed"]["lowerings"]
    for key, name in (("job_s", "entry.job"), ("wait_s", "entry.wait"),
                      ("seed_s", "entry.seed")):
        assert sums[key] == pytest.approx(d[name]["seconds"], abs=2e-3)


def test_the_new_readers_on_a_hand_made_ctx(cpu_trace, monkeypatch):
    from jax.profiler import ProfileData
    root, d = cpu_trace
    monkeypatch.setattr(sr, "TRACES", root)
    spec = Spec()
    read = {name: spec.reader(name) for name in READERS}
    assert all(spec.reader(f"{name}.small") is not None
               for name in READERS[:3])
    (lo, hi), _, _ = tr.read_events(
        ProfileData.from_file(tr.trace_file(str(root))))
    ctx = {"trace": {"window_s": (hi - lo) / 1e9}, "traced_iters": [5, 6]}
    job = d["entry.job"]["seconds"] - d["entry.wait"]["seconds"]
    assert read["host_path_s"](ctx) == pytest.approx(job / 2, abs=2e-3)
    assert read["seed_s"](ctx) == pytest.approx(
        d["entry.seed"]["seconds"] / 2, abs=2e-3)
    assert read["seed_lowerings"](ctx) == d["entry.seed"]["lowerings"] / 2
    for name, span in (("harvest_s", "stop.harvest"),
                       ("regression_s", "stop.regression")):
        with spans.span(span):
            pass
        assert read[name](ctx) == spans.totals()[span]["seconds"]
    untraced = {"trace": None, "traced_iters": []}
    assert [read[name](untraced) for name in READERS[:3]] == [None] * 3
    # another run's trace: another window, or another number of jobs
    for other in ({"trace": {"window_s": ctx["trace"]["window_s"] + 1e-9},
                   "traced_iters": [5, 6]},
                  {"trace": ctx["trace"], "traced_iters": [5, 6, 7]}):
        with pytest.raises(ValueError, match="not this run's trace"):
            read["host_path_s"](other)


def test_readers_find_nothing_without_the_programs_spans(monkeypatch):
    monkeypatch.setattr(sr, "program_names", lambda: ())
    assert sr.traced({"trace": {"busy_s": 1.0}}) is None
    assert sr.read_spans(type("PD", (), {"planes": []})()) == []
