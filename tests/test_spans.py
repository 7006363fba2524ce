"""Program spans (``repro.spans``): nesting and totals; lowerings counted
under the innermost open span of their own thread; the spans
``run_production`` and the stop-model fit open; and ``chip_smoke.timed``,
which reads the totals."""
from __future__ import annotations

import importlib.util
import pathlib
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core.longtail_train import TrainingPlan, fit_for_config
from repro.launch.cluster import run_production

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
ENTRY = ("entry.job", "entry.transfer", "entry.seed", "entry.config",
         "engine.dispatch", "entry.wait", "entry.readback")


def delta(before, after):
    """Per name, the growth of every total between two ``totals()``."""
    zero = dict(seconds=0.0, lowerings=0, compile_s=0.0)
    return {name: {k: v - before.get(name, zero)[k] for k, v in t.items()}
            for name, t in after.items()}


@pytest.fixture
def opened(monkeypatch):
    """The names of the spans opened while the test runs, in order."""
    names = []
    annotate = jax.profiler.TraceAnnotation

    def recording(name, **kw):
        names.append(name)
        return annotate(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recording)
    return names


def test_nesting_and_totals(opened):
    before = spans.totals()
    with spans.span("test.outer"):
        time.sleep(0.02)
        with spans.span("test.inner"):
            time.sleep(0.03)
        with spans.span("test.inner"):
            time.sleep(0.01)
    d = delta(before, spans.totals())
    outer, inner = d["test.outer"], d["test.inner"]
    assert opened == ["test.outer", "test.inner", "test.inner"]
    assert inner["seconds"] >= 0.04
    assert outer["seconds"] >= inner["seconds"] + 0.02
    assert outer["lowerings"] == inner["lowerings"] == 0


def test_totals_is_a_copy():
    with spans.span("test.copy"):
        pass
    table = spans.totals()
    table["test.copy"]["seconds"] = -1.0
    assert spans.totals()["test.copy"]["seconds"] >= 0.0


def test_a_span_records_when_its_body_raises():
    before = spans.totals().get("test.raises", {}).get("seconds", 0.0)
    with pytest.raises(RuntimeError):
        with spans.span("test.raises"):
            time.sleep(0.01)
            raise RuntimeError("boom")
    assert spans.totals()["test.raises"]["seconds"] >= before + 0.01
    # the stack was unwound: a lowering now counts under a top-level span
    fresh = jax.jit(lambda a: a - 2.0)
    before = spans.totals()
    with spans.span("test.after"):
        fresh(jnp.arange(3.0)).block_until_ready()
    d = delta(before, spans.totals())
    assert d["test.after"]["lowerings"] >= 1
    assert d.get("test.raises", {}).get("lowerings", 0) == 0


def test_threads_keep_their_own_nesting():
    """A lowering counts under the span open in the thread that lowers,
    not under one another thread holds open meanwhile."""
    held, done = threading.Event(), threading.Event()

    def hold():
        with spans.span("test.thread.idle"):
            held.set()
            assert done.wait(timeout=60)

    before = spans.totals()
    other = threading.Thread(target=hold)
    other.start()
    try:
        assert held.wait(timeout=60)
        fresh = jax.jit(lambda a: a * 5.0 - 1.0)
        with spans.span("test.thread.busy"):
            fresh(jnp.arange(5.0)).block_until_ready()
    finally:
        done.set()
        other.join(timeout=60)
    assert not other.is_alive()
    d = delta(before, spans.totals())
    assert d["test.thread.busy"]["lowerings"] >= 1
    assert d["test.thread.idle"]["lowerings"] == 0
    assert d["test.thread.idle"]["compile_s"] == 0


def test_a_lowering_counts_under_the_innermost_span():
    fresh = jax.jit(lambda a: a * 3.0 + 1.0)
    x = jnp.arange(7.0)
    before = spans.totals()
    with spans.span("test.parent"):
        with spans.span("test.child"):
            fresh(x).block_until_ready()
    d = delta(before, spans.totals())
    assert d["test.child"]["lowerings"] >= 1
    assert d["test.child"]["compile_s"] > 0
    assert d["test.parent"]["lowerings"] == 0
    assert d["test.parent"]["compile_s"] == 0
    # cached: the second call lowers nothing
    before = spans.totals()
    with spans.span("test.child"):
        fresh(x).block_until_ready()
    assert delta(before, spans.totals())["test.child"]["lowerings"] == 0


def test_names_are_the_spans_the_program_opens():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'spans\.span\("([^"]+)"\)',
                                 path.read_text()))
    assert opened == set(spans.NAMES)
    assert len(set(spans.NAMES)) == len(spans.NAMES)


BRANCHES = {
    "plain": dict(),
    "restarts": dict(restarts=2),
    "em_restarts": dict(algorithm="em", restarts=2),
    "sharded": dict(shard=True),
    "sharded_restarts": dict(shard=True, restarts=2),
    "minibatch": dict(mode="minibatch", chunks=4, batch_chunks=2),
}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_run_production_opens_each_entry_span_once(branch, opened):
    kw = dict(BRANCHES[branch])
    if kw.get("shard") and jax.device_count() < 2:
        pytest.skip("the sharded branch needs more than one device")
    algorithm = kw.pop("algorithm", "kmeans")
    x = np.random.default_rng(0).normal(size=(2048, 3)).astype(np.float32)
    for calls in (1, 2):
        opened.clear()
        before = spans.totals()
        for seed in range(calls):
            labels, _, n_iters, fit_s = run_production(
                x, 3, algorithm, 1e-3, max_iters=8, seed=seed, **kw)
        d = delta(before, spans.totals())
        assert {name: opened.count(name) for name in ENTRY} == \
            dict.fromkeys(ENTRY, calls)
        assert set(opened) <= set(spans.NAMES)
        assert labels.shape == (2048,) and 1 <= n_iters <= 8
        if calls == 1:
            # the returned fit seconds run from the engine call to labels
            # ready: they hold its dispatch and the wait
            assert d["engine.dispatch"]["seconds"] \
                + d["entry.wait"]["seconds"] <= fit_s \
                <= d["entry.job"]["seconds"]
        # the job span holds the others
        inside = sum(d[name]["seconds"] for name in ENTRY[1:])
        assert inside <= d["entry.job"]["seconds"]


def test_seeding_lowers_once_per_shape_k_and_chunks():
    """A job of a shape already seen seeds from the compiled program: no
    lowering under ``entry.seed``.  A new ``k``, ``chunks`` or N is a new
    program and lowers once."""
    x = np.random.default_rng(3).normal(size=(1792, 3)).astype(np.float32)

    def seed_lowerings(x, k, seed, chunks=1):
        before = spans.totals()
        run_production(x, k, "kmeans", 1e-3, max_iters=8, seed=seed,
                       chunks=chunks)
        return delta(before, spans.totals())["entry.seed"]["lowerings"]

    assert seed_lowerings(x, 3, 0) >= 1
    assert seed_lowerings(x, 3, 1) == 0
    assert seed_lowerings(x, 3, 2) == 0
    assert seed_lowerings(x, 4, 3) >= 1
    assert seed_lowerings(x, 3, 4, chunks=2) >= 1
    assert seed_lowerings(x[:1791], 3, 5) >= 1


def test_the_stop_model_fit_opens_harvest_and_regression(opened):
    rng = np.random.default_rng(1)
    groups = [np.concatenate([rng.normal(0, 1, (300, 2)),
                              rng.normal(6, 1, (300, 2))]).astype(np.float32)
              for _ in range(2)]
    before = spans.totals()
    fit_for_config(TrainingPlan(k=2, max_iters=20, family="quadratic"),
                   groups)
    d = delta(before, spans.totals())
    assert opened.count("stop.harvest") == 1
    assert opened.count("stop.regression") == 1
    assert opened.count("engine.dispatch") == len(groups)
    assert d["stop.harvest"]["seconds"] > d["engine.dispatch"]["seconds"]
    assert d["stop.regression"]["seconds"] > 0
    # the harvest's fits come before the regression, not inside it
    assert opened.index("stop.regression") > opened.index("stop.harvest") \
        + len(groups)


def test_chip_smoke_times_compiles_from_the_totals():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    fresh = jax.jit(lambda a: a * 7.0 + 2.0)
    x = jnp.arange(9.0)
    with smoke.timed() as cold:
        fresh(x).block_until_ready()
    with smoke.timed() as warm:
        fresh(x).block_until_ready()
    assert cold["lowerings"] >= 1 and cold["compile_s"] > 0
    assert warm["lowerings"] == 0 and warm["compile_s"] == 0
    assert cold["run_s"] >= 0 and warm["run_s"] >= 0
