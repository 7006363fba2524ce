"""Planner tests (ISSUE 10): pricing edge cases, spot/on-demand crossover,
throughput interpolation off-grid, iteration-model behaviour, the
predicted-vs-actual validation loop on the small skin config, and the
straggler monitor that loop is timed with."""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import pytest

from repro.core.cost_model import (Price, PriceTable, candidate_cost_usd,
                                   expected_spot_wall_s)
from repro.core.planner import (CandidatePlan, IterationModel, PlanError,
                                PlanReport, PlanSpec, ThroughputModel,
                                ThroughputPoint, plan)

# --------------------------------------------------------------------------
# fixtures: a tiny synthetic measured grid + fitted-model stand-ins
# --------------------------------------------------------------------------


def _grid_points():
    """Seconds/iter linear in touched points (1e-6 s/pt at d=1), sharding
    overhead growing with device count — a clean, assertable surface."""
    pts = []
    for mode, frac in (("full", 1.0), ("minibatch", 0.5)):
        for dev, rate in ((1, 1.0e-6), (2, 0.6e-6), (4, 0.4e-6),
                          (8, 0.35e-6)):
            for touched in (10_000.0, 100_000.0):
                pts.append(ThroughputPoint(
                    source="test", mode=mode, backend=None,
                    compression="none", devices=dev,
                    touched_points=touched * frac,
                    s_per_iter=rate * touched * frac))
    return tuple(pts)


@pytest.fixture(scope="module")
def tp():
    return ThroughputModel(points=_grid_points())


class _FakeLM:
    """threshold_for stand-in: a dict of pinned (r* -> h*) values."""

    def __init__(self, thresholds):
        self.thresholds = thresholds

    def threshold_for(self, r):
        return self.thresholds[r]


@pytest.fixture(scope="module")
def models():
    return {"full": _FakeLM({0.99: 1e-3, 0.95: 1e-2}),
            "minibatch": _FakeLM({0.99: 1e-12, 0.95: 5e-2})}


@pytest.fixture(scope="module")
def iteration_models():
    full = IterationModel.from_traces([0.5 * 0.45 ** np.arange(14)] * 3)
    rng = np.random.default_rng(0)
    mb_h = np.maximum(0.3 * 0.9 ** np.arange(128),
                      2e-4 * (1 + 0.1 * rng.standard_normal(128)))
    mb = IterationModel.from_traces([mb_h] * 3)
    return {"full": full, "minibatch": mb}


def _spec(**kw):
    base = dict(n=100_000, d=8, k=8, target_r=0.99, deadline_s=3600.0,
                prices=PriceTable.default(), compressions=("none",))
    base.update(kw)
    return PlanSpec(**base)


# --------------------------------------------------------------------------
# price-table edge cases: loud errors naming the binding constraint
# --------------------------------------------------------------------------


def test_empty_price_table_is_loud(tp, models, iteration_models):
    with pytest.raises(PlanError, match="price table is empty"):
        plan(_spec(prices=PriceTable()), models=models,
             iteration_models=iteration_models, throughput=tp)


def test_infeasible_deadline_names_constraint(tp, models, iteration_models):
    with pytest.raises(PlanError) as e:
        plan(_spec(deadline_s=1e-9), models=models,
             iteration_models=iteration_models, throughput=tp)
    msg = str(e.value)
    # the error must name the binding constraint AND the fastest candidate
    assert "deadline" in msg
    assert "fastest" in msg
    assert "billed wall" in msg


def test_missing_mode_model_is_loud(tp, models, iteration_models):
    with pytest.raises(PlanError, match="no fitted"):
        plan(_spec(modes=("full", "minibatch", "em_mb")), models=models,
             iteration_models=iteration_models, throughput=tp)


def test_uncovered_throughput_cell_is_loud(tp):
    with pytest.raises(PlanError, match="no throughput coverage"):
        tp.seconds_per_iter(1000.0, 1, mode="full", backend="tpu")


def test_price_table_duplicate_and_lookup():
    p = Price(name="a", on_demand_per_hour=1.0)
    with pytest.raises(ValueError, match="duplicate"):
        PriceTable(prices=(p, p))
    t = PriceTable(prices=(p,))
    with pytest.raises(KeyError):
        t.get("nope")
    assert t.get("a") is p
    # JSON round trip
    t2 = PriceTable.from_json(t.to_json())
    assert t2.get("a").on_demand_per_hour == 1.0


def test_price_validation():
    with pytest.raises(ValueError):
        Price(name="bad", on_demand_per_hour=-1.0)
    with pytest.raises(ValueError):
        Price(name="bad", on_demand_per_hour=1.0, spot_per_hour=0.0)
    # spotless rows only offer on_demand
    assert Price(name="od", on_demand_per_hour=1.0).pricings == \
        ("on_demand",)


# --------------------------------------------------------------------------
# spot vs on-demand: expected-restart model + crossover monotonicity
# --------------------------------------------------------------------------


def test_spot_wall_monotone_in_preemption_rate():
    walls = [expected_spot_wall_s(600.0, lam, 4)
             for lam in (0.0, 0.05, 0.2, 1.0, 5.0)]
    assert walls[0] == 600.0                       # no preemption: exact
    assert all(b > a for a, b in zip(walls, walls[1:]))


def test_spot_wall_monotone_in_fleet_size():
    walls = [expected_spot_wall_s(600.0, 0.1, n) for n in (1, 2, 8, 32)]
    assert all(b > a for a, b in zip(walls, walls[1:]))


def test_checkpointing_caps_lost_work():
    lossy = expected_spot_wall_s(3600.0, 0.2, 4)
    ckpt = expected_spot_wall_s(3600.0, 0.2, 4, checkpoint_interval_s=60.0)
    assert ckpt < lossy


def test_spot_on_demand_crossover():
    """Cheap-but-flaky capacity must lose to on-demand once the preemption
    rate is high enough, and the crossing must be monotone: below the
    crossover spot wins everywhere, above it on-demand wins everywhere."""
    wall, n_dev = 1800.0, 4
    costs = []
    for lam in np.linspace(0.0, 20.0, 41):
        p = Price(name="x", on_demand_per_hour=1.0, spot_per_hour=0.6,
                  preemption_per_hour=float(lam))
        spot = candidate_cost_usd(wall, p, n_dev, "spot")
        od = candidate_cost_usd(wall, p, n_dev, "on_demand")
        costs.append((spot, od))
    spot_costs = [s for s, _ in costs]
    od_costs = [o for _, o in costs]
    assert all(o == od_costs[0] for o in od_costs)   # λ never touches OD
    assert all(b >= a for a, b in zip(spot_costs, spot_costs[1:]))
    wins = [s < o for s, o in costs]
    assert wins[0] and not wins[-1]                  # a crossover exists
    assert wins == sorted(wins, reverse=True)        # ... and is monotone


def test_planner_prefers_on_demand_at_high_preemption(
        tp, models, iteration_models):
    def table(lam):
        return PriceTable(prices=(Price(
            name="x", on_demand_per_hour=1.0, spot_per_hour=0.6,
            preemption_per_hour=lam),))

    calm = plan(_spec(prices=table(0.001)), models=models,
                iteration_models=iteration_models, throughput=tp)
    # restart overhead is charged per preemption event; make it dominate
    stormy = plan(_spec(prices=table(1000.0), restart_overhead_s=36000.0),
                  models=models, iteration_models=iteration_models,
                  throughput=tp)
    assert calm.chosen.pricing == "spot"
    assert stormy.chosen.pricing == "on_demand"


# --------------------------------------------------------------------------
# throughput interpolation at off-grid (N, devices)
# --------------------------------------------------------------------------


def test_devices_interpolation_off_grid(tp):
    s2 = tp.seconds_per_iter(50_000, 2, mode="full", backend=None)
    s3 = tp.seconds_per_iter(50_000, 3, mode="full", backend=None)
    s4 = tp.seconds_per_iter(50_000, 4, mode="full", backend=None)
    assert min(s2, s4) <= s3 <= max(s2, s4)
    # log2 interpolation: d=3 sits 58.5% of the way from d=2 to d=4
    t = math.log2(3) - 1
    assert s3 == pytest.approx(s2 + t * (s4 - s2), rel=1e-6)


def test_devices_clamped_beyond_grid(tp):
    s8 = tp.seconds_per_iter(50_000, 8, mode="full", backend=None)
    s16 = tp.seconds_per_iter(50_000, 16, mode="full", backend=None)
    assert s16 == pytest.approx(s8)                  # clamp, no extrapolation


def test_touched_points_interpolation_between_grid(tp):
    # measured at 10k and 100k; 55k must land linearly between them
    s10 = tp.seconds_per_iter(10_000, 1, mode="full", backend=None)
    s55 = tp.seconds_per_iter(55_000, 1, mode="full", backend=None)
    s100 = tp.seconds_per_iter(100_000, 1, mode="full", backend=None)
    assert s10 < s55 < s100
    assert s55 == pytest.approx(s10 + 0.5 * (s100 - s10), rel=1e-6)


def test_touched_points_scaling_beyond_grid(tp):
    # above the largest measurement: linear per-point rate of the top cell
    s100 = tp.seconds_per_iter(100_000, 1, mode="full", backend=None)
    s400 = tp.seconds_per_iter(400_000, 1, mode="full", backend=None)
    assert s400 == pytest.approx(4 * s100, rel=1e-6)


def test_small_n_scales_through_origin(tp):
    s10k = tp.seconds_per_iter(10_000, 1, mode="full", backend=None)
    s1k = tp.seconds_per_iter(1_000, 1, mode="full", backend=None)
    assert s1k == pytest.approx(0.1 * s10k, rel=1e-6)


def test_real_bench_files_load_and_cover_jnp_paths():
    tp_real = ThroughputModel.from_bench_dir()
    assert tp_real.points, "committed BENCH files yielded no points"
    for mode in ("full", "minibatch"):
        s1 = tp_real.seconds_per_iter(50_000, 1, mode=mode, backend=None)
        s8 = tp_real.seconds_per_iter(50_000, 8, mode=mode, backend=None)
        assert s1 > 0 and s8 > 0
    # int8_ef coverage exists for the sharded minibatch path
    s = tp_real.seconds_per_iter(50_000, 4, mode="minibatch", backend=None,
                                 compression="int8_ef")
    assert s > 0


# --------------------------------------------------------------------------
# iteration model
# --------------------------------------------------------------------------


def test_iteration_model_recovers_geometric_decay():
    h = 0.8 * 0.5 ** np.arange(20)
    im = IterationModel.from_traces([h])
    assert im.h0 == pytest.approx(0.8, rel=1e-6)
    assert im.rho == pytest.approx(0.5, rel=1e-6)
    # first i with 0.8 * 0.5^i <= 1e-3 is i = 10
    assert im.iters_to(1e-3, 400) == 10
    assert im.iters_to(1e-3, 400, patience=3) == 12


def test_iteration_model_noise_floor_predicts_max_iters():
    rng = np.random.default_rng(1)
    h = np.maximum(0.3 * 0.9 ** np.arange(200), 1e-3) \
        * (1 + 0.05 * rng.standard_normal(200))
    im = IterationModel.from_traces([h])
    assert im.h_floor > 1e-4
    assert im.iters_to(1e-12, 400) == 400       # below the floor: no stop
    assert im.iters_to(0.1, 400) < 50           # above it: geometric solve


def test_iteration_model_clamps():
    im = IterationModel.from_traces([0.5 * 0.8 ** np.arange(10)])
    assert im.iters_to(0.9, 400) == 1           # h* above h0: first iter
    assert im.iters_to(1e-30, 7) == 7           # clamped to max_iters
    assert im.n_full == 10


def test_iteration_model_empty_traces_is_loud():
    with pytest.raises(PlanError, match="no finite positive h"):
        IterationModel.from_traces([np.zeros(5), np.full(3, np.nan)])


# --------------------------------------------------------------------------
# plan() search semantics + report round trip
# --------------------------------------------------------------------------


def test_plan_noise_floor_routes_to_full_mode(tp, models, iteration_models):
    """At r*=0.99 the minibatch h* (1e-12) sits below the paired-h noise
    floor -> 400 predicted iters; full mode stops geometrically and must
    win even though its per-iteration sweeps touch 2x the points."""
    rep = plan(_spec(), models=models, iteration_models=iteration_models,
               throughput=tp)
    assert rep.chosen.mode == "full"
    mb = [c for c in rep.candidates if c.mode == "minibatch"]
    assert mb and all(c.at_noise_floor for c in mb)
    assert all(c.predicted_iters == 400 for c in mb)


def test_plan_relaxed_target_routes_to_minibatch(tp, models,
                                                 iteration_models):
    rep = plan(_spec(target_r=0.95), models=models,
               iteration_models=iteration_models, throughput=tp)
    assert rep.chosen.mode == "minibatch"
    assert not rep.chosen.at_noise_floor


def test_plan_report_is_sorted_and_priced(tp, models, iteration_models):
    rep = plan(_spec(), models=models, iteration_models=iteration_models,
               throughput=tp)
    costs = [c.predicted_cost_usd for c in rep.candidates if c.feasible]
    assert costs == sorted(costs)
    assert rep.chosen == rep.candidates[0]
    assert rep.chosen.predicted_cost_usd == pytest.approx(min(costs))
    assert 0 < rep.cost_fraction < 1
    assert rep.full_reference["iters"] == iteration_models["full"].n_full


def test_plan_deadline_filters_but_keeps_candidates(tp, models,
                                                    iteration_models):
    # at r*=0.99 the noise-floored minibatch candidates need 400 iters
    # (20s at d=1) — a 10s deadline splits the space without emptying it
    rep = plan(_spec(deadline_s=10.0), models=models,
               iteration_models=iteration_models, throughput=tp)
    slow = [c for c in rep.candidates if not c.feasible]
    assert slow, "expected some candidates to miss the 10s deadline"
    for c in slow:
        assert c.binding_constraint == "deadline_s"
    assert rep.chosen.feasible and rep.chosen.billed_wall_s <= 10.0


def test_plan_int8_gating(tp, models, iteration_models):
    rep = plan(_spec(compressions=("none", "int8_ef")), models=models,
               iteration_models=iteration_models, throughput=tp)
    for c in rep.candidates:
        if c.stats_compression == "int8_ef":
            assert c.mode == "minibatch" and c.devices >= 2


def test_plan_report_json_round_trip(tp, models, iteration_models):
    rep = plan(_spec(), models=models, iteration_models=iteration_models,
               throughput=tp)
    rep2 = PlanReport.from_json(rep.to_json())
    assert rep2.chosen == rep.chosen
    assert rep2.candidates == rep.candidates
    assert rep2.cost_fraction == pytest.approx(rep.cost_fraction)
    assert isinstance(rep2.chosen, CandidatePlan)
    # the chosen row must rebuild a real EngineConfig
    from repro.core.engine import EngineConfig
    cfg = EngineConfig(**rep2.chosen.engine_kwargs())
    assert cfg.mode == rep.chosen.mode


def test_plan_spec_validation():
    with pytest.raises(ValueError, match="target_r"):
        _spec(target_r=1.5)
    with pytest.raises(ValueError, match="deadline_s"):
        _spec(deadline_s=0.0)


def test_candidate_table_renders(tp, models, iteration_models):
    rep = plan(_spec(), models=models, iteration_models=iteration_models,
               throughput=tp)
    txt = rep.table()
    assert "<== chosen" in txt and "cost_usd" in txt


# --------------------------------------------------------------------------
# predicted vs actual on the small skin config (the real fit drivers)
# --------------------------------------------------------------------------


def test_validate_small_skin_config():
    import jax.numpy as jnp
    from repro import core
    from repro.core.planner import ThroughputModel as TM
    from repro.data import load
    from repro.launch.plan import fit_models, validate_plan

    # the harvest regime BENCH_plan.json runs (groups of 6000, chunks=16,
    # batch_chunks=4): small enough for CI, large enough that the tiny-
    # harvest h(r) fit doesn't degenerate (3000-point groups stop too
    # early and miss the accuracy target)
    k, max_iters = 2, 200
    data = load("skin", n=24_000, seed=0)
    groups = core.random_groups(data, 6_000, max_groups=3)
    models, ims = fit_models(groups[:2], algorithm="kmeans", k=k,
                             chunks=16, batch_chunks=4,
                             max_iters=max_iters, seed=0)
    prices = PriceTable.default()
    tp_real = TM.from_bench_dir()
    spec = PlanSpec(n=24_000, d=int(data.shape[1]), k=k, target_r=0.99,
                    deadline_s=3600.0, prices=prices, max_iters=max_iters,
                    chunks=16, batch_chunks=4, device_grid=(1,))
    rep = plan(spec, models=models, iteration_models=ims,
               throughput=tp_real)
    record = validate_plan(rep, jnp.asarray(groups[2], jnp.float32),
                           algorithm="kmeans", k=k, models=models,
                           throughput=tp_real, prices=prices,
                           target_r=0.99, max_iters=max_iters,
                           monitor_steps=6)
    assert record["iters_within_tolerance"], record
    assert record["actual"]["accuracy"] > 0.9, record
    assert record["straggler"]["steps"] == 6
    assert record["predicted"]["cost_usd"] > 0
    assert record["actual"]["cost_usd"] > 0
    assert record["full_actual"]["iters"] >= 1


def test_straggler_monitor_flags_slow_steps():
    import time
    from repro.launch.straggler import StragglerMonitor
    m = StragglerMonitor(window=20, factor=2.0, grace_steps=2)
    for i in range(15):
        m.start()
        time.sleep(0.012 if i == 12 else 0.001)
        flagged = m.stop()
        if i == 12:
            assert flagged
    rep = m.report()
    assert rep["flagged"] >= 1 and rep["steps"] == 15
