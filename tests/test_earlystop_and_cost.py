"""LongTail model, sampling strategy, cloud cost model (Eq. 6/9/10)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (LongTailModel, fit_longtail, kfold_split,
                        random_groups, make_grouped, report,
                        landuse_case_study)
from repro.core.cost_model import n_images_for_area, CALIFORNIA_AREA_KM2


def _model(h_at_99=1e-3):
    traces = []
    rng = np.random.default_rng(0)
    for g in range(4):
        r = rng.uniform(0.4, 1.0, 80)
        scale = h_at_99 / (1.83 * (1 - 0.99) ** 2)
        h = scale * 1.83 * (1 - r) ** 2 + rng.normal(0, 1e-6, 80)
        traces.append((r, np.abs(h)))
    return fit_longtail(traces, algorithm="kmeans", dataset="synthetic",
                        family="quadratic")


def test_longtail_json_roundtrip():
    m = _model()
    m2 = LongTailModel.from_json(m.to_json())
    assert m2.regression.coeffs == pytest.approx(m.regression.coeffs)
    assert m2.threshold_for(0.99) == pytest.approx(m.threshold_for(0.99))
    assert m2.algorithm == "kmeans" and m2.n_train_groups == 4


def test_threshold_ordering_matches_paper_table2():
    """h*(90%) > h*(95%) > h*(99%) > h*(99.9%)."""
    m = _model()
    hs = [m.threshold_for(a) for a in (0.90, 0.95, 0.99, 0.999)]
    assert hs == sorted(hs, reverse=True)
    assert hs[0] / hs[2] > 10           # orders of magnitude apart (Table 2)


@given(st.integers(10, 97), st.integers(2, 10))
@settings(max_examples=30, deadline=None)
def test_kfold_partitions(n_groups, n_folds):
    seen = []
    for f in range(n_folds):
        train, val = kfold_split(n_groups, f, n_folds, seed=1)
        assert set(train) | set(val) == set(range(n_groups))
        assert not (set(train) & set(val))
        seen.extend(val.tolist())
    assert sorted(seen) == list(range(n_groups))   # each group val exactly once


def test_random_groups_shapes_and_coverage():
    data = np.arange(1000, dtype=np.float32).reshape(-1, 1)
    g = random_groups(data, 100, seed=0)
    assert g.shape == (10, 100, 1)
    assert len(np.unique(g)) == 1000     # a partition, no duplicates


def test_grouped_pipeline():
    data = np.random.default_rng(0).normal(0, 1, (5000, 3)).astype(np.float32)
    gd = make_grouped(data, 500, fold=0, n_folds=10)
    assert gd.train_groups.shape[0] + gd.val_groups.shape[0] == 10


def test_cost_report_identities():
    r = report(time_actual_s=3600, time_full_s=7200, time_train_s=360,
               instance="m5.large")
    assert r.cost_effectiveness == pytest.approx(0.5)        # Eq. 10
    assert r.time_comp_s == 3960                             # Eq. 9
    assert r.cost_full_usd == pytest.approx(0.096 * 2)       # Eq. 6
    assert r.savings_usd == pytest.approx(0.096 * 2 - 0.096 * 1.1)


def test_landuse_case_study_scale_matches_paper():
    """§5.4: California ≈ 2.567e7 images; training cost ≈ $0.039 negligible."""
    n_img = n_images_for_area(CALIFORNIA_AREA_KM2)
    assert n_img == pytest.approx(2.567e7, rel=0.01)
    rep = landuse_case_study(time_full_per_image_s=5.0, cost_effectiveness=0.6)
    assert rep.cost_train_usd == pytest.approx(0.096 * 1169.46 / 3600,
                                               rel=1e-6)
    assert rep.cost_train_usd < 0.04
    assert rep.savings_usd > 0
    assert rep.savings_usd / rep.cost_full_usd == pytest.approx(0.4, rel=1e-3)
