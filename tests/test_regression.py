"""Regression fitting + model selection (paper Eq. 8, §4)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import fit_family, select_model, FAMILIES
from repro.core.regression import pool_traces


def _quad_cloud(b0, b1, b2, noise, n=200, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.uniform(0.3, 1.0, n)
    h = b0 + b1 * r + b2 * r * r + rng.normal(0, noise, n)
    return r, h


def test_quadratic_recovery():
    r, h = _quad_cloud(1.8, -3.6, 1.8, 1e-4)
    m = fit_family(r, h, "quadratic")
    assert np.allclose(m.coeffs, [1.8, -3.6, 1.8], atol=5e-3)
    assert m.metrics.r2 > 0.999


def test_selection_prefers_quadratic_on_quadratic_data():
    r, h = _quad_cloud(1.83, -3.66, 1.83, 5e-4)
    best, table = select_model(r, h)
    assert set(table) == set(FAMILIES)
    # quadratic or cubic (which nests it) must win; linear must not
    assert best.family in ("quadratic", "cubic", "lasso_quadratic")
    assert table["quadratic"].adj_r2 > table["linear"].adj_r2


def test_exponential_fit_on_exponential_data():
    rng = np.random.default_rng(1)
    r = rng.uniform(0.2, 1.0, 300)
    h = 0.5 * np.exp(-6.0 * r)
    m = fit_family(r, h, "exponential")
    assert m.coeffs[0] == pytest.approx(0.5, rel=1e-3)
    assert m.coeffs[1] == pytest.approx(-6.0, rel=1e-3)


@given(st.floats(0.90, 0.999), st.floats(0.5, 3.0))
@settings(max_examples=25, deadline=None)
def test_threshold_monotone_decreasing_in_accuracy(acc, scale):
    """Higher desired accuracy → smaller (or equal) h* (paper Table 2)."""
    r, h = _quad_cloud(scale, -2 * scale, scale, 1e-5, seed=3)
    m = fit_family(r, h, "quadratic")
    assert m.threshold_for(acc) >= m.threshold_for(min(acc + 0.005, 0.9999)) \
        - 1e-12


def test_threshold_floor():
    r, h = _quad_cloud(1.0, -2.0, 1.0, 1e-6)   # h(1) = 0 exactly
    m = fit_family(r, h, "quadratic")
    assert m.threshold_for(1.0) >= 1e-12


def test_pool_traces_filters_nonfinite():
    r, h = pool_traces([(np.array([0.5, np.nan, 0.9]),
                         np.array([0.1, 0.2, np.inf]))])
    assert r.shape == (1,) and h.shape == (1,)


@given(st.integers(2, 6))
@settings(max_examples=10, deadline=None)
def test_lasso_close_to_ols_with_tiny_penalty(seed):
    """Coefficients of the quadratic basis are ill-conditioned — compare
    the fitted *curves*, not the raw coefficients."""
    r, h = _quad_cloud(1.2, -2.4, 1.2, 1e-4, seed=seed)
    ols = fit_family(r, h, "quadratic")
    lasso = fit_family(r, h, "lasso_quadratic")
    grid = np.linspace(0.3, 1.0, 50)
    scale = float(np.max(np.abs(ols.predict(grid))))
    assert np.allclose(np.asarray(ols.predict(grid)),
                       np.asarray(lasso.predict(grid)),
                       atol=0.05 * scale + 1e-3)


# one member of each family, decreasing on [0, 1] (the shape of h(r))
_R = np.linspace(0.0, 1.0, 201)
FAMILY_CURVES = {
    "linear": 0.5 - 0.2 * _R,
    "quadratic": 1.0 - 1.2 * _R + 0.3 * _R ** 2,
    "cubic": 1.0 - 1.5 * _R + 0.9 * _R ** 2 - 0.3 * _R ** 3,
    "exponential": 0.5 * np.exp(-6.0 * _R),
    "lasso_quadratic": 1.0 - 1.2 * _R + 0.3 * _R ** 2,
    "log_quadratic": np.exp(-1.0 - 4.0 * _R - _R ** 2),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_family_fits_its_own_curve(family):
    m = fit_family(_R, FAMILY_CURVES[family], family)
    assert m.family == family
    assert m.metrics.r2 > 0.999, m.metrics


@pytest.mark.parametrize("family", FAMILIES)
def test_threshold_for_is_the_prediction_on_a_decreasing_curve(family):
    """On a curve that decreases up to r*, h* = f(r*): the running-min
    guard only bites past a vertex, and its grid ends at r* itself."""
    from repro.core.regression import H_STAR_FLOOR
    m = fit_family(_R, FAMILY_CURVES[family], family)
    want = max(float(m.predict(0.99)), H_STAR_FLOOR)
    assert m.threshold_for(0.99) == pytest.approx(want, rel=1e-6)
