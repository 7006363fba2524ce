"""Regression between objective change-rate h and clustering accuracy r (Eq. 8).

The paper fits  h = β₀ + β₁·r + β₂·r²  on (r_i, h_i) pairs harvested from the
training groups, after comparing regression families by SSE / R² / adj-R² /
RMSE and finding the quadratic polynomial best in most cases.  We implement
the full family comparison so the selection claim itself is reproducible:

    linear, quadratic, cubic        — polynomial least squares
    exponential  h = a·exp(b·r)     — log-space linear fit (h > 0 required)
    lasso-quadratic                 — L1 on the quadratic basis (coord. descent)

Fitting is closed-form / deterministic JAX (no sklearn), so the same code
runs on-device inside the distributed pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# h spans decades down to ~1e-6: the fits and predictions need f32
# matmuls, and the TPU's default precision multiplies in bf16
_HI = jax.lax.Precision.HIGHEST

FAMILIES = ("linear", "quadratic", "cubic", "exponential", "lasso_quadratic",
            "log_quadratic")

# the smallest h* threshold_for returns: a fit that reaches h <= 0 below r*
# lands here, and a stop at it waits for the fit to stop moving at all
H_STAR_FLOOR = 1e-12


@dataclasses.dataclass(frozen=True)
class FitMetrics:
    sse: float
    rmse: float
    r2: float
    adj_r2: float


@dataclasses.dataclass(frozen=True)
class RegressionModel:
    """A fitted h(r) model.  ``coeffs`` meaning depends on family."""
    family: str
    coeffs: tuple[float, ...]
    metrics: FitMetrics

    def predict(self, r):
        r = jnp.asarray(r)
        c = jnp.asarray(self.coeffs)
        if self.family in ("linear", "quadratic", "cubic", "lasso_quadratic"):
            # coeffs = (β₀, β₁, …) low-to-high degree
            powers = jnp.stack([r ** p for p in range(len(self.coeffs))], axis=-1)
            return jnp.matmul(powers, c, precision=_HI)
        if self.family == "exponential":
            a, b = self.coeffs
            return a * jnp.exp(b * r)
        if self.family == "log_quadratic":
            # log h = β₀ + β₁ r + β₂ r² — handles h spanning many decades
            # (EM tails); beyond-paper family, sanctioned by §5.5.
            b0, b1, b2 = self.coeffs
            return jnp.exp(b0 + b1 * r + b2 * r * r)
        raise ValueError(f"unknown family {self.family}")

    def threshold_for(self, desired_accuracy: float,
                      floor: float = H_STAR_FLOOR) -> float:
        """h* = f(r*): the change-rate threshold for a desired accuracy (§4).

        The fitted curve should be decreasing in r; a noisy quadratic can
        turn up before r = 1 (vertex v < 1), which would make a HIGHER
        desired accuracy produce a LARGER threshold (stop earlier).  Guard:
        use the monotone (running-min-from-the-left) envelope
        h*(r*) = min_{r' ≤ r*} f(r') — equal to f(r*) on the physical
        decreasing branch, clamped at f(v) beyond the vertex — with a small
        positive floor (h* ≤ 0 would never trigger)."""
        grid = jnp.linspace(0.0, desired_accuracy, 256)
        h = float(jnp.min(self.predict(grid)))
        return max(h, floor)


def _metrics(h: jnp.ndarray, pred: jnp.ndarray, n_params: int) -> FitMetrics:
    resid = h - pred
    sse = float(jnp.sum(resid ** 2))
    n = h.shape[0]
    rmse = float(jnp.sqrt(sse / max(n, 1)))
    ss_tot = float(jnp.sum((h - jnp.mean(h)) ** 2))
    r2 = 1.0 - sse / ss_tot if ss_tot > 0 else 1.0
    denom = n - n_params - 1
    adj = 1.0 - (1.0 - r2) * (n - 1) / denom if denom > 0 else r2
    return FitMetrics(sse=sse, rmse=rmse, r2=r2, adj_r2=adj)


def _polyfit(r: jnp.ndarray, h: jnp.ndarray, degree: int) -> jnp.ndarray:
    """Least-squares polynomial fit via QR on the Vandermonde matrix."""
    powers = jnp.stack([r ** p for p in range(degree + 1)], axis=-1)
    with jax.default_matmul_precision("highest"):
        coeffs, *_ = jnp.linalg.lstsq(powers, h, rcond=None)
    return coeffs


def _lasso_quadratic(r: jnp.ndarray, h: jnp.ndarray, lam: float = 1e-4,
                     iters: int = 5000) -> jnp.ndarray:
    """Coordinate-descent LASSO on the quadratic basis (deterministic)."""
    X = jnp.stack([jnp.ones_like(r), r, r ** 2], axis=-1)
    col_sq = jnp.sum(X ** 2, axis=0)

    def body(_, beta):
        def update(j, b):
            resid = h - jnp.matmul(X, b, precision=_HI) + X[:, j] * b[j]
            rho = jnp.dot(X[:, j], resid, precision=_HI)
            bj = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0) / jnp.maximum(col_sq[j], 1e-12)
            return b.at[j].set(bj)
        return jax.lax.fori_loop(0, 3, update, beta)

    return jax.lax.fori_loop(0, iters, body, jnp.zeros((3,), h.dtype))


def fit_family(r, h, family: str) -> RegressionModel:
    r = jnp.asarray(r, jnp.float32).reshape(-1)
    h = jnp.asarray(h, jnp.float32).reshape(-1)
    if family == "linear":
        c = _polyfit(r, h, 1)
    elif family == "quadratic":
        c = _polyfit(r, h, 2)
    elif family == "cubic":
        c = _polyfit(r, h, 3)
    elif family == "exponential":
        # h = a·exp(b·r) → log h = log a + b·r on h > eps points.
        eps = 1e-30
        mask = h > eps
        # keep shapes static: weight invalid points to 0 in the normal equations
        w = mask.astype(h.dtype)
        logh = jnp.log(jnp.maximum(h, eps))
        sw = jnp.sum(w)
        mr = jnp.sum(w * r) / jnp.maximum(sw, 1.0)
        ml = jnp.sum(w * logh) / jnp.maximum(sw, 1.0)
        cov = jnp.sum(w * (r - mr) * (logh - ml))
        var = jnp.sum(w * (r - mr) ** 2)
        b = cov / jnp.maximum(var, 1e-12)
        a = jnp.exp(ml - b * mr)
        c = jnp.stack([a, b])
    elif family == "lasso_quadratic":
        c = _lasso_quadratic(r, h)
    elif family == "log_quadratic":
        eps = 1e-30
        w = (h > eps).astype(h.dtype)
        logh = jnp.log(jnp.maximum(h, eps))
        X = jnp.stack([jnp.ones_like(r), r, r * r], axis=-1) * w[:, None]
        with jax.default_matmul_precision("highest"):
            c, *_ = jnp.linalg.lstsq(X, logh * w, rcond=None)
    else:
        raise ValueError(f"unknown family {family}")
    coeffs = tuple(float(x) for x in np.asarray(c))
    model = RegressionModel(family=family, coeffs=coeffs,
                            metrics=FitMetrics(0, 0, 0, 0))
    pred = model.predict(r)
    return dataclasses.replace(model, metrics=_metrics(h, pred, len(coeffs)))


def select_model(r, h, families: Sequence[str] = FAMILIES) -> tuple[RegressionModel, dict]:
    """Fit every family; select by adjusted R² (paper §4: SSE/R²/adjR²/RMSE).

    Returns (best_model, {family: FitMetrics}) so benchmarks can report the
    whole comparison table (paper's internal-validity discussion, §5.5).
    """
    fits = {fam: fit_family(r, h, fam) for fam in families}
    table = {fam: m.metrics for fam, m in fits.items()}
    best = max(fits.values(), key=lambda m: m.metrics.adj_r2)
    return best, table


def rh_from_objectives(objectives: np.ndarray) -> np.ndarray:
    """h_i = |J_i − J_{i−1}| / |J_{i−1}| over a recorded objective sequence
    (Eq. 7 applied host-side) — one copy of the conversion every harvest /
    benchmark consumer used to hand-roll.  Returns h aligned with J[1:]."""
    js = np.asarray(objectives, np.float64).reshape(-1)
    return np.abs(np.diff(js)) / np.maximum(np.abs(js[:-1]), 1e-30)


def pool_traces(traces: Sequence[tuple[np.ndarray, np.ndarray]]):
    """Concatenate (r_i, h_i) traces from many training groups into one cloud.

    Drops the i=1 point of each trace (h₁ undefined, Eq. 7 starts at i=2) —
    callers pass aligned arrays where h[j] corresponds to r[j].
    """
    rs = np.concatenate([np.asarray(t[0], np.float64).reshape(-1) for t in traces])
    hs = np.concatenate([np.asarray(t[1], np.float64).reshape(-1) for t in traces])
    ok = np.isfinite(rs) & np.isfinite(hs)
    return rs[ok], hs[ok]


def balance_cloud(r: np.ndarray, h: np.ndarray, bins: int = 40):
    """r-binned geometric-mean aggregation of an (r, h) cloud.

    Long-tailed traces put most points at r ≈ 1; unweighted least squares
    then ignores the transition region the threshold lives in.  Balancing
    (one aggregate point per occupied r-bin; geometric mean because h spans
    decades) makes the fit see the whole accuracy range.  Beyond-paper
    robustification — the faithful path fits the raw cloud.
    """
    r = np.asarray(r, np.float64)
    h = np.asarray(h, np.float64)
    keep = h > 0
    r, h = r[keep], h[keep]
    if r.size == 0:
        return r, h
    edges = np.linspace(min(r.min(), 0.0), 1.0 + 1e-9, bins + 1)
    which = np.clip(np.digitize(r, edges) - 1, 0, bins - 1)
    rb, hb = [], []
    for b in range(bins):
        m = which == b
        if m.any():
            rb.append(r[m].mean())
            hb.append(np.exp(np.log(h[m]).mean()))
    return np.asarray(rb), np.asarray(hb)
