"""The paper's contribution as a reusable control layer.

``LongTailModel`` packages the offline-trained regression:  set a desired
accuracy r*, get the change-rate threshold h* = f(r*) (the fitted Eq. 8
curve), and stop the iterative process the first time
h_i = |J_i − J_{i−1}|/|J_{i−1}| ≤ h*  (Eq. 7, §4).  The dollars that stop
saves are accounted by ``cost_model`` (Eq. 6/9/10); the provisioning
planner (``core.planner``) turns h* into a predicted stop iteration.

The consumer is the clustering engine (``core.engine``): the predicate runs
**on device** inside ``jax.lax.while_loop``, with no host round-trip per
iteration.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from .regression import RegressionModel, FitMetrics, select_model, pool_traces


def change_rate(j_curr, j_prev, eps: float = 1e-30):
    """h_i = |J_i − J_{i−1}| / |J_{i−1}|   (Eq. 7). Safe at J≈0."""
    return jnp.abs(j_curr - j_prev) / jnp.maximum(jnp.abs(j_prev), eps)


@dataclasses.dataclass(frozen=True)
class LongTailModel:
    """Fitted h(r) regression + provenance, serialisable for reuse (§5.4:

    the training process runs once; the regression is applied repeatedly).

    ``engine_config`` records the engine regime the (r, h) traces were
    harvested under (mode, batch_chunks, decay, ema, kernel routing, chunk
    layout, device count — see ``longtail_train.config_fingerprint``);
    ``EngineConfig.from_longtail`` compares it against the production
    config and warns loudly on a mismatch.  ``None`` marks a legacy /
    externally-harvested fit with no stamped regime (no warning)."""
    regression: RegressionModel
    algorithm: str                  # "kmeans" | "em"
    dataset: str
    n_train_groups: int
    comparison: dict | None = None  # {family: FitMetrics} from model selection
    engine_config: dict | None = None   # harvest-regime provenance

    def threshold_for(self, desired_accuracy: float) -> float:
        """h* = f(r*) — evaluate the fitted Eq. 8 regression at the
        desired accuracy (§4: the one number reused forever)."""
        return self.regression.threshold_for(desired_accuracy)

    # ---- persistence (tiny JSON artifacts, checkpointed with the run) ----
    def to_json(self) -> str:
        d = {
            "family": self.regression.family,
            "coeffs": list(self.regression.coeffs),
            "metrics": dataclasses.asdict(self.regression.metrics),
            "algorithm": self.algorithm,
            "dataset": self.dataset,
            "n_train_groups": self.n_train_groups,
        }
        if self.comparison is not None:
            d["comparison"] = {k: dataclasses.asdict(v) for k, v in self.comparison.items()}
        if self.engine_config is not None:
            d["engine_config"] = self.engine_config
        return json.dumps(d, indent=2)

    @staticmethod
    def from_json(s: str) -> "LongTailModel":
        d = json.loads(s)
        reg = RegressionModel(family=d["family"], coeffs=tuple(d["coeffs"]),
                              metrics=FitMetrics(**d["metrics"]))
        comparison = None
        if "comparison" in d:
            comparison = {k: FitMetrics(**v) for k, v in d["comparison"].items()}
        return LongTailModel(regression=reg, algorithm=d["algorithm"],
                             dataset=d["dataset"],
                             n_train_groups=d["n_train_groups"],
                             comparison=comparison,
                             engine_config=d.get("engine_config"))


def fit_longtail(traces: Sequence[tuple[np.ndarray, np.ndarray]], *,
                 algorithm: str, dataset: str, family: str | None = None,
                 balanced: bool = False,
                 engine_config: dict | None = None) -> LongTailModel:
    """Pool (r, h) traces from the training groups and fit h = f(r) —
    the Eq. 8 regression (§4, training phase).

    ``family=None`` runs the paper's Eq. 8 model-selection comparison
    (linear/quadratic/exponential/…, lowest fit error wins) and keeps the
    winner; passing e.g. ``"quadratic"`` pins the paper's default.
    ``balanced=True`` applies the r-binned geometric-mean aggregation before
    fitting (beyond-paper robustification — see regression.balance_cloud).
    ``engine_config`` stamps harvest-regime provenance onto the model (see
    ``LongTailModel``); the mode-matched trainer always passes it.
    """
    r, h = pool_traces(traces)
    if balanced:
        from .regression import balance_cloud
        r, h = balance_cloud(r, h)
    if family is None:
        best, table = select_model(r, h)
    else:
        from .regression import fit_family
        best, table = fit_family(r, h, family), None
    return LongTailModel(regression=best, algorithm=algorithm, dataset=dataset,
                         n_train_groups=len(traces), comparison=table,
                         engine_config=engine_config)

