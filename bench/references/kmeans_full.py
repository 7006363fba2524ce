"""Plain replay of a full-batch k-means job with one restart.

The job is ``run_production`` with ``mode`` full, ``restarts`` 1: k-means++
seeding from the job's init seed, Lloyd sweeps over every point with the
Eq. 7 stop and the frozen-centroid stop, then the labels pass.  Seeding is
in float64; the sweeps are in the dtype of ``x`` with the distances
``dist`` gives (``reference.distances`` for the reference).
"""
from __future__ import annotations

import numpy as np

from bench import reference


def replay(x: np.ndarray, init_seed: int, config: dict,
           dist=reference.distances):
    """(early-stopped fit, full-convergence fit) of job ``init_seed``."""
    regime = config.get("regime", {})
    if regime.get("mode", "full") != "full" or regime.get("restarts", 1) != 1:
        raise KeyError("kmeans_full replays full-batch fits with one restart "
                       f"only, not the regime {regime}")
    c0 = reference.kmeans_pp(init_seed, np.asarray(x, np.float64),
                             config["k"])
    return reference.lloyd(
        x, c0, h_star=config["h_star"], patience=config["patience"],
        max_iters=config["max_iters"], full_max_iters=3 * config["max_iters"],
        dist=dist)
