"""The one generator of clustering jobs, driven by a traffic file's numbers.

A configuration's ``data`` block makes one data set (by the data
generator it names, ``generators/<name>.py``); a traffic file says
how many data sets the pool holds (``datasets``, seeds ``data_seed`` on)
and whether each is one job or is split into groups of ``group_size``
(``null``: the whole data set is the job).  Job ``j`` of the pool is
seeded with ``init_seed = j``.  The pool is the same for every ``--seed``,
so every run does the same work; the seed orders the jobs and draws the
sample that the reference replays.

The stop model trains on its own groups (the configuration's
``stop_model`` block): ``datasets`` data sets from ``data_seed``, each
split into ``groups`` groups of ``group_size`` or taken whole.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Pool(NamedTuple):
    jobs: list          # [N, D] float32 host arrays; job j has init seed j
    train: np.ndarray   # [G, n, D] groups the stop model is fitted on


def random_groups(data: np.ndarray, group_size: int, *, seed: int,
                  max_groups: int | None = None) -> np.ndarray:
    """Shuffle and split into floor(n / group_size) equal groups (a copy of
    the program's ``repro.core.sampling.random_groups``)."""
    rng = np.random.default_rng(seed)
    n_groups = data.shape[0] // group_size
    if max_groups is not None:
        n_groups = min(n_groups, max_groups)
    perm = rng.permutation(data.shape[0])[: n_groups * group_size]
    return data[perm].reshape(n_groups, group_size, data.shape[-1])


def _split(sets, group_size, groups, seed):
    if group_size is None:
        return list(sets)
    out = []
    for i, s in enumerate(sets):
        out.extend(random_groups(s, int(group_size), seed=seed + i,
                                 max_groups=groups))
    return out


def build(config: dict, traffic: dict, make) -> Pool:
    """The pool of jobs and the stop model's groups; ``make(data, seed)``
    makes one data set of the configuration's ``data`` block."""
    def sets(first, count):
        return [make(config["data"], s) for s in range(first, first + count)]

    base = int(config["data_seed"])
    jobs = _split(sets(base, int(traffic["datasets"])),
                  traffic["group_size"], None, base)
    sm = config["stop_model"]
    tseed = int(sm["data_seed"])
    train = _split(sets(tseed, int(sm["datasets"])), sm["group_size"],
                   sm.get("groups"), tseed)
    return Pool(jobs=[np.ascontiguousarray(j) for j in jobs],
                train=np.stack(train))


def order(n_jobs: int, seed: int) -> np.ndarray:
    """The window's order of the pool's jobs for ``--seed``."""
    return np.random.default_rng(seed).permutation(n_jobs)
