"""Seeded stand-in for UCI Poker Hand (paper Table 1): 5 cards x (suit 1-4,
rank 1-13) and a weak hand-type signal, 11 attributes.

A copy of the program's ``repro.data.synthetic.poker``, kept with the
benchmark so that a change to the program's generator cannot change what
the benchmark measures.  ``data``: ``{"n": rows}``.
"""
from __future__ import annotations

import numpy as np


def make(data: dict, seed: int) -> np.ndarray:
    n = int(data["n"])
    rng = np.random.default_rng(seed)
    suits = rng.integers(1, 5, size=(n, 5)).astype(np.float32)
    ranks = rng.integers(1, 14, size=(n, 5)).astype(np.float32)
    # weak class-correlated structure: pairs share ranks
    has_pair = rng.random(n) < 0.42
    ranks[has_pair, 1] = ranks[has_pair, 0]
    cards = np.empty((n, 10), np.float32)
    cards[:, 0::2] = suits
    cards[:, 1::2] = ranks
    hand = has_pair.astype(np.float32) + (ranks.max(1) > 11)
    return np.concatenate([cards, hand[:, None]], axis=-1)
