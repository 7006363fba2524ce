"""Seeded SpaceNet-like image, flattened to its pixels (image = group, paper
section 5.2): ``k_true`` spatially smooth regions, each one of six fixed
spectral signatures, with noise.

A copy of the program's ``repro.data.synthetic.spacenet_images`` for one
image, kept with the benchmark so that a change to the program's generator
cannot change what the benchmark measures.  ``data``:
``{"image_shape": [H, W, C], "k_true": regions}``.
"""
from __future__ import annotations

import numpy as np


def make(data: dict, seed: int) -> np.ndarray:
    h, w, c = (int(v) for v in data["image_shape"])
    k_true = int(data["k_true"])
    rng = np.random.default_rng(seed)
    # fixed spectral signatures (forest, water, road, building, grass, waste)
    sigs = np.array([[40, 90, 40], [20, 40, 90], [90, 90, 95],
                     [150, 130, 120], [90, 140, 60], [130, 110, 80]],
                    np.float32)[:k_true]
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    field = np.zeros((h, w, k_true), np.float32)
    for k in range(k_true):
        for _ in range(3):
            fy, fx = rng.uniform(0.5, 3.0, 2)
            py, px = rng.uniform(0, 2 * np.pi, 2)
            field[:, :, k] += rng.uniform(0.4, 1.0) * np.sin(
                2 * np.pi * fy * yy / h + py) * np.cos(
                    2 * np.pi * fx * xx / w + px)
    img = sigs[field.argmax(-1)] + rng.normal(0, 9.0, (h, w, c))
    return np.clip(img, 0, 255).astype(np.float32).reshape(h * w, c)
