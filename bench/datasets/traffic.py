"""Synthetic stand-in for the paper's traffic-camera features (the
K-means data): a Gaussian mixture with weights .5/.3/.2.  The recipe of
the program's ``repro.data.classic_data``, copied."""

import numpy as np


def make(n: int, d: int, k: int, seed: int):
    """``(rng, x, y)``: ``n`` rows of width ``d`` from ``k`` components."""
    rng = np.random.default_rng(seed + 1)
    weights = np.array([0.5, 0.3, 0.2])[:k]
    weights = weights / weights.sum()
    means = rng.normal(0.0, 0.35, size=(k, d))
    y = rng.choice(k, size=n, p=weights)
    x = means[y] + rng.normal(0.0, 1.0, size=(n, d))
    return rng, x.astype(np.float32), y.astype(np.int32)
