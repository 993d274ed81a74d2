"""Synthetic stand-in for the paper's wafer images (the SVM's data):
anisotropic Gaussian class clusters with partial overlap, standardized.
The recipe of the program's ``repro.data.classic_data``, copied."""

import numpy as np


def make(n: int, d: int, n_classes: int, seed: int):
    """``(rng, x, y)``: ``n`` rows of width ``d`` over ``n_classes``."""
    rng = np.random.default_rng(seed)
    means = rng.normal(0.0, 0.55, size=(n_classes, d))
    basis = rng.normal(0.0, 1.0, size=(d, d))
    scales = np.exp(rng.normal(0.0, 0.4, size=d))
    y = rng.integers(0, n_classes, size=n)
    x = means[y] + rng.normal(0.0, 1.0, size=(n, d)) * scales
    x = x @ (basis / np.sqrt(d))
    x = (x - x.mean(0)) / (x.std(0) + 1e-6)
    return rng, x.astype(np.float32), y.astype(np.int32)
