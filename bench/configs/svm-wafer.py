"""Plain reference of the svm-wafer model: a one-vs-rest linear SVM with
a squared hinge and an L2 term, trained by plain SGD (the paper's §V.A
SVM; the loss of the repo's ``LinearSVM``).

    scores  s = x w + b                                   [B, C]
    loss      = mean_b sum_c max(0, 1 - y_pm s)^2 + l2 |w|^2
    dL/ds     = -2 max(0, 1 - y_pm s) y_pm / B
    w <- w - lr (x^T dL/ds + 2 l2 w),   b <- b - lr sum_b dL/ds

Arrays carry a leading edge axis ``[E, ...]`` so one call steps every
edge.  ``P`` is a ``benchlib.prec.Prec``: float64 for the reference,
one-pass bfloat16 products for its control.  Nothing here imports the
program.
"""

from __future__ import annotations

import numpy as np


def init(cfg: dict, seed: int) -> dict:
    """The model's initial parameters (all zeros, as the repo's SVM
    starts)."""
    del seed
    return {"w": np.zeros((cfg["features"], cfg["classes"]), np.float32),
            "b": np.zeros((cfg["classes"],), np.float32)}


def local_step(P, cfg: dict, p: dict, x: np.ndarray, y: np.ndarray
               ) -> dict:
    """One SGD step on every edge: ``p`` leaves ``[E, ...]``, ``x``
    ``[E, B, D]``, ``y`` ``[E, B]``."""
    lr, l2 = cfg["lr"], cfg["l2"]
    w, b = p["w"], p["b"]
    s = P.r(P.mm(x, w) + b[:, None, :])
    y_pm = 2.0 * (y[..., None] == np.arange(cfg["classes"])) - 1.0
    margin = P.r(np.maximum(0.0, 1.0 - y_pm * s))
    g_s = P.r(-2.0 * margin * y_pm / x.shape[1])
    g_w = P.r(P.mm(np.swapaxes(x, 1, 2), g_s) + 2.0 * l2 * w)
    g_b = P.r(g_s.sum(axis=1))
    return {"w": P.r(w - lr * g_w), "b": P.r(b - lr * g_b)}


def metric(P, cfg: dict, p: dict, eval_set: dict) -> float:
    """Prediction accuracy on the held-out set (the in-run metric)."""
    s = P.r(P.mm(eval_set["x"], p["w"]) + p["b"])
    return float(np.mean(np.argmax(s, axis=-1) == eval_set["y"]))


def step_flops(cfg: dict) -> float:
    """One local step on one edge: the forward x w and the backward
    x^T dL/ds, each 2 B D C."""
    return 4.0 * cfg["batch"] * cfg["features"] * cfg["classes"]


def n_params(cfg: dict) -> int:
    """The weights and the biases: D C + C."""
    return cfg["features"] * cfg["classes"] + cfg["classes"]


def eval_flops(cfg: dict) -> float:
    """The per-aggregation accuracy: x w over the held-out rows,
    2 N_eval D C."""
    n_eval = int(cfg["data"]["samples"] * cfg["data"]["test_frac"])
    return 2.0 * n_eval * cfg["features"] * cfg["classes"]
