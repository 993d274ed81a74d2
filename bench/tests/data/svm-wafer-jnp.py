"""The svm-wafer model's plain reference in ``jax.numpy``, with the
interface of the ``device-f32`` check (``bench/checks/device-f32.py``):
a one-vs-rest linear SVM with a squared hinge and an L2 term, one SGD
step of one edge at a time.

    scores  s = x w + b                                   [B, C]
    loss      = mean_b sum_c max(0, 1 - y_pm s)^2 + l2 |w|^2
    dL/ds     = -2 max(0, 1 - y_pm s) y_pm / B
    w <- w - lr (x^T dL/ds + 2 l2 w),   b <- b - lr sum_b dL/ds

The tests run svm-wafer's cells with it in place of the numpy reference
to check the device check itself.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def init(cfg: dict, seed: int) -> dict:
    """All zeros, as the repo's SVM starts."""
    del seed
    return {"w": np.zeros((cfg["features"], cfg["classes"]), np.float32),
            "b": np.zeros((cfg["classes"],), np.float32)}


def edge_step(M, cfg: dict, p: dict, x, y) -> dict:
    """One SGD step of one edge: ``x`` ``[B, D]``, ``y`` ``[B]``."""
    import jax.numpy as jnp
    lr, l2 = cfg["lr"], cfg["l2"]
    w, b = p["w"], p["b"]
    s = M.mm(x, w) + b
    y_pm = 2.0 * (y[:, None] == jnp.arange(cfg["classes"])) - 1.0
    margin = jnp.maximum(0.0, 1.0 - y_pm * s)
    g_s = -2.0 * margin * y_pm / x.shape[0]
    g_w = M.mm(x.T, g_s) + 2.0 * l2 * w
    return {"w": w - lr * g_w, "b": b - lr * g_s.sum(axis=0)}


def eval_metric(M, cfg: dict, p: dict, eval_set: dict):
    """Prediction accuracy on the held-out rows."""
    import jax.numpy as jnp
    s = M.einsum("nd,dc->nc", eval_set["x"], p["w"]) + p["b"]
    return jnp.mean(jnp.argmax(s, axis=-1) == eval_set["y"])


def weighted_sum(acc, p: dict, w) -> dict:
    return {k: w * v if acc is None else acc[k] + w * v
            for k, v in p.items()}


def step_flops(cfg: dict) -> float:
    return 4.0 * cfg["batch"] * cfg["features"] * cfg["classes"]


def n_params(cfg: dict) -> int:
    return cfg["features"] * cfg["classes"] + cfg["classes"]


def eval_flops(cfg: dict) -> float:
    n_eval = int(cfg["data"]["samples"] * cfg["data"]["test_frac"])
    return 2.0 * n_eval * cfg["features"] * cfg["classes"]
