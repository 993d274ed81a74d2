"""Plain reference of the kmeans-traffic model: minibatch Lloyd K-means
(the paper's §V.A K-means; the update of the repo's ``KMeans``).

    E-step   a_i = argmin_k |x_i - c_k|^2                 (the
             ``kmeans_assign`` kernel's job in the program)
    M-step   m_k = mean of the batch rows assigned to k
             c_k <- (1 - r) c_k + r m_k   where row k got any,  r = blend lr

The metric is the macro F1 after a greedy majority cluster->class
mapping.  Arrays carry a leading edge axis ``[E, ...]``.  ``P`` is a
``benchlib.prec.Prec``.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def init(cfg: dict, seed: int) -> dict:
    """Centroids drawn N(0, 1) from ``seed``."""
    rng = np.random.default_rng(seed)
    return {"centers": rng.normal(
        size=(cfg["classes"], cfg["features"])).astype(np.float32)}


#: two centroids whose distances from a point differ by less than this
#: share of the distances' terms (``|x|^2 + 2|x.c| + |c|^2``) are tied
#: for the program's float32 E-step, 16 times float32's epsilon: its
#: rounding of the terms and its centroids' own float32 drift (a
#: ``param_gap`` near 1e-7) move a margin by a few epsilon, so it may
#: assign such a point to either (``benchlib.elref.Ties``)
TIE_WIDTH = 16 * float(np.finfo(np.float32).eps)


def _assign(P, x: np.ndarray, c: np.ndarray, pick=None) -> np.ndarray:
    x2 = P.r(np.sum(x * x, axis=-1, keepdims=True))
    xc = P.mm(x, np.swapaxes(c, -1, -2))
    c2 = P.r(np.sum(c * c, axis=-1))[..., None, :]
    d2 = P.r(x2 - 2.0 * xc + c2)
    if pick is None:
        return np.argmin(d2, axis=-1)
    return pick(d2, x2 + 2.0 * np.abs(xc) + c2)


def local_step(P, cfg: dict, p: dict, x: np.ndarray, y: np.ndarray,
               pick=None) -> dict:
    """One minibatch Lloyd step on every edge: ``centers`` ``[E, K, D]``,
    ``x`` ``[E, B, D]``; ``pick(d2, scale)``, where given, makes the
    assignment in place of the argmin."""
    del y
    c = p["centers"]
    a = _assign(P, x, c, pick)                            # [E, B]
    onehot = (a[..., None] == np.arange(c.shape[-2])).astype(x.dtype)
    counts = onehot.sum(axis=1)                           # [E, K]
    sums = P.mm(np.swapaxes(onehot, 1, 2), x)             # [E, K, D]
    new = P.r(sums / np.maximum(counts, 1.0)[..., None])
    rate = cfg["blend"] * cfg["lr"]
    moved = P.r(P.r((1.0 - rate) * c) + P.r(rate * new))
    return {"centers": np.where((counts > 0)[..., None], moved, c)}


def metric(P, cfg: dict, p: dict, eval_set: dict) -> float:
    """Macro F1 of the held-out assignments (greedy majority mapping)."""
    a = _assign(P, eval_set["x"], p["centers"])
    y = eval_set["y"]
    k = p["centers"].shape[-2]
    n_classes = int(y.max()) + 1
    mapping = np.zeros(k, np.int64)
    for c in range(k):
        members = y[a == c]
        if members.size:
            mapping[c] = np.bincount(members, minlength=n_classes).argmax()
    pred = mapping[a]
    f1s = []
    for cls in range(n_classes):
        tp = np.sum((pred == cls) & (y == cls))
        fp = np.sum((pred == cls) & (y != cls))
        fn = np.sum((pred != cls) & (y == cls))
        prec, rec = tp / max(tp + fp, 1), tp / max(tp + fn, 1)
        f1s.append(0.0 if prec + rec == 0 else 2 * prec * rec / (prec + rec))
    return float(np.mean(f1s))


def step_flops(cfg: dict) -> float:
    """One local step on one edge: the distances x c^T and the cluster
    sums onehot^T x, each 2 B D K."""
    return 4.0 * cfg["batch"] * cfg["features"] * cfg["classes"]


def n_params(cfg: dict) -> int:
    """The centroids: K D."""
    return cfg["features"] * cfg["classes"]


def eval_flops(cfg: dict) -> float:
    """The per-aggregation parameter-delta utility, 3 K D (the F1 is
    host work)."""
    return 3.0 * n_params(cfg)
