"""The operations and bytes the algorithm needs, from shapes alone.

Counted: the matrix products of the model's local step (forward and
backward for the SVM, the two products of a Lloyd step for K-means),
the aggregation, and the per-aggregation eval metric.  What belongs to
one model (a step's products, its parameters, its metric) is counted by
the configuration's reference (``bench/configs/<name>.py``:
``step_flops``, ``n_params``, ``eval_flops``), so a new model brings its
counts in its own file.  Elementwise work
is left out, and so are the steps a compiled block masks past its
interval: they are waste, not required work.

``kmeans_assign_launch`` counts what one launch of the E-step kernel
reads, writes and computes, for the kernel's roofline share.
"""

from __future__ import annotations

from typing import Dict, Iterable


def step_flops(cfg: dict, ref) -> float:
    """One local step on one edge: the configuration's reference
    (``bench/configs/<name>.py``) counts its own model's products."""
    return float(ref.step_flops(cfg))


def n_params(cfg: dict, ref) -> int:
    """The model's parameters, as its reference counts them."""
    return int(ref.n_params(cfg))


def eval_flops(cfg: dict, ref) -> float:
    """The per-aggregation metric, as the reference counts it."""
    return float(ref.eval_flops(cfg))


def sync_round_flops(cfg: dict, ref, interval: int) -> float:
    e = cfg["n_edges"]
    return (e * interval * step_flops(cfg, ref)
            + 2.0 * e * n_params(cfg, ref) + eval_flops(cfg, ref))


def async_event_flops(cfg: dict, ref, interval: int) -> float:
    return (interval * step_flops(cfg, ref) + 3.0 * n_params(cfg, ref)
            + eval_flops(cfg, ref))


def required_flops(cfg: dict, ref, mode: str, intervals: Iterable[float]
                   ) -> float:
    """The required operations of every aggregation of a run."""
    f = sync_round_flops if mode == "sync" else async_event_flops
    return sum(f(cfg, ref, int(i)) for i in intervals)


def kmeans_assign_launch(rows: int, d: int, k: int,
                         batch_elems: int = 1) -> Dict[str, float]:
    """One E-step kernel launch over ``rows`` points of width ``d``
    against ``k`` centroids (``batch_elems`` centroid sets when vmapped):
    distances by the matmul expansion, one argmin and one min."""
    flops = 2.0 * rows * k * d + 2.0 * rows * d + 5.0 * rows * k
    bytes_ = 4.0 * rows * d + 4.0 * batch_elems * k * d + 8.0 * rows
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(flops: float, bytes_: float, peak: dict):
    """``(seconds, bound)``: the least time the chip could take and
    which of its two peaks sets it."""
    t_f = flops / float(peak["flops_per_s"])
    t_b = bytes_ / float(peak["hbm_bytes_per_s"])
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
