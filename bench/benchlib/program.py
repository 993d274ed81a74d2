"""The system under test, built from a configuration file.

The program is driven only through its library entry points
(``ELSession``, ``FleetServer``); the benchmark hands it the data, the
initial parameters and the knobs, and takes back its reports.  Widths in
the configuration file are checked against the program's own
configuration of the same architecture: a mismatch is an error, never a
silent resize.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchlib import data


def build(cfg: dict, init: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """The executor, the base run config and the placed initial params."""
    import jax.numpy as jnp

    from repro.config import OL4ELConfig, get_config
    from repro.federated import ClassicExecutor
    from repro.models import build_model

    exp = get_config(cfg["arch"])
    if (exp.model.d_model, exp.model.vocab_size) != (cfg["features"],
                                                     cfg["classes"]):
        raise ValueError(
            f"{cfg['name']}: the program's {cfg['arch']} has widths "
            f"({exp.model.d_model}, {exp.model.vocab_size}), the "
            f"configuration ({cfg['features']}, {cfg['classes']})")
    # the configuration names the model's build arguments by the keys
    # of its own numbers, so that one number feeds program and reference
    model = build_model(exp.model, **{arg: cfg[key] for arg, key in
                                      cfg["model_args"].items()})
    edges, test = data.make(cfg)
    ex = ClassicExecutor(model, edges, test, batch=cfg["batch"],
                         lr=cfg["lr"])
    base = OL4ELConfig(
        max_interval=cfg["max_interval"], mode="sync", cost_model="fixed",
        policy="ol4el", budget=float(cfg["budget"]),
        comp_cost=float(cfg["comp_cost"]), comm_cost=float(cfg["comm_cost"]),
        heterogeneity=float(cfg["heterogeneity"]), utility=cfg["utility"],
        async_alpha=float(cfg["async_alpha"]), async_batch_k=0,
        ucb_c=float(cfg["ucb_c"]), n_edges=cfg["n_edges"], seed=0)
    return {"executor": ex, "base": base,
            "n_samples": [len(e["y"]) for e in edges],
            "init": {k: jnp.asarray(v) for k, v in init.items()},
            "metric": cfg["metric"]}


def record_from_report(rep) -> Dict[str, Any]:
    """A checked-run record from an ``ELReport``."""
    recs = rep.records
    edge = [r.edge for r in recs]
    return {
        "n": rep.n_aggregations,
        "interval": np.array([r.interval for r in recs], np.float64),
        "metric": np.array([r.metric for r in recs], np.float64),
        "utility": np.array([r.utility for r in recs], np.float64),
        "consumed": np.array([r.total_consumed for r in recs], np.float64),
        "wall": np.array([r.wall_time for r in recs], np.float64),
        "edge": (None if rep.mode == "sync" else np.array(edge, np.int64)),
        "final_params": {k: np.asarray(v) for k, v in
                         rep.final_params.items()},
        "final_metric": float(rep.final_metric),
    }


def records_from_sweep(rep) -> list:
    """One checked-run record per sweep cell."""
    out = rep.out
    n = np.asarray(out["n_rounds"])
    finals = np.asarray(out["final_metric_host"]) \
        if "final_metric_host" in out else None
    rows = []
    for i in range(len(n)):
        k = int(n[i])
        rows.append({
            "n": k,
            "interval": np.asarray(out["interval"][i, :k], np.float64),
            "metric": np.asarray(out["metric"][i, :k], np.float64),
            "utility": np.asarray(out["utility"][i, :k], np.float64),
            "consumed": np.asarray(out["consumed"][i, :k], np.float64),
            "wall": np.asarray(out["wall"][i, :k], np.float64),
            "edge": None,
            "final_params": {kk: np.asarray(v[i]) for kk, v in
                             rep.final_params.items()},
            "final_metric": (float(finals[i]) if finals is not None
                             else float(out["metric"][i, k - 1])),
        })
    return rows
