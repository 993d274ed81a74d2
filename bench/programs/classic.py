"""The ``classic`` program: the paper's SVM or K-means behind the
program's ``ClassicExecutor``, run as OL4EL from one ``OL4ELConfig``
base.  The configuration's ``(features, classes)`` must be the widths of
the program's own configuration of its ``arch``."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchlib import data


def build(cfg: dict, init: Dict[str, np.ndarray], mesh=None
          ) -> Dict[str, Any]:
    """The executor, the base run config and the placed initial params
    (the programs place their data plane on ``mesh`` themselves)."""
    del mesh
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
