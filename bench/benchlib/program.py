"""The system under test, built from a configuration file.

The program is driven only through its library entry points
(``ELSession``, ``FleetServer``); the benchmark hands it the data, the
initial parameters, the knobs and, for a cell on more than one chip, a
mesh, and takes back its reports.  How a configuration becomes a program
is found by name: the configuration file's ``program`` key names
``bench/programs/<kind>.py`` (``classic`` where the key is absent), whose
``build(cfg, init, mesh)`` returns the pieces the drivers use
(``executor``, ``base``, ``n_samples``, ``init``, ``metric``).  Each
kind checks the configuration's widths against the program's own
configuration of the same architecture: a mismatch is an error, never a
silent resize.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchlib import load_named


def build(cfg: dict, init: Dict[str, np.ndarray], mesh=None
          ) -> Dict[str, Any]:
    """The program of ``cfg``'s kind, with ``mesh`` (``None`` on one
    chip) under ``fx["mesh"]`` for the drivers."""
    kind = load_named("programs", cfg.get("program", "classic"))
    fx = kind.build(cfg, init, mesh)
    fx["mesh"] = mesh
    return fx


def mesh_for(cfg: dict, n_chips: int) -> Optional[Any]:
    """The mesh of a cell on ``n_chips`` chips, built by the program's
    own Auto-axis builder over the first ``n_chips`` devices: ``None`` on
    one chip, so a one-chip cell runs the program's mesh-less path;
    otherwise every chip on the edge (``data``) axis, unless the
    configuration file states its axes as ``"mesh": {"data": d,
    "model": m}``."""
    if n_chips == 1:
        return None
    from repro.launch.mesh import make_debug_mesh
    axes = cfg.get("mesh", {"data": n_chips, "model": 1})
    if axes["data"] * axes["model"] != n_chips:
        raise ValueError(f"{cfg['name']}: mesh {axes} does not cover the "
                         f"cell's {n_chips} chips")
    return make_debug_mesh(axes["data"], axes["model"])


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
