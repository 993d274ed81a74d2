"""The comparison that decides ``correct``.

A checked run is one the timed window produced (a single run, a sweep
cell or a fleet tenant), as a *record*: per-aggregation ``interval``,
``metric``, ``utility``, ``consumed``, ``wall`` (and ``edge`` for async
events), the count ``n``, ``final_params`` and ``final_metric``.  The
float64 reference replays it along its recorded decisions
(``elref.simulate_*(forced=record)``) and five numbers compare the two:

  select_gap   the widest gap by which the reference's own draw prefers
               another arm over the one the run took (perturbed
               log-weight units): arm selection and the utilities that
               feed it;
  ledger_gap   the largest budget-accounting gap: consumed and wall
               clock per aggregation (as a share of the budget), plus 1
               for every aggregation count, event order or stop decision
               that differs;
  param_gap    the final parameters: the worst leaf's |prog - ref| over
               max(|ref leaf|, median leaf norm);
  metric_gap   the largest absolute gap of the per-aggregation metric
               and of the final metric (accuracy or F1);
  utility_gap  the largest absolute gap of the per-aggregation utility.

Each has a limit in the cell's file (``cells/<cell>.json``), set from
readings of sound runs and of the control (``PERF.md`` gives both).
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

NUMBERS = ("select_gap", "ledger_gap", "param_gap", "metric_gap",
           "utility_gap")


def _finite_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both = np.isfinite(a) & np.isfinite(b)
    one = np.isfinite(a) != np.isfinite(b)
    if one.any():
        return math.inf
    return float(np.max(np.abs(a[both] - b[both]), initial=0.0))


def param_gap(prog: dict, ref: dict) -> float:
    norms = [float(np.linalg.norm(np.asarray(ref[k], np.float64)))
             for k in ref]
    floor = float(np.median(norms))
    gap = 0.0
    for k, n in zip(ref, norms):
        d = np.asarray(prog[k], np.float64) - np.asarray(ref[k], np.float64)
        if not np.all(np.isfinite(d)):
            return math.inf
        gap = max(gap, float(np.linalg.norm(d)) / max(n, floor, 1e-30))
    return gap


def compare(prog: dict, replay: dict, budget: float, n_edges: int
            ) -> Dict[str, float]:
    """The five numbers of one checked run (``replay`` is the forced
    reference's ``simulate_*`` result)."""
    ref = replay["record"]
    n = min(prog["n"], ref["n"])
    count = abs(prog["n"] - ref["n"]) + replay["count_gap"]
    if prog.get("edge") is not None and ref.get("edge") is not None:
        count += int(np.sum(np.asarray(prog["edge"][:n])
                            != np.asarray(ref["edge"][:n])))
    money = max(
        _finite_gap(prog["consumed"][:n], ref["consumed"][:n])
        / (budget * n_edges),
        _finite_gap(prog["wall"][:n], ref["wall"][:n]) / budget) if n else 0.0
    return {
        "select_gap": replay["select_gap"],
        "ledger_gap": money + count,
        "param_gap": param_gap(prog["final_params"], ref["final_params"]),
        "metric_gap": max(
            _finite_gap(prog["metric"][:n], ref["metric"][:n]),
            abs(prog["final_metric"] - ref["final_metric"])),
        "utility_gap": _finite_gap(prog["utility"][:n], ref["utility"][:n]),
    }


def worst(rows: List[Dict[str, float]]) -> Dict[str, float]:
    """Each number's worst reading over the checked runs."""
    return {k: max(r[k] for r in rows) for k in NUMBERS}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
