"""The comparison that decides ``correct``.

A checked run is one the timed window produced (a single run, a sweep
cell or a fleet tenant), as a *record*: per-aggregation ``interval``,
``metric``, ``utility``, ``consumed``, ``wall`` (and ``edge`` for async
events), the count ``n``, ``final_params`` and ``final_metric``.  The
configuration's check (``bench/checks/<kind>.py``: ``host-f64``, the
float64 replay on the host, or ``device-f32``, the model's arithmetic on
the device) replays it along its recorded decisions
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

Where the reference names a tie width (``TIE_WIDTH``: K-means, whose
E-step the program takes in float32), a replay that meets a point whose
two nearest centroids lie closer than float32 can tell is replayed again
with such points assigned the other way, and the run is compared with
the branch that lies nearest to it (``replay_numbers``).
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from benchlib import elref, load_named

NUMBERS = ("select_gap", "ledger_gap", "param_gap", "metric_gap",
           "utility_gap")

#: the near ties of one replay that are followed both ways (2**4 - 1
#: more replays at most; a sound run meets one in a few hundred)
MAX_TIES = 4


def _finite_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both = np.isfinite(a) & np.isfinite(b)
    one = np.isfinite(a) != np.isfinite(b)
    if one.any():
        return math.inf
    return float(np.max(np.abs(a[both] - b[both]), initial=0.0))


def param_gap(prog: dict, ref: dict, init: dict = None) -> float:
    """The worst leaf's gap, of the parameters or, given the run's
    ``init``, of their update from it."""
    def leaf(p, k):
        v = np.asarray(p[k], np.float64)
        return v if init is None else v - np.asarray(init[k], np.float64)

    norms = [float(np.linalg.norm(leaf(ref, k))) for k in ref]
    floor = float(np.median(norms))
    gap = 0.0
    for k, n in zip(ref, norms):
        d = leaf(prog, k) - leaf(ref, k)
        if not np.all(np.isfinite(d)):
            return math.inf
        gap = max(gap, float(np.linalg.norm(d)) / max(n, floor, 1e-30))
    return gap


def compare(prog: dict, replay: dict, budget: float, n_edges: int,
            init: dict = None) -> Dict[str, float]:
    """The five numbers of one checked run (``replay`` is the forced
    reference's ``simulate_*`` result; with ``init``, ``param_gap`` reads
    the parameters' update from it)."""
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
        "param_gap": param_gap(prog["final_params"], ref["final_params"],
                               init),
        "metric_gap": max(
            _finite_gap(prog["metric"][:n], ref["metric"][:n]),
            abs(prog["final_metric"] - ref["final_metric"])),
        "utility_gap": _finite_gap(prog["utility"][:n], ref["utility"][:n]),
    }


def kind(cfg: dict):
    """The check of a configuration, ``bench/checks/<kind>.py`` by its
    ``check`` key (``host-f64`` where the key is absent): its
    ``workload(cfg, ref, control=False)`` is the reference that replays
    a run (``elref.Workload``'s interface), or with ``control`` the same
    reference a precision step lower, run in the program's place."""
    return load_named("checks", cfg.get("check", "host-f64"))


def replay_numbers(cfg: dict, wl, row: dict) -> Dict[str, float]:
    """The five numbers of one checked row (``{"run", "record",
    "init"}``), replayed by the workload ``wl`` along its record, and
    ``ties_followed``, the near ties of the branch compared.  Where the
    configuration says ``"param_gap_of": "update"``, ``param_gap`` reads
    the run's parameter update, not its parameters: a model whose update
    is tiny next to its initial weights would pass any arithmetic by a
    gap relative to the weights.

    Where ``wl`` follows near ties (``wl.tie_width``), every subset of
    the first ``MAX_TIES`` ties that the plain replay meets is replayed
    assigned the other way, and the branch whose largest number is least
    is compared: a float32 program may resolve each tie either way, and
    only a tie changes the branch.  All five numbers choose it, not the
    final parameters alone, which forget an early tie as the run goes on
    while its utilities do not."""
    run = dict(row["run"], init=row["init"])
    init = row["init"] if cfg.get("param_gap_of") == "update" else None

    def numbers(flips):
        replay, met = elref.replay(wl, run, row["record"], flips)
        return dict(compare(row["record"], replay, run["budget"],
                            cfg["n_edges"], init),
                    ties_followed=len(flips)), met

    best, met = numbers(())
    ties = met[:MAX_TIES]
    for mask in range(1, 1 << len(ties)):
        nums, _ = numbers(frozenset(t for i, t in enumerate(ties)
                                    if mask >> i & 1))
        if max(nums[k] for k in NUMBERS) < max(best[k] for k in NUMBERS):
            best = nums
    return best


def worst(rows: List[Dict[str, float]]) -> Dict[str, float]:
    """Each number's worst reading over the checked runs."""
    return {k: max(r[k] for r in rows) for k in NUMBERS}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
