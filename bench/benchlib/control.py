"""The control of the correctness check: the plain reference put in the
program's place and computed the way the program's control computes on
the chip — every matrix product in one bfloat16 pass (JAX's
``default`` on a TPU), the step below the configurations' float32 at
``high`` or ``highest``.  The configuration's check kind gives both
sides (``check.kind``): ``host-f64`` runs it in numpy on the host
(float32 values, ``benchlib.prec``'s ``default``), ``device-f32`` on the
device.  It runs free — its own decisions from its own utilities — and
its records go through the same comparison as the program's: the
check's reference replays them and ``check.compare`` reads the five
numbers.  A sound limit lets the program through and stops the control.
On the chip the control is also the program itself at ``default``
precision (``bench/control.py``).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib import check, elref
from benchlib.drive import Draws


def run_specs(cfg: dict, traffic: dict, seed: int, n: int) -> List[dict]:
    """``n`` runs of the cell's traffic, as the window would start them
    (same knob distributions; sweep calls expand into their cells)."""
    d = Draws(seed, 7)
    mode = traffic["mode"]
    horizon = traffic.get("max_rounds", 1 << 30) if mode == "sync" \
        else 1 << 30
    base = {"mode": mode, "ucb_c": cfg["ucb_c"], "budget": cfg["budget"],
            "heterogeneity": cfg["heterogeneity"],
            "async_alpha": cfg["async_alpha"], "max_rounds": horizon}
    if traffic["kind"] == "sweep":
        grid, specs = traffic["grid"], []
        while len(specs) < n:
            s0 = int(d.seeds(1)[0])
            cells = [dict(base, budget=b, heterogeneity=h, seed=s0 + i)
                     for b in grid["budget"] for h in grid["heterogeneity"]
                     for i in range(traffic["seeds_per_call"])]
            specs += [cells[j] for j in d.rng.permutation(len(cells))]
        return specs[:n]
    cols = {k: d.knob(v, n) for k, v in traffic.get("knobs", {}).items()}
    seeds = d.seeds(n)
    return [dict(base, seed=int(seeds[i]),
                 **{k: float(v[i]) for k, v in cols.items()})
            for i in range(n)]


def readings(cfg: dict, ref, traffic: dict, seed: int, n: int
             ) -> Dict[str, float]:
    """The five numbers of ``n`` control runs (worst over the runs): the
    configuration's check reference at its control precision, in the
    program's place, replayed by the same check."""
    kind = check.kind(cfg)
    low = kind.workload(cfg, ref, control=True)
    high = kind.workload(cfg, ref)
    init = ref.init(cfg, int(Draws(seed, 0).seeds(1)[0]))
    rows = []
    for run in run_specs(cfg, traffic, seed, n):
        rec = elref.simulate(low, dict(run, init=init))["record"]
        rec["final_params"] = {k: np.asarray(v, np.float32)
                               for k, v in rec["final_params"].items()}
        rows.append(check.replay_numbers(
            cfg, high, {"run": run, "record": rec, "init": init}))
    return check.worst(rows)
