"""Back-to-back ablation grids through ``ELSession.sweep``: the mix's
``grid`` (heterogeneity x budget) with ``seeds_per_call`` seeds per grid
point, the seeds offset per call.  A sweep cell's rounds count as
aggregations; ``iters`` and ``cells`` per call give the vmapped loop's
padding."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchlib import program
from benchlib.drive import load, run_config

Runs = load("runs").Driver


class Driver(Runs):

    def _spec(self, base_seed: int):
        from repro.el.sweep import SweepSpec
        grid = self.traffic["grid"]
        return SweepSpec(
            heterogeneity=tuple(float(h) for h in grid["heterogeneity"]),
            budget=tuple(float(b) for b in grid["budget"]),
            seeds=tuple(base_seed + i
                        for i in range(self.traffic["seeds_per_call"])),
            max_rounds=self.traffic["max_rounds"])

    def _call(self, knobs):
        self.session.cfg = run_config(self.fx, self.traffic, mode="sync")
        return self.session.sweep(self._spec(knobs["seed"]),
                                  mesh=self.fx["mesh"])

    def _counts(self, rep) -> Dict[str, int]:
        n = np.asarray(rep.out["n_rounds"])
        return {"aggs": int(n.sum()), "iters": int(n.max()),
                "cells": len(n)}

    def checked_runs(self) -> List[Dict[str, Any]]:
        rows = []
        for c in self.calls:
            rep = c["report"]
            cells = rep.spec.cell_cfgs(self.session.cfg)
            for cell_cfg, rec in zip(cells, program.records_from_sweep(rep)):
                rows.append({"run": self._run_spec(
                    {"seed": cell_cfg.seed, "budget": cell_cfg.budget,
                     "heterogeneity": cell_cfg.heterogeneity}),
                    "record": rec})
            c["report"] = None
        self.session.close()
        return rows
