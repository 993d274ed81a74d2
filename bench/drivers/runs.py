"""Back-to-back single runs through ``ELSession.run_sync_ingraph`` or
``run_async_ingraph`` (the mix's ``mode``), each with a fresh seed and
knobs drawn from the mix's ``knobs``, on the cell's mesh (``None`` on
one chip).  The window runs from the first call's start to the end of
the last call begun before ``seconds``."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchlib import program
from benchlib.drive import Draws, annotate, run_config, run_spec

#: the async program's own horizon: the reference never stops it early
NO_HORIZON = 1 << 30


class Driver:

    def __init__(self, cfg, traffic, fx, seed):
        from repro.el import ELSession
        self.cfg, self.traffic, self.fx = cfg, traffic, fx
        self.mode = traffic["mode"]
        self.draws = Draws(seed, 1)
        self.session = ELSession(
            run_config(fx, traffic, mode=self.mode),
            metric_name=fx["metric"], lr=cfg["lr"]).with_executor(
                fx["executor"], init_params=fx["init"],
                n_samples=fx["n_samples"])
        self.calls: List[Dict[str, Any]] = []

    def _plan(self, n: int) -> List[Dict[str, float]]:
        return self.draws.plan(self.traffic.get("knobs", {}), n)

    def _call(self, knobs: Dict[str, float]):
        self.session.cfg = run_config(self.fx, self.traffic, mode=self.mode,
                                      **knobs)
        if self.mode == "sync":
            return self.session.run_sync_ingraph(
                max_rounds=self.traffic["max_rounds"], mesh=self.fx["mesh"])
        return self.session.run_async_ingraph(mesh=self.fx["mesh"])

    def setup(self) -> None:
        self._call(self._plan(1)[0])

    def _counts(self, rep) -> Dict[str, int]:
        return {"aggs": rep.n_aggregations}

    def window(self, seconds: float) -> Dict[str, Any]:
        plan = self._plan(self.traffic["plan_calls"])
        t_start = time.perf_counter()
        for knobs in plan:
            t0 = time.perf_counter()
            if t0 - t_start >= seconds:
                break
            with annotate("bench.call"):
                rep = self._call(knobs)
            self.calls.append({"t0": t0, "t1": time.perf_counter(),
                               "knobs": knobs, "report": rep,
                               **self._counts(rep)})
        else:
            raise RuntimeError("the plan ran out before the window "
                               "closed: raise plan_calls")
        return {"t0": t_start, "t1": self.calls[-1]["t1"],
                "aggs": sum(c["aggs"] for c in self.calls)}

    def _run_spec(self, knobs: Dict[str, float]) -> Dict[str, Any]:
        horizon = (self.traffic["max_rounds"] if self.mode == "sync"
                   else NO_HORIZON)
        return run_spec(self.cfg, self.traffic, self.mode, knobs, horizon)

    def checked_runs(self) -> List[Dict[str, Any]]:
        """Every window call as ``(run spec, record)``; ends the
        session's hold on the device."""
        rows = []
        for c in self.calls:
            rows.append({"run": self._run_spec(c["knobs"]),
                         "record": program.record_from_report(c["report"])})
            c["report"] = None
        self.session.close()
        return rows
