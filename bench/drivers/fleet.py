"""An open-loop ``FleetServer``: tenants due at the mix's arrival times
(Poisson, or on/off bursts) at its ``rate_per_s``, submitted when due,
served in waves of ``rounds_per_wave`` rounds over ``n_slots`` slots.
Each tenant's latency runs from when it was due to its ``ReportReady``;
arrivals stop at ``seconds`` and the window runs until every tenant due
in it has reported, or ``grace_s`` past its close."""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from benchlib import program
from benchlib.drive import Draws, annotate, run_config, run_spec


class Driver:

    def __init__(self, cfg, traffic, fx, seed):
        from repro.el.fleet import FleetServer, ReportReady
        self.cfg, self.traffic, self.fx = cfg, traffic, fx
        self.draws = Draws(seed, 2)
        self.server = FleetServer(n_slots=traffic["n_slots"],
                                  rounds_per_wave=traffic["rounds_per_wave"],
                                  mesh=fx["mesh"])
        self.done: Dict[str, float] = {}
        self.reports: Dict[str, Any] = {}

        def on_event(ev):
            if isinstance(ev, ReportReady):
                self.done[ev.tenant_id] = time.perf_counter()
                self.reports[ev.tenant_id] = ev.report

        self.server.subscribe(on_event)
        self.tenants: List[Dict[str, Any]] = []
        self.n_submitted = 0        # tenant ids stay unique across windows
        self.waves = 0
        self.lateness: List[float] = []

    def _tenant(self, tid: str, knobs: Dict[str, float]):
        from repro.el.fleet import TenantRun
        return TenantRun(cfg=run_config(self.fx, self.traffic,
                                        mode=self.traffic["mode"], **knobs),
                         executor=self.fx["executor"], tenant_id=tid,
                         metric_name=self.fx["metric"],
                         n_samples=self.fx["n_samples"],
                         init_params=self.fx["init"],
                         max_rounds=self.traffic["max_rounds"])

    def _plan(self, n: int) -> List[Dict[str, float]]:
        return self.draws.plan(self.traffic.get("knobs", {}), n)

    def setup(self) -> None:
        self.server.submit(self._tenant("warmup", self._plan(1)[0]))
        self.server.drain()
        self.done.clear()
        self.reports.clear()

    def _busy(self) -> bool:
        st = self.server.stats()
        return st["tenants_pending"] + st["tenants_active"] > 0

    def window(self, seconds: float, rate: float = None) -> Dict[str, Any]:
        rate = float(rate or self.traffic["rate_per_s"])
        n = int(math.ceil(rate * seconds * 1.5)) + 8
        gaps = self.draws.gaps(self.traffic["arrivals"], rate, n)
        due = np.cumsum(gaps)
        due = due[due < seconds]
        plan = self._plan(len(due))
        grace = float(self.traffic.get("grace_s", 60.0))
        t_start = time.perf_counter()
        i = 0
        srv = self.server
        while True:
            now = time.perf_counter() - t_start
            while i < len(due) and due[i] <= now:
                tid = f"t{self.n_submitted:05d}"
                self.n_submitted += 1
                srv.submit(self._tenant(tid, plan[i]))
                self.tenants.append({"id": tid, "due": t_start + due[i],
                                     "knobs": plan[i]})
                self.lateness.append(now - due[i])
                i += 1
            if self._busy():
                srv.step()
                self.waves += 1
            elif i < len(due):
                with annotate("bench.wait"):
                    time.sleep(max(0.0, due[i] - now))
            else:
                break
            if now > seconds + grace:
                break
        self.t_end = time.perf_counter()
        return {"t0": t_start, "t1": self.t_end,
                "aggs": sum(r.n_aggregations
                            for r in self.reports.values())}

    def missing(self) -> int:
        """Tenants due in the window that never reported."""
        return sum(t["id"] not in self.done for t in self.tenants)

    def latencies_ms(self) -> np.ndarray:
        """Due-to-report latency of every tenant due in the window; a
        tenant with no report counts as late as the end of the run."""
        return np.array([(self.done.get(t["id"], self.t_end) - t["due"])
                         * 1e3 for t in self.tenants])

    def checked_runs(self) -> List[Dict[str, Any]]:
        rows = []
        for t in self.tenants:
            rep = self.reports.pop(t["id"], None)
            if rep is None:
                continue
            rows.append({"run": run_spec(self.cfg, self.traffic,
                                         self.traffic["mode"], t["knobs"],
                                         self.traffic["max_rounds"]),
                         "record": program.record_from_report(rep)})
        self.server.close()
        return rows
