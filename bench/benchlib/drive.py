"""The general traffic generator: a traffic file's ``kind`` names its
driver, ``bench/drivers/<kind>.py``, found by name like every other part
of a cell; every number of the mix is read from the traffic file.

A knob is a number, ``{"choice": [...]}`` or ``{"loguniform": [lo, hi]}``.
Draws are stratified: a run of n draws covers the range in n equal
strata, in an order shuffled by the seed, so that every seed gets the
same spread of sizes and only their order differs.  A knob names a field
of the program's run configuration (``OL4ELConfig``); so does each key of
the traffic file's ``run_config``, which fixes that field for every run
of the mix.  Both reach the program's run and the reference's replay
alike.

A driver module defines ``Driver(cfg, traffic, fx, seed)`` with
``setup()`` (warm up every shape the mix uses), ``window(seconds)``
(measure; returns ``{"t0", "t1", "aggs"}``) and ``checked_runs()`` (the
window's runs as ``{"run", "record"}`` rows for the check; frees the
program's state).  It may keep ``calls`` (one entry per entry-point
call), and a driver of open-loop traffic ``latencies_ms()``,
``missing()``, ``lateness`` and ``waves``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from benchlib import load_named

SEED_RANGE = 1 << 30


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Draws:
    """Stratified knob draws from the run's seed."""

    def __init__(self, seed: int, stream: int):
        self.rng = np.random.default_rng([seed, stream])

    def seeds(self, n: int) -> np.ndarray:
        return self.rng.integers(0, SEED_RANGE, size=n)

    def knob(self, spec, n: int) -> np.ndarray:
        if not isinstance(spec, dict):
            return np.full(n, float(spec))
        q = (self.rng.permutation(n) + self.rng.uniform(size=n)) / n
        if "choice" in spec:
            vals = np.asarray(spec["choice"], np.float64)
            return vals[np.minimum((q * len(vals)).astype(int),
                                   len(vals) - 1)]
        if "loguniform" in spec:
            lo, hi = (math.log(v) for v in spec["loguniform"])
            return np.exp(lo + q * (hi - lo))
        raise ValueError(f"unknown knob spec {spec!r}")

    def plan(self, knobs: Dict[str, Any], n: int) -> List[Dict[str, float]]:
        """``n`` runs' knobs, each with a fresh ``seed``."""
        cols = {k: self.knob(v, n) for k, v in knobs.items()}
        seeds = self.seeds(n)
        return [dict({k: float(v[i]) for k, v in cols.items()},
                     seed=int(seeds[i])) for i in range(n)]

    def gaps(self, arrivals: dict, rate: float, n: int) -> np.ndarray:
        """Inter-arrival gaps: exponential at ``rate`` (``poisson``), or
        an on/off process (``bursty``: ``on_s``/``off_s`` periods, all
        arrivals inside the on periods at rate / duty)."""
        q = (self.rng.permutation(n) + self.rng.uniform(size=n)) / n
        if arrivals["process"] == "poisson":
            return -np.log1p(-q) / rate
        if arrivals["process"] == "bursty":
            on, off = arrivals["on_s"], arrivals["off_s"]
            t = np.cumsum(-np.log1p(-q) / (rate * (on + off) / on))
            t = t + np.floor(t / on) * off
            return np.diff(np.concatenate([[0.0], t]))
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def run_config(fx: dict, traffic: dict, **knobs):
    """The program's run configuration for one run of the mix: the
    configuration's base, the mix's ``run_config``, then the run's
    knobs."""
    import dataclasses
    return dataclasses.replace(fx["base"], **{**traffic.get("run_config", {}),
                                              **knobs})


def run_spec(cfg: dict, traffic: dict, mode: str, knobs: Dict[str, float],
             max_rounds: int) -> Dict[str, Any]:
    """The reference's description of the same run: the configuration's
    knobs, overridden by the mix's ``run_config`` and the run's knobs."""
    spec = {"mode": mode, "budget": cfg["budget"], "ucb_c": cfg["ucb_c"],
            "heterogeneity": cfg["heterogeneity"],
            "async_alpha": cfg["async_alpha"], "max_rounds": max_rounds}
    spec.update(traffic.get("run_config", {}))
    spec.update(knobs)
    return spec


def load(kind: str):
    """The module ``bench/drivers/<kind>.py``."""
    return load_named("drivers", kind)


def make(cfg: dict, traffic: dict, fx: dict, seed: int):
    """The driver that ``bench/drivers/<kind>.py`` defines for this
    traffic file."""
    return load(traffic["kind"]).Driver(cfg, traffic, fx, seed)
