"""Shared helpers of the benchmark's tests: the harness loaded as a
module, and small sizes that a CPU test run can hold."""

from __future__ import annotations

import atexit
import importlib.util
import io
import json
import os
import shutil
import sys
import tempfile

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small(cell: str) -> dict:
    """Overrides that cut a cell to CPU size: 2,000 rows over 4 edges, a
    budget of a few rounds, a 2x1 sweep grid, the jnp E-step."""
    kind = "wafer" if cell.startswith("svm") else "traffic"
    return {
        "config": {"data": {"kind": kind, "samples": 2000,
                            "test_frac": 0.2, "dirichlet_alpha": 100.0,
                            "seed": 0},
                   "n_edges": 4, "budget": 800.0, "kmeans_impl": "jnp"},
        "traffic": {"grid": {"heterogeneity": [1.0, 15.0],
                             "budget": [800.0]},
                    "seeds_per_call": 2, "rate_per_s": 40.0},
        "limits": {"sample": 3},
    }


def checkout(cell: str) -> str:
    """A checkout that has ``cell``: the repository, or, for a cell kept
    only as test data (``tests/data/<cell>.json``: its ``BENCHMARK.json``
    entry, traffic and limits), a copy of the benchmark with the cell
    added, made once per process."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if any(w["name"] == cell for w in bench["workloads"]):
        return ROOT
    if cell not in _TEST_ROOTS:
        with open(os.path.join(BENCH, "tests", "data", cell + ".json")) as f:
            extra = json.load(f)
        root = tempfile.mkdtemp(prefix="bench-test-")
        atexit.register(shutil.rmtree, root, True)
        for d in ("configs", "traffic", "cells", "metrics"):
            shutil.copytree(os.path.join(BENCH, d),
                            os.path.join(root, "bench", d))
        w = extra["workload"]
        bench["workloads"].append(w)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in extra["metrics"]:
                m.setdefault("workloads", []).append(w["name"])
        bench["end_to_end"] += extra.get("end_to_end", [])
        bench["per_layer"] += extra.get("per_layer", [])
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        for part, name, body in (("traffic", w["traffic"], extra["traffic"]),
                                 ("cells", cell, extra["limits"])):
            with open(os.path.join(root, "bench", part, name + ".json"),
                      "w") as f:
                json.dump(body, f)
        _TEST_ROOTS[cell] = root
    return _TEST_ROOTS[cell]


_TEST_ROOTS: dict = {}


def variant(cell: str, name: str, chips: int = None, config: str = None,
            config_entries: dict = None, reference: str = None) -> str:
    """A checkout that adds, by files alone, the cell ``name``: ``cell``
    on ``chips`` chips, or of the configuration ``config``, a copy of
    ``cell``'s with ``config_entries`` and the plain reference at
    ``reference`` (a path under ``bench/tests/data``).  Made once per
    process."""
    key = (cell, name, chips, config)
    if key in _TEST_ROOTS:
        return _TEST_ROOTS[key]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    root = tempfile.mkdtemp(prefix="bench-variant-")
    atexit.register(shutil.rmtree, root, True)
    for d in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "bench", d))
    w = dict(next(x for x in bench["workloads"] if x["name"] == cell),
             name=name)
    if chips is not None:
        w["chips"] = chips
    if config is not None:
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            body = dict(json.load(f), name=config, **(config_entries or {}))
        path = os.path.join(root, "bench", "configs", config)
        with open(path + ".json", "w") as f:
            json.dump(body, f)
        shutil.copy(os.path.join(BENCH, "tests", "data", reference),
                    path + ".py")
        bench["configs"].append(dict(conf, name=config,
                                     file=f"bench/configs/{config}.json"))
        w["config"] = config
    bench["workloads"].append(w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copy(os.path.join(BENCH, "cells", cell + ".json"),
                os.path.join(root, "bench", "cells", name + ".json"))
    _TEST_ROOTS[key] = root
    return root


def run_small(cell: str, seed: int = 2**31 + 77, seconds: float = 0.5,
              trace: bool = False, root: str = None, extra: dict = None):
    """One CPU run of ``cell`` at small size; returns its result dict.
    ``extra`` adds overrides: entries of the configuration, the traffic
    or the limits."""
    root = root or checkout(cell)
    ov = small(cell)
    if cell.endswith("run-sync") or cell.endswith("run-async"):
        ov["traffic"] = {}
    elif cell.endswith("sweep-sync"):
        ov["traffic"].pop("rate_per_s")
    else:
        ov["traffic"] = {"rate_per_s": 40.0}
    for part, entries in (extra or {}).items():
        ov.setdefault(part, {}).update(entries)
    return harness().run_cell(cell, seed, seconds, trace,
                              require_device=False, cache=False,
                              overrides=ov, root=root, out=io.StringIO(),
                              err=io.StringIO())


CELLS = ("svm-wafer.run-sync", "kmeans-traffic.sweep-sync",
         "svm-wafer.run-async", "svm-wafer.fleet-poisson")


@pytest.fixture(autouse=True)
def keep_matmul_precision():
    """The harness sets JAX's matmul precision process-wide: put it back
    so other tests in the worker see their own setting."""
    import jax
    prev = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", prev)
