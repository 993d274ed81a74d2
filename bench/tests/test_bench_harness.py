"""The harness's discovery by name, its refusal to run without a chip,
and the shape of ``BENCHMARK.json``."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np

from benchtest import (BENCH, ROOT, harness, keep_matmul_precision,  # noqa: F401
                       run_small)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_entry_finds_its_files():
    b = _bench()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.splitext(
            os.path.join(ROOT, c["file"]))[0] + ".py")
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        for part, d in (("traffic", w["traffic"]), ("cells", w["name"])):
            assert os.path.isfile(os.path.join(BENCH, part, d + ".json"))
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m["workloads"]) <= cells
        e2e = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e.get("workloads", cells))


def test_a_cell_mix_and_metric_are_added_by_adding_files(tmp_path,
                                                         monkeypatch):
    import benchlib
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    for d in ("configs", "traffic", "cells", "metrics", "drivers",
              "datasets", "programs", "checks"):
        shutil.copytree(os.path.join(BENCH, d), root / "bench" / d)
    monkeypatch.setattr(benchlib, "BENCH", str(root / "bench"))
    b = _bench()
    # a new kind of traffic (a driver file), a new mix of it (data only,
    # with a run-config field fixed for the mix), a new cell on it and a
    # new per-layer metric
    (root / "bench" / "drivers" / "runs-counted.py").write_text(
        "from benchlib.drive import load\n\n"
        "class Driver(load('runs').Driver):\n"
        "    def _counts(self, rep):\n"
        "        return dict(super()._counts(rep), counted=1)\n")
    with open(os.path.join(BENCH, "traffic", "run-sync.json")) as f:
        mix = json.load(f)
    mix["kind"] = "runs-counted"
    mix["run_config"] = {"async_alpha": 0.25}
    mix["knobs"] = {"budget": {"loguniform": [600.0, 900.0]},
                    "ucb_c": {"choice": [0.5, 1.0]}}
    (root / "bench" / "traffic" / "run-sync-mixed.json").write_text(
        json.dumps(mix))
    shutil.copy(os.path.join(BENCH, "cells", "svm-wafer.run-sync.json"),
                root / "bench" / "cells" / "svm-wafer.run-sync-mixed.json")
    (root / "bench" / "metrics" / "calls_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")
    b["workloads"].append({"name": "svm-wafer.run-sync-mixed",
                           "config": "svm-wafer",
                           "traffic": "run-sync-mixed", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "session facade",
                           "moves": "aggregations_per_s",
                           "workloads": ["svm-wafer.run-sync-mixed"]})
    b["end_to_end"][0]["workloads"].append("svm-wafer.run-sync-mixed")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    res = run_small("svm-wafer.run-sync-mixed", trace=False, root=str(root))
    assert res["correct"] and "aggregations_per_s" in res["metrics"]
    h = harness()
    got = [m["name"] for m in h.metrics_for(
        json.loads((root / "BENCHMARK.json").read_text()),
        "svm-wafer.run-sync-mixed", True)]
    assert "calls_in_window" in got

    # a new kind of program and a new kind of check (each a file that
    # notes its use), a configuration that names both, and a cell of it
    # on four chips: the harness asks for a four-chip mesh for it
    seen = []
    monkeypatch.setattr(benchlib, "seen", seen, raising=False)
    (root / "bench" / "programs" / "classic-noted.py").write_text(
        "import benchlib\n\n"
        "def build(cfg, init, mesh):\n"
        "    benchlib.seen.append(('program', cfg['name']))\n"
        "    return benchlib.load_named('programs', 'classic').build(\n"
        "        cfg, init, mesh)\n")
    (root / "bench" / "checks" / "host-f64-noted.py").write_text(
        "import benchlib\n\n"
        "def workload(cfg, ref, control=False):\n"
        "    benchlib.seen.append(('check', cfg['name']))\n"
        "    return benchlib.load_named('checks', 'host-f64').workload(\n"
        "        cfg, ref, control)\n")
    with open(os.path.join(BENCH, "configs", "svm-wafer.json")) as f:
        conf = json.load(f)
    conf.update(name="svm-noted", program="classic-noted",
                check="host-f64-noted", param_gap_of="update")
    (root / "bench" / "configs" / "svm-noted.json").write_text(
        json.dumps(conf))
    shutil.copy(os.path.join(BENCH, "configs", "svm-wafer.py"),
                root / "bench" / "configs" / "svm-noted.py")
    shutil.copy(os.path.join(BENCH, "cells", "svm-wafer.run-sync.json"),
                root / "bench" / "cells" / "svm-noted.run-sync-4.json")
    b["configs"].append(dict(b["configs"][0], name="svm-noted",
                             file="bench/configs/svm-noted.json"))
    b["workloads"].append({"name": "svm-noted.run-sync-4",
                           "config": "svm-noted", "traffic": "run-sync",
                           "chips": 4, "why": "test"})
    b["end_to_end"][0]["workloads"].append("svm-noted.run-sync-4")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    from benchlib import program
    monkeypatch.setattr(program, "mesh_for",
                        lambda cfg, n: seen.append(("mesh", n)))
    _, cell, cfg, _, _, _ = h.load_cell("svm-noted.run-sync-4", str(root))
    assert cell["chips"] == 4 and cfg["program"] == "classic-noted"
    res = run_small("svm-noted.run-sync-4", root=str(root))
    assert res["correct"] and res["device"]["count"] == 4
    assert seen == [("mesh", 4), ("program", "svm-noted"),
                    ("check", "svm-noted")]


def test_a_run_field_the_reference_does_not_model_is_refused():
    import pytest
    from benchlib import elref
    with pytest.raises(NotImplementedError, match="async_batch_k"):
        elref.modelled({"mode": "async", "seed": 1, "async_batch_k": 4})


def _run(args, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_and_prints_no_result():
    p = _run(["bench/run.py", "--workload", "svm-wafer.run-sync", "--seed",
              "3", "--seconds", "1", "--trace", "0"], ROOT,
             {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert p.stdout.strip() == ""


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(["bench/run.py", "--workload", "svm-wafer.run-sync", "--seed",
              "3", "--seconds", "1", "--trace", "0"], str(tmp_path),
             {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_draws_are_stratified_and_seeded():
    from benchlib.drive import Draws
    a = Draws(2**31 + 5, 2).knob({"loguniform": [1000.0, 5000.0]}, 40)
    b = Draws(2**31 + 5, 2).knob({"loguniform": [1000.0, 5000.0]}, 40)
    c = Draws(2**31 + 6, 2).knob({"loguniform": [1000.0, 5000.0]}, 40)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # one draw in each of 40 equal log-strata, whatever the seed
    for v in (a, c):
        strata = np.floor(np.log(v / 1000.0) / np.log(5.0) * 40)
        assert sorted(strata.astype(int)) == list(range(40))
    gaps = Draws(7, 2).gaps({"process": "poisson"}, 10.0, 400)
    assert abs(gaps.mean() - 0.1) < 0.01
    # on/off bursts: the same mean rate, every arrival inside an on period
    t = np.cumsum(Draws(7, 2).gaps(
        {"process": "bursty", "on_s": 2.0, "off_s": 3.0}, 10.0, 400))
    assert np.all(np.mod(t, 5.0) <= 2.0 + 1e-9)
    assert abs(400 / t[-1] - 10.0) < 1.5


def test_a_longer_plan_keeps_its_first_calls():
    """Raising a mix's ``plan_calls`` leaves every call the old plan
    made as it was: the window's first calls and their seeds stay."""
    from benchlib.drive import Draws
    for mix, before in (("run-sync", 4000), ("run-async", 1000)):
        with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
            traffic = json.load(f)
        assert traffic["plan_calls"] >= 16 * before

        def plan(n):
            d = Draws(2**31 + 1234, 1)          # as the runs driver draws
            d.plan(traffic["knobs"], 1)         # the warm-up call
            return d.plan(traffic["knobs"], n)

        assert plan(traffic["plan_calls"])[:before] == plan(before)


def test_busy_per_device_averages_the_chips():
    from benchlib import trace
    one = [(0, 10, "a"), (5, 20, "b")]
    other = [(30, 40, "c")]
    assert trace.busy_ns_per_device([one], 0, 60) == 20
    assert trace.busy_ns_per_device([one, other], 0, 60) == 15
    assert trace.busy_ns_per_device([], 0, 60) == 0


def test_device_readings_take_each_chip_apart():
    """Two chips that take turns, each busy half of the window: no instant
    of the window is idle on both, but each chip idles half of it, and
    every busy or idle reading says so, as ``busy_s`` does."""
    import types

    import pytest
    from benchlib import load_named, trace
    a, b = [(0, 30, "fusion.1")], [(30, 60, "fusion.2")]
    host = [(0, 60, "session.call"), (0, 15, "session.dispatch"),
            (45, 60, "session.records")]
    ctx = types.SimpleNamespace(
        trace={"device": a + b, "by_device": [a, b], "host": host},
        lo=0, hi=60, aggs=3)

    def read(name):
        return load_named("metrics", name).read(ctx)

    assert read("device_idle_share") == pytest.approx(50.0)
    assert read("program.device_us_per_agg") == pytest.approx(30e-3 / 3)
    # idle [30,60) on chip a and [0,30) on chip b: the stages cover
    # [45,60) of the one and [0,15) of the other, 30 of 60 ns
    assert read("session.unspanned_idle_share") == pytest.approx(50.0)
    assert trace.mean_totals([trace.op_totals(d, 0, 60) for d in (a, b)]) \
        == [("fusion", 30e-9)]
    # each chip's one gap is named by the span open at its midpoint
    gaps = trace.mean_totals([trace.idle_gaps(d, host, 0, 60,
                                              ("session.dispatch",
                                               "session.records"))
                              for d in (a, b)])
    assert dict(gaps) == pytest.approx({"session.records": 15e-9,
                                        "session.dispatch": 15e-9})


def test_mesh_for_one_chip_is_none_and_axes_must_cover_the_chips():
    import pytest
    from benchlib import program
    assert program.mesh_for({"name": "c"}, 1) is None
    with pytest.raises(ValueError, match="does not cover"):
        program.mesh_for({"name": "c", "mesh": {"data": 2, "model": 1}}, 4)
