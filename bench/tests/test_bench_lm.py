"""The granite-4.0-h-micro cell on the CPU: its files load from a copy of
the benchmark, a short window at smoke size on four forced host devices
(one edge a device) is correct under ``device-f32-edges``, the check's
control is not, and its three metric readers read a small hand-made
trace."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchtest import (BENCH, ROOT, harness,  # noqa: F401
                       keep_matmul_precision)
from benchlib import load_named

CELL = "granite-4.0-h-micro.run-sync"
METRICS = ("ssd_scan_roofline", "aggregation.collective_share",
           "lm.device_ms_per_step")

#: the configuration cut to smoke size (2 layers: Mamba-2 then NoPE
#: GQA, d 256, vocabulary 512, 8 rows of 64 tokens an edge): every width
#: the program's smoke variant has
SMOKE = {"variant": "smoke", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "mamba_d_state": 16, "mamba_d_head": 32, "mamba_chunk_size": 32,
         "mamba_n_heads": 16, "layer_types": ["mamba", "attention"],
         "num_hidden_layers": 2, "vocab_size": 512,
         "published": {"num_hidden_layers": 2, "vocab_size": 512},
         "data": {"kind": "tokens", "seq_len": 64, "sequences": 8,
                  "held_out": 2, "zipf": 1.1, "seed": 0}}


def test_cell_files_load_from_a_copy_of_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench, cell, cfg, ref, traffic, limits = harness().load_cell(
        CELL, str(tmp_path))
    assert cell["chips"] == 4 and cfg["program"] == "lm"
    assert cfg["check"] == "device-f32-edges"
    assert cfg["param_gap_of"] == "update" and limits["sample"] == 1
    assert traffic["kind"] == "runs" and traffic["mode"] == "sync"
    for fn in ("init", "edge_step", "eval_metric", "weighted_sum",
               "step_flops", "n_params", "eval_flops"):
        assert callable(getattr(ref, fn)), fn
    copy = str(tmp_path / "bench")
    for part, name in (("programs", cfg["program"]),
                       ("checks", cfg["check"]),
                       ("datasets", cfg["data"]["kind"]),
                       ("drivers", traffic["kind"])):
        assert load_named(part, name, copy)
    reported = {m["name"] for m in harness().metrics_for(bench, CELL, True)}
    assert set(METRICS) <= reported
    for name in reported:
        assert callable(load_named("metrics", name, copy).read)
    # the widths the cell runs at are the published ones
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (2048, 8192, 64, 64, 128, 256,
                                            32, 8)
    assert ref.n_params(cfg) == 772160448


CHILD = r"""
import io, json, sys
sys.path[:0] = [sys.argv[1]]
import benchtest
smoke = json.loads(sys.argv[2])
res = benchtest.harness().run_cell(
    "granite-4.0-h-micro.run-sync", 2**31 + 123, 1.0, False,
    require_device=False, cache=False, overrides={"config": smoke},
    out=io.StringIO(), err=io.StringIO())
print(json.dumps(res))
"""


def test_smoke_window_is_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", CHILD, os.path.join(BENCH, "tests"),
         json.dumps(SMOKE)], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert len(res["device"]["memory_peak_bytes_per_chip"]) == 4
    assert res["metrics"]["aggregations_per_s"]["value"] > 0


def test_check_control_is_not_correct():
    """The reference with every product's operands in bfloat16, in the
    program's place, fails the committed limits: at the smoke widths
    and the cell's own depth (the whole period; two layers amplify a
    rounding too little over 11 steps to tell it from float32)."""
    from benchlib import check, control
    _, _, cfg, ref, traffic, limits = harness().load_cell(CELL)
    cfg.update(SMOKE, num_hidden_layers=cfg["num_hidden_layers"],
               layer_types=cfg["layer_types"])
    nums = control.readings(cfg, ref, traffic, seed=5, n=1)
    assert not check.verdict(nums, limits["limits"]), nums
    # a departure by rounding, not a fault: the arms and counts agree
    assert nums["ledger_gap"] == 0.0 and nums["select_gap"] == 0.0


def _ctx(device, host=(), spans=(), chips=2):
    """A traced window [0, 100) ns of ``chips`` chips."""
    from benchlib.peaks import PEAKS
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    by_device = [list(device)] * chips
    return types.SimpleNamespace(
        cfg=cfg, cell={"chips": chips}, spans=list(spans),
        trace={"device": [ev for d in by_device for ev in d],
               "by_device": by_device, "host": list(host), "meta": {}},
        peak=PEAKS["TPU v5 lite"], lo=0, hi=100)


def test_metric_readers_on_a_hand_made_trace():
    ssd = ("%custom-call.3 = (f32[2,64,4096,64]{3,2,1,0}, "
           "f32[2,64,64,128]{3,2,1,0}) custom-call(f32[2,64,4096,64] %t, "
           "f32[2,64,1,4096] %a, f32[2,4096,128] %b, f32[2,4096,128] %c), "
           "custom_call_target=\"tpu_custom_call\"")
    dev = [(0, 40, ssd), (40, 50, "%all_to_all.2 = f32[1,4,512]"),
           (50, 60, "%all-gather-start.1 = f32[4096]"),
           (60, 80, "%fusion.9 = f32[2]")]
    ctx = _ctx(dev, spans=[{"name": "session.records",
                            "edge_steps": [11, 11]}])
    read = {m: load_named("metrics", m).read for m in METRICS}
    # 20 of 80 busy ns in collectives, on each chip
    assert read["aggregation.collective_share"](ctx) == pytest.approx(25.0)
    # 80 busy ns over 22 steps on two chips: 11 a chip
    assert read["lm.device_ms_per_step"](ctx) == pytest.approx(80e-6 / 11)
    need = load_named("metrics", "ssd_scan_roofline").launch(
        2, 4096, 64, 64, 128, 256)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    # two chips' launches of 40 ns each
    assert read["ssd_scan_roofline"](ctx) == pytest.approx(
        100.0 * 2 * least / 80e-9)
    bare = _ctx([(60, 80, "%fusion.9 = f32[2]")])
    assert all(read[m](bare) is None for m in METRICS)
