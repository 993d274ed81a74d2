"""FLOP and byte counts by hand, the peak table, and shares that never
read 0."""

import json
import types

import pytest

import benchtest  # noqa: F401  (puts the benchmark on the path)
from benchlib import counts, load_module
from benchlib.peaks import peak_for

SVM_REF = load_module(benchtest.BENCH + "/configs/svm-wafer.py", "svm_ref")
KM_REF = load_module(benchtest.BENCH + "/configs/kmeans-traffic.py",
                     "km_ref")

SVM = {"model": "svm", "batch": 64, "features": 59, "classes": 8,
       "n_edges": 16, "utility": "eval_gain",
       "data": {"samples": 20000, "test_frac": 0.2}}
KM = {"model": "kmeans", "batch": 128, "features": 64, "classes": 3,
      "n_edges": 16, "utility": "param_delta",
      "data": {"samples": 20000, "test_frac": 0.2}}


def test_svm_step_and_round_by_hand():
    # forward x[64,59] @ w[59,8] and backward x^T @ g: 2 * 2*64*59*8
    assert counts.step_flops(SVM, SVM_REF) == 120832.0
    # round of interval 3: 16 edges * 3 steps, the 16-way weighted mean
    # of 59*8+8 = 480 params, and accuracy over 4,000 rows
    assert counts.sync_round_flops(SVM, SVM_REF, 3) == (
        16 * 3 * 120832 + 2 * 16 * 480 + 2 * 4000 * 59 * 8)
    assert counts.async_event_flops(SVM, SVM_REF, 2) == (
        2 * 120832 + 3 * 480 + 2 * 4000 * 59 * 8)
    assert counts.required_flops(SVM, SVM_REF, "sync", [1.0, 2.0]) == (
        counts.sync_round_flops(SVM, SVM_REF, 1)
        + counts.sync_round_flops(SVM, SVM_REF, 2))


def test_kmeans_step_and_kernel_launch_by_hand():
    assert counts.step_flops(KM, KM_REF) == 4 * 128 * 64 * 3
    assert counts.eval_flops(KM, KM_REF) == 3 * 3 * 64
    launch = counts.kmeans_assign_launch(rows=128, d=64, k=3)
    assert launch["flops"] == 2 * 128 * 3 * 64 + 2 * 128 * 64 + 5 * 128 * 3
    assert launch["bytes"] == 4 * 128 * 64 + 4 * 3 * 64 + 8 * 128
    t, bound = counts.roofline_seconds(launch["flops"], launch["bytes"],
                                       peak_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(launch["bytes"] / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no entry in the peak table"):
        peak_for("TPU v99 imaginary")


def test_a_share_is_never_printed_as_zero():
    mfu = benchtest.harness().load_module(
        benchtest.BENCH + "/metrics/mfu.py", "mfu_under_test")
    ctx = types.SimpleNamespace(
        cfg=SVM, ref=SVM_REF, peak=peak_for("TPU v5 lite"), lo=0,
        hi=10**10, trace={},
        rows=[{"run": {"mode": "sync"},
               "record": {"interval": [1.0, 2.0]}}])
    share = mfu.read(ctx)
    # about 2e-6 percent: printed with its digits, never rounded to 0
    assert 0 < share < 1e-4
    assert float(json.loads(json.dumps({"v": share}))["v"]) == share
    ctx.rows = []
    assert mfu.read(ctx) is None
