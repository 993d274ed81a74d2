"""The readers of the session's stage spans: the unexplained share of
device idle on a small hand-made trace, and a traced run of every cell
through the harness."""

import types

import pytest

from benchtest import keep_matmul_precision, run_small  # noqa: F401
from benchlib import load_named

# ops [0,10) [5,20) [30,40) [50,55) (ns) in a window [0, 60): idle is
# [20,30) [40,50) [55,60), 25 ns in all
DEVICE = [(0, 10, "fusion.1"), (5, 20, "fusion.7"), (30, 40, "while.3"),
          (50, 55, "_assign_kernel")]
HOST = [(0, 60, "bench.call"), (18, 58, "session.call"),
        (19, 22, "session.prepare"), (22, 29, "session.dispatch"),
        (41, 47, "session.records"), (47, 49, "other")]


def _ctx(host, device=DEVICE):
    return types.SimpleNamespace(trace={"device": device,
                                        "by_device": [device], "host": host},
                                 lo=0, hi=60)


def test_unspanned_idle_share_by_hand():
    reader = load_named("metrics", "session.unspanned_idle_share")
    # stages cover idle [20,29) (prepare, then dispatch) and [41,47)
    # (records): 15 of 25 ns.  Unexplained: [29,30), [40,41) and [47,50)
    # inside session.call alone or "other", and [55,60), part of it past
    # the call's end: 10 ns
    assert reader.read(_ctx(HOST)) == pytest.approx(100.0 * 10 / 25)


def test_unspanned_idle_share_counts_overlapping_stages_once():
    reader = load_named("metrics", "session.unspanned_idle_share")
    nested = HOST + [(20, 23, "session.compile")]
    assert reader.read(_ctx(nested)) == pytest.approx(100.0 * 10 / 25)


def test_unspanned_idle_share_needs_the_root_span():
    """A program without ``session.call`` (one that predates the stage
    spans) gives no reading, and no error."""
    reader = load_named("metrics", "session.unspanned_idle_share")
    assert reader.read(_ctx([h for h in HOST
                             if h[2] != "session.call"])) is None
    assert reader.read(types.SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("metric", ["session.records_ms_per_call",
                                    "session.evaluate_ms_per_call"])
def test_stage_ms_per_call_by_hand(metric):
    stage = metric.split(".")[1].removesuffix("_ms_per_call")
    reader = load_named("metrics", metric)
    spans = [{"name": "session." + stage, "dur_us": 1500.0},
             {"name": "session." + stage, "dur_us": 500.0},
             {"name": "session.dispatch", "dur_us": 9000.0}]
    calls = [{"t0": 0.0, "t1": 1.0}] * 4
    ctx = types.SimpleNamespace(spans=spans, calls=calls)
    assert reader.read(ctx) == pytest.approx(2000.0 / 4 / 1e3)
    # a program without the stage span reads nothing
    ctx.spans = spans[2:]
    assert reader.read(ctx) is None


STAGE_METRICS = {
    "svm-wafer.run-sync": {"session.records_ms_per_call",
                           "session.evaluate_ms_per_call",
                           "session.unspanned_idle_share"},
    "kmeans-traffic.sweep-sync": {"session.evaluate_ms_per_call",
                                  "session.unspanned_idle_share"},
    "svm-wafer.run-async": {"session.records_ms_per_call",
                            "session.evaluate_ms_per_call",
                            "session.unspanned_idle_share"},
}


@pytest.mark.parametrize("cell", sorted(STAGE_METRICS))
def test_traced_run_reports_the_stage_metrics(cell):
    res = run_small(cell, trace=True)
    assert res["correct"], res["checks"]
    stage = {m for m in res["metrics"] if m.startswith("session.")
             and m != "session.host_ms_per_call"}
    assert stage == STAGE_METRICS[cell]
    for m in stage:
        assert res["metrics"][m]["value"] > 0
    assert res["metrics"]["session.unspanned_idle_share"]["value"] < 100
