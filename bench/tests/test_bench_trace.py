"""The trace reduction on a small hand-made trace, and a traced run of
every cell through the harness."""

import pytest

from benchtest import keep_matmul_precision, run_small  # noqa: F401
from benchlib import trace

# ops [0,10) [5,20) [30,40) [50,55) (ns) in a window [0, 60): busy 35
DEVICE = [(0, 10, "fusion.1"), (5, 20, "fusion.7"), (30, 40, "while.3"),
          (50, 55, "_assign_kernel")]
HOST = [(0, 60, "bench.call"), (21, 29, "session.dispatch"),
        (41, 49, "other")]


def test_union_busy_and_idle():
    assert trace.union(DEVICE, 0, 60) == [(0, 20), (30, 40), (50, 55)]
    assert trace.busy_ns(DEVICE, 0, 60) == 35
    # clipped to a window
    assert trace.busy_ns(DEVICE, 8, 35) == 17


def test_op_totals_merge_instances():
    tot = dict(trace.op_totals(DEVICE, 0, 60))
    assert tot["fusion"] == pytest.approx(25e-9)
    assert tot["while"] == pytest.approx(10e-9)


def test_kernel_events_by_stable_name():
    ev = trace.kernel_events(DEVICE, {}, "_assign_kernel", 0, 60)
    assert [e[:2] for e in ev] == [(50, 55)]
    meta = {"while.3": "tf_op=jit(step)/while/pallas_call"}
    ev = trace.kernel_events(DEVICE, meta, "pallas_call", 0, 60)
    assert [e[:2] for e in ev] == [(30, 40)]


def test_idle_gaps_named_by_innermost_open_span():
    gaps = dict(trace.idle_gaps(DEVICE, HOST, 0, 60,
                                ("bench.call", "session.dispatch")))
    # [20,30) inside session.dispatch; [40,50) and [55,60) only inside
    # bench.call ("other" is not an attributable span)
    assert gaps["session.dispatch"] == pytest.approx(10e-9)
    assert gaps["bench.call"] == pytest.approx(15e-9)


SPAN_METRICS = {
    "svm-wafer.run-sync": {"session.host_ms_per_call"},
    "kmeans-traffic.sweep-sync": {"session.host_ms_per_call",
                                  "sweep.useful_iter_share"},
    "svm-wafer.run-async": {"session.host_ms_per_call"},
    "svm-wafer.fleet-poisson": {"fleet.wave_ms", "fleet.slot_occupancy"},
}


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_traced_run_reports_its_span_and_counter_metrics(cell):
    """A ``--trace 1`` run on the CPU: the window is traced and reduced,
    the result carries ``busy_s``, ``window_s`` and a breakdown, and the
    metrics read from program spans and counters are there (the device
    metrics need a chip's trace and peaks)."""
    res = run_small(cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] >= 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert SPAN_METRICS[cell] <= set(res["metrics"])
    for m in res["metrics"].values():
        assert m["value"] > 0


# a TPU trace names each op by its HLO text; a while loop's event holds
# the events of the ops it runs
LOOP = "%while.258 = (s32[63,10]{0,1:T(8,128)}) while((s32[63,10]) %tuple.1)"
FUSION = "%fusion.142 = f32[129024,64]{1,0:T(8,128)S(1)} fusion(f32[16,1052,64] %copy.26)"
KERNEL = ("%closed_call.10 = (s32[63,16,128,1]{3,2,1,0:T(8,128)}, "
          "f32[63,16,128,1]{3,2,1,0:T(8,128)}) custom-call("
          "f32[63,16,128,64]{3,2,1,0:T(8,128)S(1)} %bitcast.96, "
          "f32[63,16,3,64]{3,2,1,0:T(4,128)S(1)} %get-tuple-element.1), "
          "custom_call_target=\"tpu_custom_call\"")
CONSUMER = ("%reduce.175 = s32[63,16,128]{2,1,0:T(8,128)S(1)} "
            "reduce(s32[63,16,128,1]{3,2,1,0:T(8,128)} %pallas_call.10)")
OTHER_CALL = ("%custom-call.3 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} "
              "%p.1), custom_call_target=\"tpu_custom_call\"")
# (ns) a window of 1 ms; the kernel launch takes 356 us
TPU_DEVICE = [(0, 1_000_000, LOOP), (10_000, 200_000, FUSION),
              (200_000, 556_000, KERNEL), (560_000, 600_000, CONSUMER),
              (700_000, 800_000, OTHER_CALL)]


def test_op_names_from_hlo_text():
    assert trace.op_name(LOOP) == "while"
    assert trace.op_name(FUSION) == "fusion"
    assert trace.op_name(KERNEL) == "closed_call"
    assert trace.op_name("copy.26") == "copy"


def test_op_totals_leave_out_an_op_that_holds_others():
    tot = dict(trace.op_totals(TPU_DEVICE, 0, 1_000_000))
    assert "while" not in tot
    assert tot["fusion"] == pytest.approx(190e-6)
    assert tot["closed_call"] == pytest.approx(356e-6)
    # the loop still counts as busy time
    assert trace.busy_ns(TPU_DEVICE, 0, 1_000_000) == 1_000_000


def test_kernel_roofline_reads_the_e_step_custom_call():
    """Only the custom call with the E-step's operands counts: not the
    op that consumes its result, nor another custom call."""
    import types

    from benchlib import counts, load_named
    from benchlib.peaks import peak_for
    reader = load_named("metrics", "kmeans_assign_roofline")
    peak = peak_for("TPU v5 lite")
    ctx = types.SimpleNamespace(
        trace={"device": TPU_DEVICE, "meta": {}}, peak=peak, lo=0,
        hi=1_000_000,
        cfg={"features": 64, "classes": 3})
    need = counts.kmeans_assign_launch(63 * 16 * 128, 64, 3, 63 * 16)
    least = counts.roofline_seconds(need["flops"], need["bytes"], peak)[0]
    share = reader.read(ctx)
    assert share == pytest.approx(100.0 * least / 356e-6)
    assert 0 < share < 100
    ctx.trace = {"device": [(0, 10, FUSION), (20, 30, OTHER_CALL)],
                 "meta": {}}
    assert reader.read(ctx) is None
