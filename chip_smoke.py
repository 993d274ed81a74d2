"""Drive the compiled OL4EL engine once on a TPU, through its entry points.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # a 2x2 mesh: the sharded run only

One process and no children: a process that has touched JAX holds the
chip.  Each phase prints one JSON line with its compile and run seconds
(compile = JAX trace + lowering + backend compile or cache load, from
``jax.monitoring``); the last line is ``{"ok": true, "device": ...}``.
Nothing is caught: a failed phase raises, the script exits non-zero and
that line is never printed.  No TPU (``JAX_PLATFORMS=cpu``, or a
directory without the repo) is a failure too, never a CPU fallback.

One chip, at the paper's widths and data size (20,000 samples, 16
edges, budget 5000; rounds capped with ``--steps``):

  svm-sync       ``repro.launch.train`` sync: aggregations happen and the
                 final accuracy is finite and well above chance (1/8);
  svm-async      the async run; the same capped run replayed by the host
                 event queue (``ELSession.run_async(rng_streams="jax")``)
                 must match it event for event;
  kmeans-pallas  sync K-means (64-d, K=3) with ``--kmeans-impl pallas``:
                 the compiled program holds a ``tpu_custom_call`` (the
                 kernel runs natively), and one E-step over all 20,000
                 points agrees with ``kernels/kmeans_assign/ref.py``
                 wherever the nearest two centroids are not tied;
  fleet          ``repro.launch.fleet --demo --assert-compiles 2``.

``--four-chips`` runs svm-wafer sync and async (auto K=4 event waves)
sharded over a 2x2 ``(data, model)`` mesh with donated params and the
default collective contract armed (>= 1 all-gather, 0 all-reduce, alias
bytes == param bytes), each beside the same run unsharded on one chip:
they must agree bit for bit.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the paper's SVM testbed at full size (benchmarks/run.py --full)
SVM = ["--arch", "svm-wafer", "--mode", "ol4el", "--samples", "20000",
       "--edges", "16", "--budget", "5000"]
KMEANS = ["--arch", "kmeans-traffic", "--mode", "ol4el", "--samples",
          "20000", "--edges", "16", "--budget", "5000"]
#: sync rounds / async steps (x16 edges = events) per capped run
SYNC_ROUNDS, ASYNC_STEPS = "48", "24"
#: "well above chance" for the 8-class wafer task
MIN_ACCURACY = 0.5


def require_tpu(n_chips: int):
    """The device check: a TPU with at least ``n_chips`` chips, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX sees {devs[0].platform!r} devices "
                 f"({len(devs)}); this smoke test runs on a TPU only")
    if len(devs) < n_chips:
        sys.exit(f"need {n_chips} TPU chips, {len(devs)} visible")
    return devs


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), summed from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self._events = {dispatch.JAXPR_TRACE_EVENT,
                        dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                        dispatch.BACKEND_COMPILE_EVENT}
        self._backend = dispatch.BACKEND_COMPILE_EVENT
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._events:
            self.seconds += duration
            self.compiles += event == self._backend


@contextlib.contextmanager
def phase(clock: CompileClock, name: str):
    """Time one phase; print its line only if it completed."""
    out = {}
    c0, n0, t0 = clock.seconds, clock.compiles, time.perf_counter()
    yield out
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    line = {"phase": name, "compile_s": round(comp, 3),
            "run_s": round(wall - comp, 3),
            "compiles": clock.compiles - n0, **out}
    print(json.dumps(line), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def first_mismatch(a, b):
    """``None`` when two reports agree bit for bit (records, arm pulls,
    final params); else a description of the first difference."""
    import jax
    import numpy as np
    if a.n_aggregations != b.n_aggregations:
        return f"n_aggregations {a.n_aggregations} != {b.n_aggregations}"
    for t, (ra, rb) in enumerate(zip(a.records, b.records)):
        for f in ("edge", "interval", "wall_time", "total_consumed",
                  "metric", "utility"):
            x, y = getattr(ra, f), getattr(rb, f)
            if x != y and not (math.isnan(x) and math.isnan(y)):
                return f"event {t}: {f} {x!r} != {y!r}"
    if a.arm_pulls != b.arm_pulls:
        return f"arm_pulls {a.arm_pulls} != {b.arm_pulls}"
    for x, y in zip(jax.tree.leaves(a.final_params),
                    jax.tree.leaves(b.final_params)):
        x, y = np.asarray(x), np.asarray(y)
        if not np.array_equal(x, y):
            return (f"final params differ at {int(np.sum(x != y))} of "
                    f"{x.size} entries (max |diff| "
                    f"{float(np.max(np.abs(x - y)))!r})")
    return None


def run_summary(report) -> dict:
    return {"aggregations": report.n_aggregations,
            "final_metric": report.final_metric,
            "consumed": report.total_consumed,
            "reason": report.terminated_reason}


def svm_phases(clock, train) -> None:
    with phase(clock, "svm-sync") as out:
        rep = train.main(SVM + ["--el-mode", "sync", "--steps", SYNC_ROUNDS])
        out.update(run_summary(rep))
        check(rep.n_aggregations > 0, "svm sync: no aggregations")
        check(math.isfinite(rep.final_metric)
              and rep.final_metric >= MIN_ACCURACY,
              f"svm sync: accuracy {rep.final_metric!r} < {MIN_ACCURACY}")

    argv = SVM + ["--el-mode", "async", "--steps", ASYNC_STEPS]
    with phase(clock, "svm-async") as out:
        rep = train.main(argv)
        out.update(run_summary(rep))
        check(rep.n_aggregations > 0, "svm async: no aggregations")
        check(math.isfinite(rep.final_metric)
              and rep.final_metric >= MIN_ACCURACY,
              f"svm async: accuracy {rep.final_metric!r} < {MIN_ACCURACY}")

    with phase(clock, "svm-async-vs-host") as out:
        args = train.parse_args(argv)
        host = train.classic_session(args).run_async(
            max_events=int(ASYNC_STEPS) * args.edges, rng_streams="jax")
        diff = first_mismatch(host, rep)
        out.update(events=host.n_aggregations, bit_identical=diff is None)
        check(diff is None, f"host event queue vs compiled async: {diff}")


def kmeans_phase(clock, train) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import make_traffic_dataset
    from repro.kernels.kmeans_assign.ops import assign_with_dist
    from repro.kernels.kmeans_assign.ref import assign_ref

    with phase(clock, "kmeans-pallas") as out:
        os.environ["REPRO_EL_PROFILE"] = "1"   # profile: the HLO census
        try:
            rep = train.main(KMEANS + ["--el-mode", "sync", "--steps",
                                       SYNC_ROUNDS, "--kmeans-impl",
                                       "pallas"])
        finally:
            del os.environ["REPRO_EL_PROFILE"]
        kernels = rep.telemetry["profile"]["custom_calls"].get(
            "tpu_custom_call", 0)
        out.update(run_summary(rep), tpu_custom_calls=kernels)
        check(rep.n_aggregations > 0, "kmeans: no aggregations")
        check(math.isfinite(rep.final_metric), "kmeans: F1 not finite")
        check(kernels > 0, "kmeans: no tpu_custom_call in the compiled "
                           "program (the kernel did not run natively)")

    with phase(clock, "kmeans-estep-vs-ref") as out:
        train_d, test_d = make_traffic_dataset(n=20000)
        x = jnp.asarray(np.concatenate([train_d["x"], test_d["x"]]))
        centers = rep.final_params["centers"]
        a, d2 = jax.jit(assign_with_dist)(x, centers)
        with jax.default_matmul_precision("highest"):
            a_ref, _ = jax.jit(assign_ref)(x, centers)
            xn, cn = np.asarray(x, np.float64), np.asarray(centers, np.float64)
        # a tie: the two nearest centroids closer than what rounding
        # bf16 inputs could move a distance (|2 x.c| <= |x|^2 + |c|^2)
        exact = (np.sum(xn ** 2, 1)[:, None] - 2 * xn @ cn.T
                 + np.sum(cn ** 2, 1)[None, :])
        best2 = np.sort(exact, 1)[:, :2]
        scale = np.sum(xn ** 2, 1) + np.max(np.sum(cn ** 2, 1))
        tie = (best2[:, 1] - best2[:, 0]) <= 2.0 ** -7 * scale
        a, a_ref = np.asarray(a), np.asarray(a_ref)
        wrong = (a != a_ref) & ~tie
        out.update(points=int(x.shape[0]), ties=int(tie.sum()),
                   mismatches=int((a != a_ref).sum()),
                   mismatches_off_tie=int(wrong.sum()),
                   max_d2_err=float(np.max(np.abs(
                       np.asarray(d2, np.float64) - best2[:, 0]))))
        check(not wrong.any(), f"E-step: {int(wrong.sum())} assignments "
                               "differ from ref.py away from a tie")


def fleet_phase(clock) -> None:
    from repro.launch import fleet
    with phase(clock, "fleet") as out:
        st = fleet.main(["--demo", "--assert-compiles", "2"])
        out.update(tenants=st["tenants_done"], cohorts=st["cohorts"],
                   cohort_compiles=st["compiles"], waves=st["waves"])


def sharded_phase(clock, train) -> None:
    """The 2x2 sharded single run vs the same run on one chip."""
    import jax

    from repro.obs.prof import param_tree_bytes

    os.environ["REPRO_EL_CONTRACTS"] = "1"     # enforce before dispatch
    try:
        for mode, cap in (("sync", SYNC_ROUNDS), ("async", ASYNC_STEPS)):
            argv = SVM + ["--el-mode", mode, "--steps", cap, "--donate"]
            with phase(clock, f"svm-{mode}-2x2") as out:
                one = train.main(argv)
                mesh = train.main(argv + ["--mesh", "debug"])
                prof = mesh.telemetry["profile"]
                count = {op: int(prof["collectives"].get(op, {}).get(
                    "count", 0)) for op in ("all-gather", "all-reduce")}
                # on-device bytes: a TPU pads each leaf to whole tiles
                pbytes = param_tree_bytes(mesh.final_params)
                diff = first_mismatch(one, mesh)
                out.update(run_summary(mesh), mesh="2x2", census=count,
                           alias_bytes=prof["alias_bytes"],
                           param_bytes=pbytes,
                           param_logical_bytes=sum(
                               x.nbytes for x in
                               jax.tree.leaves(mesh.final_params)),
                           bit_identical=diff is None)
                check(count["all-gather"] >= 1 and count["all-reduce"] == 0,
                      f"{mode} 2x2 census {count}")
                check(prof["alias_bytes"] == pbytes,
                      f"{mode} 2x2 alias {prof['alias_bytes']} != {pbytes}")
                check(diff is None, f"{mode} sharded vs unsharded: {diff}")
    finally:
        del os.environ["REPRO_EL_CONTRACTS"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded single run and its "
                         "unsharded comparison (needs 4 chips)")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)

    from repro.launch import train
    from repro.launch.hostdev import use_compile_cache

    clock = CompileClock()
    with phase(clock, "device") as out:
        out.update(platform=devs[0].platform, kind=devs[0].device_kind,
                   count=len(devs), compile_cache=use_compile_cache())
    if args.four_chips:
        sharded_phase(clock, train)
    else:
        svm_phases(clock, train)
        kmeans_phase(clock, train)
        fleet_phase(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
