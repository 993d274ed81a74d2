"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the cell needs is found by name: the cell in
``BENCHMARK.json`` names its configuration (``bench/configs/<c>.json``
with its plain reference ``<c>.py``) and its traffic
(``bench/traffic/<t>.json``, driven by ``bench/drivers/<kind>.py``);
the configuration names how it becomes a program
(``bench/programs/<program>.py``, ``classic`` by default), its data
(``bench/datasets/<kind>.py``) and its check
(``bench/checks/<check>.py``, ``host-f64`` by default);
``bench/cells/<cell>.json`` holds the limits of its correctness check;
each metric is read by ``bench/metrics/<metric>.py``.  A cell, a mix, a
model kind, a check or a metric is added by adding files.

One process, no children.  A cell on more than one chip runs on a mesh
over exactly its chips (``benchlib.program.mesh_for``).  Set-up (import,
data, program build and the warm-up of every shape the traffic uses,
compiles included) runs from process start to the window; the window
measures for ``--seconds``; then the program's state is freed and the
configuration's check replays a sample of what the window produced,
drawn from the seed.  ``--trace 1`` runs the same window under the
profiler and reports the per-layer metrics instead of the end-to-end
ones.

It runs on an accelerator only: without one, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.  JAX's
persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from benchlib import load_module, load_named  # noqa: E402

#: spans, of the harness and of the program, that name an idle gap
HOST_SPANS = ("bench.call", "bench.wait", "session.dispatch",
              "session.compile", "cohort.wave", "fleet.compile")


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, root=ROOT):
    """The cell's entry, configuration, reference, traffic and limits."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} "
                         f"(cells: {sorted(cells)})")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(os.path.join(root, conf["file"]))
    ref = load_module(os.path.splitext(os.path.join(root, conf["file"]))[0]
                      + ".py", "bench_ref_" + cell["config"].replace("-", "_"))
    here = os.path.join(root, "bench")
    traffic = _json(os.path.join(here, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(here, "cells", workload + ".json"))
    return bench, cell, cfg, ref, traffic, limits


def metrics_for(bench, workload, trace):
    """The metric entries this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(n_chips):
    """The device check: an accelerator with ``n_chips`` chips, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit(f"no accelerator: JAX sees {len(devs)} CPU "
                         "device(s); this benchmark runs on a TPU only")
    if len(devs) < n_chips:
        raise SystemExit(f"the cell needs {n_chips} chips, "
                         f"{len(devs)} visible")
    from benchlib.peaks import peak_for
    peak_for(devs[0].device_kind)
    return devs


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache) and the number of backend compiles, from
    ``jax.monitoring`` events."""

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


def configure_jax(cfg, cache=True):
    """The persistent cache at the checkout's fixed path (every program
    cached, so a second run of a cell compiles nothing), and matmuls at
    the precision the configuration states."""
    import jax
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])


def check(cfg, ref, rows, limits, seed):
    """Replay a sample of the window's runs with the configuration's
    check (``bench/checks/<kind>.py``) and compare; returns ``(correct,
    numbers, n_checked, ties_followed)``, the last the checked runs
    compared with a branch that assigns near ties the other way."""
    import numpy as np

    from benchlib import check as chk
    rng = np.random.default_rng([seed, 9])
    n = min(len(rows), int(limits["sample"]))
    longest = int(np.argmax([r["record"]["n"] for r in rows]))
    pick = [longest] + [int(i) for i in rng.permutation(len(rows))
                        if i != longest][:n - 1]
    wl = chk.kind(cfg).workload(cfg, ref)
    got = [chk.replay_numbers(cfg, wl, rows[i]) for i in pick]
    numbers = chk.worst(got)
    return (chk.verdict(numbers, limits["limits"]), numbers, len(pick),
            sum(g["ties_followed"] > 0 for g in got))


def run_cell(workload, seed, seconds, trace, *, root=ROOT,
             require_device=True, cache=True, overrides=None,
             out=sys.stdout, err=sys.stderr):
    """One run of one cell.  The tests use the keywords: ``overrides``
    replaces entries of the configuration, the traffic and the limits,
    ``require_device=False`` skips the look for a chip and
    ``cache=False`` leaves JAX's compilation cache alone."""
    import numpy as np

    bench, cell, cfg, ref, traffic, limits = load_cell(workload, root)
    for name, part in (("config", cfg), ("traffic", traffic),
                       ("limits", limits)):
        part.update((overrides or {}).get(name, {}))
    configure_jax(cfg, cache)
    import jax
    devs = require_chips(cell["chips"]) if require_device else jax.devices()
    chips = devs[:cell["chips"]]
    from benchlib import drive, program
    from benchlib import trace as btrace
    from repro.obs import trace as obs_trace

    clock = CompileClock()
    init_seed = int(drive.Draws(seed, 0).seeds(1)[0])
    init = ref.init(cfg, init_seed)
    fx = program.build(cfg, init, program.mesh_for(cfg, cell["chips"]))
    drv = drive.make(cfg, traffic, fx, seed)
    drv.setup()
    setup_s = time.perf_counter() - T_PROCESS

    tracer = obs_trace.Tracer(buffer=1 << 20)
    prev_tracer = obs_trace.use_tracer(tracer)
    compiles0 = clock.compiles
    cap = None
    try:
        if trace:
            with btrace.capture() as cap:
                with jax.profiler.TraceAnnotation("bench.window"):
                    win = drv.window(seconds)
        else:
            win = drv.window(seconds)
    finally:
        obs_trace.use_tracer(prev_tracer)
    compiles_in_window = clock.compiles - compiles0
    per_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in chips]
    memory_peak = max((b for b in per_chip if b is not None), default=None)

    spans = tracer.events()
    calls = [{k: v for k, v in c.items() if k != "report"}
             for c in getattr(drv, "calls", [])]
    lat = drv.latencies_ms() if hasattr(drv, "latencies_ms") else None
    waves = getattr(drv, "waves", None)
    rows = drv.checked_runs()
    for r in rows:
        r["init"] = init
    attempted = len(lat) if lat is not None else len(calls)
    failed = drv.missing() if lat is not None else 0

    correct, numbers, n_checked, ties = check(cfg, ref, rows, limits,
                                              seed)

    ctx = types.SimpleNamespace(
        cfg=cfg, ref=ref, traffic=traffic, cell=cell, seconds=seconds,
        window_s=win["t1"] - win["t0"], aggs=win["aggs"], setup_s=setup_s,
        calls=calls, latencies_ms=lat, waves=waves, spans=spans, rows=rows,
        peak=None, trace=None, lo=None, hi=None)
    info = {"workload": workload, "seed": seed, "calls": len(calls),
            "attempted": attempted, "aggregations": win["aggs"],
            "window_s": ctx.window_s, "setup_s": setup_s,
            "compiles_in_window": compiles_in_window,
            "checked_runs": n_checked, "ties_followed": ties}
    if lat is not None:
        info["generator_late_ms_max"] = max(drv.lateness, default=0.0) * 1e3
        info["generator_late_ms_mean"] = (float(np.mean(drv.lateness)) * 1e3
                                          if drv.lateness else 0.0)
        info["waves"] = waves
    print(json.dumps(info), file=out, flush=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak,
              "memory_peak_bytes_per_chip": per_chip}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if trace:
        from benchlib.peaks import peak_for
        tr = btrace.load(cap["path"])
        btrace.discard(cap)
        win_ev = [h for h in tr["host"] if h[2] == "bench.window"]
        lo, hi = win_ev[0][0], win_ev[0][1]
        ctx.trace, ctx.lo, ctx.hi = tr, lo, hi
        ctx.peak = peak_for(devs[0].device_kind) if require_device else None
        device["busy_s"] = btrace.busy_ns_per_device(
            tr["by_device"], lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in btrace.mean_totals(
                [btrace.op_totals(d, lo, hi) for d in tr["by_device"]])[:10]],
            "idle_gaps": [list(x) for x in btrace.mean_totals(
                [btrace.idle_gaps(d, tr["host"], lo, hi, HOST_SPANS)
                 for d in tr["by_device"]])[:10]]}
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        mod = load_named("metrics", m["name"], os.path.join(root, "bench"))
        value = mod.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    lim = limits["limits"]
    # a reading with no finite value (a count or an arm the reference
    # cannot match) is printed as 1e300: JSON has no infinity
    result["checks"] = {k: {"value": numbers[k] if math.isfinite(numbers[k])
                            else 1e300, "limit": lim[k]} for k in numbers}
    for k in numbers:
        print(f"check {k} {numbers[k]!r} limit {lim[k]!r}", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
