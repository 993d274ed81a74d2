"""Find a fleet cell's knee: the highest arrival rate the server
sustains without a growing queue.

    python3 bench/knee.py --workload svm-wafer.fleet-poisson --rates 10 20 40 --seconds 10

One process, one warm server; each rate runs the cell's open loop for
``--seconds`` and prints one JSON line: tenants due, reports, p50/p95
latency of the first and the second half of the arrivals, and the
backlog (due but unreported) when the arrivals stop.  A queue that grows
shows as a second half slower than the first and a backlog that rises
with the rate.  The rates given replace the mix's ``rate_per_s``, so the
sweep runs on a cell whose rate is not known yet; the cell's
``rate_per_s`` is then set, once, to about 0.8 of the highest rate that
shows neither.  It needs the chip, as the harness does.
"""

import argparse
import json
import time

import numpy as np

import run as bench_run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="svm-wafer.fleet-poisson")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    _, cell, cfg, ref, traffic, _ = bench_run.load_cell(args.workload)
    bench_run.configure_jax(cfg)
    bench_run.require_chips(cell["chips"])
    from benchlib import drive, program
    fx = program.build(cfg, ref.init(cfg, 0))
    drv = drive.make(cfg, traffic, fx, args.seed)
    drv.setup()
    for rate in args.rates:
        drv.tenants, drv.lateness, drv.waves = [], [], 0
        drv.done.clear()
        drv.reports.clear()
        t0 = time.perf_counter()
        drv.window(args.seconds, rate=rate)
        lat = drv.latencies_ms()
        due = np.array([t["due"] for t in drv.tenants])
        stop = due.max() if len(due) else 0.0
        half = len(lat) // 2
        backlog = sum(1 for t in drv.tenants
                      if drv.done.get(t["id"], np.inf) > stop)
        print(json.dumps({
            "rate_per_s": rate, "due": len(lat),
            "reports": int(np.isfinite(lat).sum()),
            "p50_first_ms": float(np.percentile(lat[:half], 50)),
            "p50_second_ms": float(np.percentile(lat[half:], 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_at_last_arrival": backlog, "waves": drv.waves,
            "late_ms_max": max(drv.lateness, default=0.0) * 1e3,
            "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
