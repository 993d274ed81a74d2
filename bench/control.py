"""Read the correctness check's numbers of a cell over many seeds, for
the program as the configuration states it and for its control.

    python3 bench/control.py --workload <cell> --seconds 3 \\
        --precisions config default --seeds 11 12 13

The control is the program with its own lower-precision path switched
on: matrix products in one bfloat16 pass (JAX's ``default`` matmul
precision on a TPU) where the configuration states float32 at ``high``.
Each (precision, seed) runs the cell's harness once, at the cell's own
sizes and load, in this one process (set-up is paid once per program),
and prints one JSON line: the numbers compared, ``correct`` under the
committed limits, and the run's counts.  ``check-control`` puts the
configuration's check reference in the program's place instead, a
precision step lower, on the cell's ``sample`` runs
(``benchlib.control``; for a ``device-f32`` check, on the chip).  The
lower readings of the limits in ``bench/cells/`` come from ``config``
runs, the upper readings from ``default`` runs (``PERF.md`` lists both).
The benchmark's own runs never run this.  It needs the chip, as the
harness does.
"""

import argparse
import io
import json
import sys
import time

import run as bench_run


#: the configuration's check reference in the program's place, a
#: precision step lower (``benchlib.control``)
REFERENCE = "check-control"


def reference_control(workload: str, seed: int) -> dict:
    """The check kind's control on ``limits["sample"]`` runs of the
    cell's traffic: for ``device-f32`` the model's reference on the
    chip with one-pass bfloat16 products, replayed at ``highest``."""
    from benchlib import check, control
    _, cell, cfg, ref, traffic, limits = bench_run.load_cell(workload)
    bench_run.configure_jax(cfg)
    bench_run.require_chips(cell["chips"])
    nums = control.readings(cfg, ref, traffic, seed, int(limits["sample"]))
    return {"workload": workload, "precision": REFERENCE, "seed": seed,
            "check": cfg.get("check", "host-f64"),
            "correct": check.verdict(nums, limits["limits"]),
            "numbers": nums}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precisions", nargs="+", default=["config", "default"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for prec in args.precisions:
        ov = ({} if prec == "config"
              else {"config": {"matmul_precision": prec}})
        for seed in args.seeds:
            t0 = time.perf_counter()
            if prec == REFERENCE:
                print(json.dumps(dict(reference_control(args.workload,
                                                        seed),
                                      seconds=time.perf_counter() - t0)),
                      flush=True)
                continue
            out = io.StringIO()
            res = bench_run.run_cell(args.workload, seed, args.seconds,
                                     False, overrides=ov, out=out,
                                     err=io.StringIO())
            info = json.loads(out.getvalue().splitlines()[0])
            print(json.dumps({
                "workload": args.workload, "precision": prec, "seed": seed,
                "correct": res["correct"], "attempted": res["attempted"],
                "checked_runs": info["checked_runs"],
                "aggregations": info["aggregations"],
                "numbers": {k: v["value"] for k, v in res["checks"].items()},
                "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
