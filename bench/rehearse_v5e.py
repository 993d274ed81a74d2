"""Compile the K-means sweep cell's program for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse_v5e.py [--workload kmeans-traffic.sweep-sync]

No chip: the TPU compiler builds the vmapped sweep program — 63 cells x
16 edges, the ``kmeans_assign`` kernel at [128, 64] rows per lane — for
a v5e that is described, not attached, and refuses what the chip would
refuse.  Prints whether the kernel is there natively
(``tpu_custom_call``), the kernel's operand shapes as the compiler sees
them, and the program's memory analysis.  Run it before the first chip
call of a cell whose shapes the kernel has not compiled at.
"""

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="kmeans-traffic.sweep-sync")
    args = ap.parse_args(argv)
    _, _, cfg, ref, traffic, _ = bench_run.load_cell(args.workload)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.kmeans_assign.ops as ka_ops
    from benchlib import program
    from repro.el.sweep import SweepSpec
    from repro.el.sweep.engine import make_sweep_program, stack_knobs

    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    # the CPU backend would pick interpret mode: compile the native kernel
    ka_ops.interpret_default = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    fx = program.build(cfg, ref.init(cfg, 0))
    ex, base = fx["executor"], fx["base"]
    grid = traffic["grid"]
    spec = SweepSpec(heterogeneity=tuple(grid["heterogeneity"]),
                     budget=tuple(grid["budget"]),
                     seeds=tuple(range(traffic["seeds_per_call"])),
                     max_rounds=traffic["max_rounds"])
    prog = make_sweep_program(ex.model, ex.edge_data, ex.eval_set, base,
                              spec, lr=ex.lr, batch=ex.batch,
                              n_samples=np.asarray(fx["n_samples"]),
                              metric_name=fx["metric"])
    cells = spec.cell_cfgs(base)
    knobs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
             for k, v in stack_knobs(cells).items()}
    key = jax.random.key(0)
    keys = jax.ShapeDtypeStruct((len(cells),), key.dtype, sharding=one)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
              for k, v in fx["init"].items()}
    compiled = prog.lower(params, keys, knobs).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"^.*custom_call_target=\"tpu_custom_call\".*$", hlo,
                       re.M)
    shapes = sorted({m for c in calls
                     for m in re.findall(r"f32\[[0-9,]+\]", c)})
    names = sorted(set(re.findall(r'"kernel_name":\s*"?([A-Za-z0-9_]+)',
                                  hlo)) | set(re.findall(
                                      r"name=\"?([A-Za-z0-9_]*assign"
                                      r"[A-Za-z0-9_]*)", hlo)))
    mem = compiled.memory_analysis()
    print(json.dumps({
        "workload": args.workload, "cells": len(cells),
        "tpu_custom_calls": len(calls), "kernel_operand_shapes": shapes,
        "kernel_names": names,
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None)}))
    return 0 if calls else 1


if __name__ == "__main__":
    sys.exit(main())
