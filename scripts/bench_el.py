"""Benchmark the single-run EL data plane → ``BENCH_el.json``.

Measures, for one sync run and one async run of the paper's SVM
workload, the per-aggregation wall-clock and per-device peak live bytes
of every execution tier:

  * ``host``            — the host-driven loop (numpy control plane);
  * ``ingraph``         — the compiled ``lax.while_loop`` program
                          (the PR 3 replicated path — the baseline the
                          sharded/donated rows are judged against);
  * ``ingraph_donate``  — same program with the initial params' buffers
                          donated (XLA aliases them into the output:
                          in-place fleet update instead of a copy);
  * ``ingraph_telemetry`` — the program with the ``repro.obs`` in-graph
                          telemetry rings recording every round; against
                          the bare ``ingraph`` row this bounds the
                          observability overhead (acceptance: <10%);
  * ``ingraph_batched`` — (async only) the K-event wave program with an
                          explicit ``--async-batch-k`` wave width: K
                          completions pop, dispatch and merge per
                          while-loop step — order-equivalent to K=1,
                          fewer loop iterations;
  * ``ingraph_churn``   — (sync only) the scenario-path program
                          (``repro.el.scenarios``) under a
                          ``--churn-rate`` dropout schedule: mask-aware
                          aggregation + the policy switch; against the
                          bare ``ingraph`` row this bounds the scenario
                          engine's overhead (acceptance: <10%);
  * ``sharded``         — the program pjit-sharded over a debug mesh
                          built from forced host devices (edge dim over
                          ``data``, model tensors over ``model``), the
                          placement a TPU fleet uses via
                          ``repro.launch.mesh``;
  * ``sharded_donate``  — both.

Every compiled row carries the tier's full ``repro.obs.prof``
``ProgramProfile``: peak live bytes (arguments + outputs + temps −
aliased, per device, from XLA's ``memory_analysis``), cost-analysis
flops, and the HLO collective census — so the donation saving, the
per-device sharding saving AND the sharded program's collective
shape are visible (and regression-gated) even on CPU.  Timings are
CPU-host numbers — correctness-path costs, not TPU perf (the roofline
models that) — but the sharded rows execute the real partitioned
program on real (forced) devices.

Timing convention (shared with ``bench_fleet.py``): the scalar
``wall_us`` is the MIN over ``--repeats`` (the floor is the honest
cost on a shared host); the full min/mean/std/percentile spread is
kept alongside as ``wall_us_stats`` (``repro.obs.timing.
summarize_ns`` shape).  Each run also appends a schema-versioned
entry to ``BENCH_history.jsonl`` (``--no-history`` to skip) for
``scripts/bench_check.py``.

    PYTHONPATH=src python scripts/bench_el.py --devices 4 --out BENCH_el.json

Run from the repo root; the committed ``BENCH_el.json`` is this
script's output on the CI-class container.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# must precede the jax import: the sharded rows need a real (CPU-
# emulated) multi-device fleet, sized by --devices (default 4)
from repro.launch.hostdev import force_host_devices, use_compile_cache

force_host_devices("--devices", skip=(), count_from_flag=True,
                   always=True)

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.el import ELSession
from repro.el.events import (async_knob_names, async_knobs,
                             make_async_program, resolve_async_batch_k)
from repro.el.ingraph import (make_sync_program, sync_knob_names,
                              sync_knobs)
from repro.el.scenarios import ChurnSpec, ScenarioSpec
from repro.launch.classic import classic_fixture
from repro.launch.mesh import make_debug_mesh_for
from repro.obs.prof import profile_jit
from repro.obs.regress import append_history
from repro.obs.timing import repeat_s, summarize_ns, time_block
from repro.sharding import el_run_in_shardings


def _fixture(args):
    fx = classic_fixture("svm-wafer", samples=args.samples,
                         n_edges=args.edges, alpha=1.0,
                         batch=args.batch)
    ol = dataclasses.replace(
        fx["exp"].ol4el, mode="sync", policy="ol4el", n_edges=args.edges,
        budget=args.budget, heterogeneity=4.0, utility=fx["utility"],
        seed=0)
    return fx["model"], fx["executor"], ol, fx["n_samples"]


def _profile_row(jfn, example_args, donate):
    """The tier's ``ProgramProfile`` flattened into BENCH-row fields
    (the memory keys keep their historical names; the census and flops
    are new with the performance observatory)."""
    prof = profile_jit(jfn, *example_args, donated=donate)
    row = {
        "peak_live_bytes": prof.peak_live_bytes,
        "argument_bytes": prof.argument_bytes,
        "output_bytes": prof.output_bytes,
        "temp_bytes": prof.temp_bytes,
        "alias_bytes": prof.alias_bytes,
        "flops": prof.flops,
        "collectives": prof.collectives,
        "collective_bytes": prof.collective_bytes,
        "hlo_lines": prof.hlo_lines,
    }
    if prof.errors:
        row["profile_errors"] = list(prof.errors)
    return row


def bench_compiled(model, ex, ol, ns, mode, mesh, donate, args,
                   telemetry=None, batch_k=None, scenario=None):
    """Time one compiled-program tier and read its memory analysis."""
    cfg = dataclasses.replace(ol, mode=mode, scenario=scenario)
    if batch_k is not None:
        cfg = dataclasses.replace(cfg, async_batch_k=int(batch_k))
    if mode == "sync":
        core = make_sync_program(
            model, ex.edge_data, ex.eval_set, cfg, lr=ex.lr, batch=ex.batch,
            n_samples=np.asarray(ns, np.float64),
            max_rounds=args.max_rounds, mesh=mesh, telemetry=telemetry)
        knobs, knob_names = sync_knobs(cfg), sync_knob_names(cfg)
    else:
        core = make_async_program(
            model, ex.edge_data, ex.eval_set, cfg, lr=ex.lr, batch=ex.batch,
            max_events=args.max_events, mesh=mesh, telemetry=telemetry)
        knobs, knob_names = async_knobs(cfg), async_knob_names(cfg)
    params0 = model.init(jax.random.key(0))
    rng = jax.random.key(cfg.seed + 17)
    kw = {}
    if donate:
        kw["donate_argnums"] = (0,)
    if mesh is not None:
        kw["in_shardings"] = el_run_in_shardings(
            mesh, model.cfg, jax.eval_shape(lambda p: p, params0),
            knob_names)
    jfn = jax.jit(core, **kw)

    def fresh():
        return jax.tree.map(lambda x: x.copy(), params0)

    _, out = jax.block_until_ready(jfn(fresh(), rng, knobs))   # compile
    n_agg = int(out["n_rounds"])
    reps = [s * 1e6 for s in repeat_s(
        lambda: jax.block_until_ready(jfn(fresh(), rng, knobs)),
        args.repeats)]
    # min-of-repeats: the host is a shared CPU, so the floor is the
    # honest per-program cost (the mean rides scheduler noise)
    dt_us = min(reps)
    row = {
        "n_aggregations": n_agg,
        "us_per_aggregation": dt_us / max(n_agg, 1),
        "wall_us": dt_us,
        "wall_us_stats": summarize_ns(reps),
    }
    row.update(_profile_row(
        jfn, (jax.eval_shape(lambda p: p, params0), rng, knobs), donate))
    return row


def bench_host(model, ex, ol, ns, mode):
    cfg = dataclasses.replace(ol, mode=mode)

    def run():
        s = (ELSession(cfg, metric_name="accuracy", lr=0.05)
             .with_executor(ex, init_params=model.init(jax.random.key(0)),
                            n_samples=ns))
        return s.run_sync() if mode == "sync" else s.run_async()

    run()                                       # warm the executor jits
    with time_block() as tb:
        rep = run()
    dt_us = tb.us
    return {"n_aggregations": rep.n_aggregations,
            "us_per_aggregation": dt_us / max(rep.n_aggregations, 1),
            "wall_us": dt_us, "peak_live_bytes": None}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="single-run EL data-plane benchmark -> BENCH_el.json")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count (the debug mesh is "
                         "(devices//2, 2))")
    ap.add_argument("--edges", type=int, default=8)
    ap.add_argument("--samples", type=int, default=4000)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--budget", type=float, default=4000.0)
    ap.add_argument("--max-rounds", type=int, default=64)
    ap.add_argument("--max-events", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--async-batch-k", type=int, default=4,
                    help="explicit K of the el_async_ingraph_batched "
                         "tier (the replicated K-event wave program; "
                         "sharded tiers auto-tune K from the mesh)")
    ap.add_argument("--telemetry-ring", type=int, default=64,
                    help="ring length of the el_*_ingraph_telemetry "
                         "tiers (repro.obs in-graph rings)")
    ap.add_argument("--churn-rate", type=float, default=0.25,
                    help="dropout rate of the el_sync_ingraph_churn "
                         "tier's scenario (repro.el.scenarios)")
    ap.add_argument("--skip-host", action="store_true",
                    help="omit the slow host-loop baselines")
    ap.add_argument("--out", default="BENCH_el.json")
    ap.add_argument("--history", default="BENCH_history.jsonl",
                    help="append a schema-versioned entry here "
                         "(scripts/bench_check.py reads it)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.jsonl append")
    args = ap.parse_args(argv)
    use_compile_cache()

    n_dev = jax.device_count()
    mesh = make_debug_mesh_for(n_dev)
    model, ex, ol, ns = _fixture(args)

    churn_scn = ScenarioSpec(churn=ChurnSpec(rate=args.churn_rate))

    rows = {}
    # (name, mesh, donate, telemetry, batch_k, scenario) — batch_k is
    # async-only: the batched tier pins an explicit K-event wave width
    # on the replicated program; sharded tiers auto-tune K from the
    # mesh; the churn tier is sync-only (the scenario-path program with
    # a dropout schedule, gated <10% per-round over the bare one)
    tiers = [("ingraph", None, False, None, None, None),
             ("ingraph_donate", None, True, None, None, None),
             ("ingraph_telemetry", None, False, args.telemetry_ring, None,
              None),
             ("ingraph_batched", None, False, None, args.async_batch_k,
              None),
             ("ingraph_churn", None, False, None, None, churn_scn),
             ("sharded", mesh, False, None, None, None),
             ("sharded_donate", mesh, True, None, None, None)]
    for mode in ("sync", "async"):
        if not args.skip_host:
            rows[f"el_{mode}_host"] = bench_host(model, ex, ol, ns, mode)
            print(f"el_{mode}_host: "
                  f"{rows[f'el_{mode}_host']['us_per_aggregation']:.0f} "
                  "us/agg", flush=True)
        for name, m, donate, telem, batch_k, scn in tiers:
            if batch_k is not None and mode != "async":
                continue
            if scn is not None and mode != "sync":
                continue
            row = bench_compiled(model, ex, ol, ns, mode, m, donate, args,
                                 telemetry=telem, batch_k=batch_k,
                                 scenario=scn)
            rows[f"el_{mode}_{name}"] = row
            peak = row.get("peak_live_bytes")
            print(f"el_{mode}_{name}: {row['us_per_aggregation']:.0f} "
                  f"us/agg, peak "
                  f"{peak if peak is None else f'{peak / 1e6:.2f}MB'}",
                  flush=True)
        # instrumented/scenario per-round cost vs the bare program —
        # the acceptance bound for both is <10% (bench_check gates any
        # row carrying overhead_vs_ingraph_pct)
        base = rows[f"el_{mode}_ingraph"]["us_per_aggregation"]
        over = [f"el_{mode}_ingraph_telemetry"]
        if mode == "sync":
            over.append("el_sync_ingraph_churn")
        for tier_name in over:
            trow = rows[tier_name]
            trow["overhead_vs_ingraph_pct"] = (
                (trow["us_per_aggregation"] - base) / max(base, 1e-9)
                * 100)
            print(f"{tier_name} overhead: "
                  f"{trow['overhead_vs_ingraph_pct']:+.1f}%", flush=True)

    report = {
        "meta": {
            "workload": "svm-wafer",
            "edges": args.edges, "samples": args.samples,
            "batch": args.batch, "budget": args.budget,
            "max_rounds": args.max_rounds, "max_events": args.max_events,
            "devices": n_dev, "mesh": dict(mesh.shape),
            "repeats": args.repeats,
            "async_batch_k": {
                "batched_tier": int(args.async_batch_k),
                "sharded_auto": resolve_async_batch_k(
                    dataclasses.replace(ol, mode="async"), mesh),
            },
            "churn": {"rate": float(args.churn_rate),
                      "period": churn_scn.period},
            "backend": jax.default_backend(), "jax": jax.__version__,
            "note": ("CPU-host correctness-path timings; wall_us is "
                     "min-of-repeats (wall_us_stats carries the spread); "
                     "peak bytes are per-device XLA memory_analysis "
                     "(args+outputs+temps-aliased); collectives are the "
                     "optimized-HLO census (XLA-version dependent)"),
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    if not args.no_history:
        append_history(args.history, "el", report["meta"], rows)
        print(f"appended to {args.history}")


if __name__ == "__main__":
    main()
