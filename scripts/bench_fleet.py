"""Benchmark multi-tenant EL serving → ``BENCH_fleet.json``.

Times three ways to serve N independent EL tenants (same structural
config, per-tenant knobs/seeds — i.e. one cohort):

  * ``sequential_host``    — one ``ELSession.run`` per tenant: the
    host-driven loop, back-to-back (the pre-fleet way to serve a
    tenant population, and the baseline the acceptance speedup is
    judged against);
  * ``sequential_ingraph`` — one ``ELSession.run_sync_ingraph`` per
    tenant, all sessions sharing ONE compiled-program pool (the
    strongest sequential baseline: compiled data plane, no
    per-tenant recompiles);
  * ``fleet``              — a :class:`repro.el.fleet.FleetServer`
    with ``--slots`` batch width serving the same tenants as slot
    waves of one vmapped program, free slots refilled mid-flight.

All tiers produce bit-identical per-tenant reports (that is the fleet
test suite's contract); this script only measures throughput —
tenants/sec and per-aggregation latency — at each ``--tenants`` count.
Timings are CPU-host numbers, min-of-repeats.  On a CPU host the
vmapped slot batch buys no data parallelism (lane compute serializes),
so the fleet's edge over the ingraph tier is amortized dispatch and
bulk host-side report reads; against the host loop it is the compiled
data plane itself.

    PYTHONPATH=src python scripts/bench_fleet.py --out BENCH_fleet.json

Run from the repo root; the committed ``BENCH_fleet.json`` is this
script's output on the CI-class container.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# must precede the jax import (keeps the env identical to bench_el.py;
# the default rows run replicated, so the forced fleet is idle)
from repro.launch.hostdev import force_host_devices, use_compile_cache

force_host_devices("--devices", skip=(), count_from_flag=True,
                   always=True)

import argparse
import dataclasses
import json
from typing import List

import jax

from repro.el import ELSession, TenantRun
from repro.el.cache import ProgramCache
from repro.el.fleet import FleetServer
from repro.launch.classic import classic_fixture
from repro.obs.regress import append_history
from repro.obs.timing import repeat_s, summarize_ns

#: per-tenant knob grids — every combination is the SAME structural
#: config, so the whole population is one cohort / one compile
UCB_GRID = (0.5, 1.0, 1.5, 2.0)
BUDGET_GRID = (600.0, 900.0, 1200.0, 1500.0)


def _fixture(args):
    fx = classic_fixture("svm-wafer", samples=args.samples,
                         n_edges=args.edges, alpha=args.alpha,
                         data_seed=0)
    base = dataclasses.replace(
        fx["exp"].ol4el, mode="sync", policy="ol4el", n_edges=args.edges,
        utility=fx["utility"])
    return fx, base


def _tenant_cfgs(base, n: int):
    return [dataclasses.replace(base, ucb_c=UCB_GRID[i % len(UCB_GRID)],
                                budget=BUDGET_GRID[i % len(BUDGET_GRID)],
                                seed=i)
            for i in range(n)]


def bench_sequential(fx, base, n: int, args, ingraph: bool) -> dict:
    """N back-to-back single-tenant runs: the host loop
    (``ELSession.run``) or the compiled fast path
    (``run_sync_ingraph``, one shared program pool so the timed loop
    measures steady-state throughput, not N-1 recompiles)."""
    pool = ProgramCache(8)

    def run_all(count: int) -> int:
        total = 0
        for cfg in _tenant_cfgs(base, count):
            s = ELSession(cfg, metric_name=fx["metric"], lr=fx["lr"])
            s._programs = pool              # shared pool: no per-tenant recompile
            s.with_executor(fx["executor"],
                            init_params=fx["init_params"],
                            n_samples=fx["n_samples"])
            rep = (s.run_sync_ingraph(max_rounds=args.max_rounds)
                   if ingraph else s.run())
            total += rep.n_aggregations
        return total

    run_all(1)                              # warm the jits / compile once
    last = {}
    reps = repeat_s(lambda: last.update(n_agg=run_all(n)), args.repeats)
    n_agg = last["n_agg"]
    wall = min(reps)
    return {"tenants": n, "wall_s": wall,
            "wall_s_stats": summarize_ns(reps),
            "tenants_per_sec": n / wall,
            "n_aggregations": n_agg,
            "us_per_aggregation": wall * 1e6 / max(n_agg, 1)}


def bench_fleet(fx, base, n: int, args) -> dict:
    """The same tenants through a FleetServer (one cohort, slot waves
    with mid-flight refill); the shared cache keeps the program warm
    across repeats."""
    cache = ProgramCache(8)

    def runs(count: int) -> List[TenantRun]:
        return [TenantRun(cfg=cfg, executor=fx["executor"],
                          tenant_id=f"t{i:04d}",
                          metric_name=fx["metric"],
                          n_samples=fx["n_samples"],
                          init_params=fx["init_params"],
                          max_rounds=args.max_rounds)
                for i, cfg in enumerate(_tenant_cfgs(base, count))]

    def serve(count: int):
        srv = FleetServer(n_slots=args.slots,
                          rounds_per_wave=args.rounds_per_wave,
                          cache=cache)
        for run in runs(count):
            srv.submit(run)
        reports = srv.drain()
        st = srv.stats()
        srv.close()
        return reports, st

    serve(args.slots)                       # compile the cohort program
    last = {}
    reps = repeat_s(lambda: last.update(zip(("reports", "stats"),
                                            serve(n))), args.repeats)
    stats = last["stats"]
    n_agg = sum(r.n_aggregations for r in last["reports"].values())
    wall = min(reps)
    return {"tenants": n, "wall_s": wall,
            "wall_s_stats": summarize_ns(reps),
            "tenants_per_sec": n / wall,
            "n_aggregations": n_agg,
            "us_per_aggregation": wall * 1e6 / max(n_agg, 1),
            "waves": stats["waves"], "compiles": stats["compiles"]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="multi-tenant EL serving benchmark -> BENCH_fleet.json")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--tenants", default="16,64,256",
                    help="comma-separated tenant counts to benchmark")
    ap.add_argument("--slots", type=int, default=8,
                    help="fleet cohort batch width (8 is the CPU-host "
                         "sweet spot: wider batches burn masked lanes "
                         "on round-count divergence)")
    ap.add_argument("--rounds-per-wave", type=int, default=4,
                    help="device rounds between host harvest/refill "
                         "points (small waves refill freed slots "
                         "sooner)")
    ap.add_argument("--edges", type=int, default=4)
    ap.add_argument("--samples", type=int, default=512)
    ap.add_argument("--alpha", type=float, default=100.0)
    ap.add_argument("--max-rounds", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--skip-host", action="store_true",
                    help="omit the slow host-loop sequential baseline")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--history", default="BENCH_history.jsonl",
                    help="append a schema-versioned entry here "
                         "(scripts/bench_check.py reads it)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_history.jsonl append")
    args = ap.parse_args(argv)
    use_compile_cache()
    counts = [int(c) for c in args.tenants.split(",") if c]

    fx, base = _fixture(args)
    rows = {}
    for n in counts:
        host = None
        if not args.skip_host:
            host = bench_sequential(fx, base, n, args, ingraph=False)
            rows[f"sequential_host_{n}"] = host
        seq = bench_sequential(fx, base, n, args, ingraph=True)
        flt = bench_fleet(fx, base, n, args)
        flt["speedup_vs_sequential_ingraph"] = (flt["tenants_per_sec"]
                                               / seq["tenants_per_sec"])
        if host is not None:
            flt["speedup_vs_sequential_host"] = (flt["tenants_per_sec"]
                                                 / host["tenants_per_sec"])
        rows[f"sequential_ingraph_{n}"] = seq
        rows[f"fleet_{n}"] = flt
        hosttxt = ("" if host is None else
                   f"host {host['tenants_per_sec']:6.2f} t/s | ")
        print(f"n={n:4d}: {hosttxt}ingraph "
              f"{seq['tenants_per_sec']:7.2f} t/s "
              f"({seq['us_per_aggregation']:.0f} us/agg) | fleet "
              f"{flt['tenants_per_sec']:7.2f} t/s "
              f"({flt['us_per_aggregation']:.0f} us/agg, "
              f"{flt['waves']} waves) -> "
              f"{flt['speedup_vs_sequential_ingraph']:.2f}x vs ingraph"
              + ("" if host is None else
                 f", {flt['speedup_vs_sequential_host']:.2f}x vs host"),
              flush=True)

    report = {
        "meta": {
            "workload": "svm-wafer sync, one cohort (knobs/seed vary "
                        "per tenant)",
            "slots": args.slots, "rounds_per_wave": args.rounds_per_wave,
            "edges": args.edges, "samples": args.samples,
            "max_rounds": args.max_rounds, "repeats": args.repeats,
            "backend": jax.default_backend(), "jax": jax.__version__,
            "note": ("CPU-host wall clock: wall_s is min-of-repeats "
                     "(wall_s_stats carries the spread); every tier "
                     "warm-compiled before timing and bit-identical by "
                     "the fleet test suite's contract; on CPU the "
                     "fleet's edge over ingraph is amortized dispatch + "
                     "bulk report reads, not lane parallelism"),
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    if not args.no_history:
        append_history(args.history, "fleet", report["meta"], rows)
        print(f"appended to {args.history}")


if __name__ == "__main__":
    main()
