"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Two execution modes:
  * ``--mode standard`` — plain synchronous training (train_step loop).
  * ``--mode ol4el``    — the paper's edge-cloud collaborative loop via
    the ``repro.el.ELSession`` façade: E simulated edges, per-block
    intervals chosen by the budget-limited MAB, local-SGD blocks +
    aggregation, budgets charged per the heterogeneous cost model.

Classic archs (``svm-wafer`` / ``kmeans-traffic``) under ``--mode
ol4el`` run the COMPILED single-run programs (``run_sync_ingraph`` /
``run_async_ingraph``).  ``--mesh debug|prod`` shards that single run's
``[n_edges, ...]`` data plane over a mesh (``debug``: a 2x2 forced
host-device mesh; ``prod``: ``repro.launch.mesh.make_production_mesh``,
which ``REPRO_DEBUG_MESH=d`` shrinks to ``d x d`` for CI) — bit-identical
to the unsharded run.  ``--donate`` donates the initial params' buffers
so aggregations update the fleet parameters in place.

On a real TPU cluster the same code runs under the production mesh; on
this CPU host ``--mesh`` emulates a small fleet via forced host devices
(``REPRO_SWEEP_DEVICES``, default 4) and LM archs run on the default
device with the smoke-scale configs.
"""

from __future__ import annotations

from repro.launch.hostdev import force_host_devices, use_compile_cache

force_host_devices()     # must precede the jax import (emulated fleet)

import argparse
import dataclasses
import time

import jax

from repro.config import get_config, get_smoke_config
from repro.data import SyntheticLMData
from repro.el import ELSession
from repro.federated import LMExecutor
from repro.models import build_model
from repro.obs.cli import (add_metrics_args, begin_observability,
                           finish_observability, telemetry_arg)
from repro.train import (checkpoint, init_train_state, make_train_step)


def train_standard(exp, args) -> None:
    n_steps = args.steps if args.steps is not None else 50
    model = build_model(exp.model)
    state = init_train_state(model, exp.train, jax.random.key(exp.train.seed))
    data = SyntheticLMData.for_model(exp.model, args.batch, args.seq)
    step = jax.jit(make_train_step(model, exp.train))
    for i in range(n_steps):
        t0 = time.time()
        state, metrics = step(state, data.batch(0, i))
        if i % args.log_every == 0 or i == n_steps - 1:
            print(f"step {i:5d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f} "
                  f"dt={time.time() - t0:.2f}s", flush=True)
    if args.ckpt:
        checkpoint.save(args.ckpt, state, step=n_steps)
        print(f"saved checkpoint to {args.ckpt}")
    return None


def _build_mesh(args):
    import os
    if args.mesh == "none":
        return None
    from repro.launch.mesh import make_debug_mesh_for, make_production_mesh
    if args.mesh == "debug":
        n_dev = jax.device_count()
        if n_dev < 2:
            # the forced-host-device preamble scans sys.argv, so a
            # programmatic main(argv=[..., "--mesh", "debug"]) call
            # misses it; a 1x1 "mesh" would be an unsharded run
            raise SystemExit(
                f"--mesh debug needs >= 2 devices, {n_dev} visible "
                "(forced host devices are set from sys.argv before jax "
                "init — invoke via the CLI, or set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N yourself)")
        return make_debug_mesh_for(n_dev)
    if not os.environ.get("REPRO_DEBUG_MESH") and jax.device_count() < 256:
        raise SystemExit(
            "--mesh prod needs the production fleet (a 16x16 = 256-chip "
            "pod); on a CPU host set REPRO_DEBUG_MESH=2 (with "
            "REPRO_SWEEP_DEVICES=4) for the debug-scale 2x2 production "
            "mesh, or use --mesh debug")
    return make_production_mesh()


def classic_session(args) -> ELSession:
    """The session ``--mode ol4el`` builds for a classic arch: the
    ``classic_fixture`` data plane under the CLI's config (exposed so a
    caller can replay the same run on another path, e.g. the host
    reference ``run_async(rng_streams="jax")``)."""
    from repro.el.scenarios.cli import scenario_from_args
    from repro.launch.classic import classic_fixture

    fx = classic_fixture(args.arch, samples=args.samples,
                         n_edges=args.edges, alpha=args.alpha,
                         kmeans_impl=args.kmeans_impl)
    metric = fx["metric"]
    scenario, base_cost_model = scenario_from_args(args)
    ol = dataclasses.replace(fx["exp"].ol4el, n_edges=args.edges,
                             heterogeneity=args.heterogeneity,
                             budget=args.budget, mode=args.el_mode,
                             async_alpha=args.async_alpha,
                             async_batch_k=args.async_batch_k,
                             policy="ol4el", utility=fx["utility"],
                             cost_model=base_cost_model,
                             scenario=scenario)
    return (ELSession(ol, metric_name=metric, lr=fx["lr"])
            .with_executor(fx["executor"], init_params=fx["init_params"],
                           n_samples=fx["n_samples"]))


def train_classic_ol4el(exp, args):
    """Classic archs through the compiled single-run EL programs —
    optionally mesh-sharded (``--mesh``), buffer-donating
    (``--donate``) and scenario-injected (``--churn``/``--cost-model``/
    ``--drift``, see ``repro.el.scenarios``)."""
    session = classic_session(args)
    ol, metric = session.cfg, session.metric_name
    mesh = _build_mesh(args)
    desc = (f"compiled {ol.mode} run, {args.edges} edges"
            + (f", mesh {tuple(mesh.shape.items())}" if mesh else "")
            + (", donated params" if args.donate else ""))
    print(f"ol4el {args.arch}: {desc}", flush=True)
    if ol.mode == "sync":
        report = session.run_sync_ingraph(
            max_rounds=args.steps if args.steps is not None else 256,
            mesh=mesh, donate=args.donate, telemetry=args.telemetry)
    else:
        # same announced-cap contract as train_ol4el: an explicit
        # --steps bounds the run at steps*edges events, never silently
        if args.steps is not None:
            print(f"async: --steps caps the run at "
                  f"{args.steps * args.edges} events (omit --steps to "
                  "run to budget exhaustion)", flush=True)
        report = session.run_async_ingraph(
            max_events=None if args.steps is None
            else args.steps * args.edges,
            mesh=mesh, donate=args.donate, telemetry=args.telemetry)
    print(f"done: {report.n_aggregations} aggregations, "
          f"final {metric} {report.final_metric:.4f}, "
          f"consumed {report.total_consumed:.0f} "
          f"({report.terminated_reason}); arm pulls {report.arm_pulls}")
    cache = (report.telemetry or {}).get("cache")
    if cache:
        print(f"compile cache: {cache['entries']} programs "
              f"({cache['hits']} hits, {cache['misses']} misses, "
              f"{cache['evictions']} evictions)", flush=True)
    if args.ckpt:
        checkpoint.save(args.ckpt, report.final_params,
                        step=report.n_aggregations)
        print(f"saved EL checkpoint to {args.ckpt}")
    return report


def train_ol4el(exp, args) -> None:
    model = build_model(exp.model)
    ol = dataclasses.replace(exp.ol4el, n_edges=args.edges,
                             heterogeneity=args.heterogeneity,
                             budget=args.budget, mode=args.el_mode,
                             async_alpha=args.async_alpha,
                             utility="loss_delta")
    ex = LMExecutor(model, exp.model, exp.train, batch=args.batch,
                    seq_len=args.seq, seed=exp.train.seed)

    def progress(rec):
        if rec.n_aggregations % args.log_every == 0:
            print(f"agg {rec.n_aggregations:4d} loss={rec.metric:.4f} "
                  f"interval={rec.interval:.0f} edge={rec.edge} "
                  f"consumed={rec.total_consumed:.0f}/"
                  f"{args.edges * args.budget:.0f}", flush=True)

    session = (ELSession(ol, metric_name="loss", lr=exp.train.peak_lr)
               .with_executor(ex)
               .on_round(progress))
    if ol.mode == "sync":
        report = session.run_sync(
            max_rounds=args.steps if args.steps is not None else 50)
    else:
        # without an explicit --steps the event horizon is derived from
        # budget/cost (repro.el.events.default_event_horizon): async
        # runs terminate on budget exhaustion — the old steps-based
        # default silently truncated long runs.  An explicit --steps
        # still caps the run (steps * edges events).
        if args.steps is not None:
            print(f"async: --steps caps the run at "
                  f"{args.steps * args.edges} events (omit --steps to "
                  "run to budget exhaustion)", flush=True)
        report = session.run_async(
            max_events=None if args.steps is None
            else args.steps * args.edges)
    print(f"done: {report.n_aggregations} aggregations, "
          f"final loss {report.final_metric:.4f}, "
          f"consumed {report.total_consumed:.0f} "
          f"({report.terminated_reason}); arm pulls {report.arm_pulls}")
    if args.ckpt:
        checkpoint.save(args.ckpt, report.final_params,
                        step=report.n_aggregations)
        print(f"saved EL checkpoint to {args.ckpt}")
    return report


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--mode", default="standard",
                    choices=["standard", "ol4el"])
    ap.add_argument("--el-mode", default="async", choices=["sync", "async"])
    ap.add_argument("--async-alpha", type=float, default=0.5,
                    help="async staleness-mix base rate (cfg.async_alpha)")
    ap.add_argument("--async-batch-k", type=int, default=0,
                    help="async K-event wave width (cfg.async_batch_k; "
                         "0 = auto: 1 replicated, mesh-tuned sharded)")
    ap.add_argument("--steps", type=int, default=None,
                    help="standard/sync: training steps/rounds (default "
                         "50); async: optional event cap of steps*edges "
                         "— omitted, the run goes to budget exhaustion")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--edges", type=int, default=4)
    ap.add_argument("--heterogeneity", type=float, default=4.0)
    ap.add_argument("--budget", type=float, default=1e5)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "debug", "prod"],
                    help="shard a classic-arch single EL run: 'debug' "
                         "builds a mesh over the forced host devices "
                         "(REPRO_SWEEP_DEVICES, default 4); 'prod' uses "
                         "repro.launch.mesh.make_production_mesh "
                         "(REPRO_DEBUG_MESH=d shrinks it to d x d)")
    ap.add_argument("--donate", action="store_true",
                    help="donate the initial params' buffers to the "
                         "compiled run (in-place fleet update; classic "
                         "ol4el only)")
    ap.add_argument("--samples", type=int, default=4000,
                    help="classic-arch dataset size (ol4el mode)")
    ap.add_argument("--alpha", type=float, default=100.0,
                    help="Dirichlet concentration of the classic edge "
                         "data split (matches repro.launch.sweep)")
    ap.add_argument("--kmeans-impl", default="jnp",
                    choices=["jnp", "pallas"],
                    help="K-means E-step engine for the local blocks "
                         "(pallas: the repro.kernels.kmeans_assign "
                         "kernel; native on TPU, interpret mode on the CPU)")
    from repro.el.scenarios.cli import add_scenario_args
    add_scenario_args(ap)
    add_metrics_args(ap, trace_dir=True)
    telemetry_arg(ap)
    args = ap.parse_args(argv)
    family = get_config(args.arch).model.family
    scenario_flags = (args.churn is not None or args.drift is not None
                      or args.cost_model not in ("fixed", "variable"))
    if (not (args.mode == "ol4el" and family == "classic")
            and (args.mesh != "none" or args.donate
                 or args.telemetry is not None or scenario_flags)):
        ap.error("--mesh/--donate/--telemetry/--churn/--drift and the "
                 "scenario --cost-model kinds drive the compiled "
                 "single-run programs, which need a classic arch under "
                 "--mode ol4el (LM archs and --mode standard run the "
                 "host loops)")
    return args


def main(argv=None):
    """Run the launcher; returns the run's ``ELReport`` (``None`` for
    ``--mode standard``)."""
    args = parse_args(argv)
    use_compile_cache()
    exp = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    classic_el = args.mode == "ol4el" and exp.model.family == "classic"
    begin_observability(args)
    if args.mode == "standard":
        report = train_standard(exp, args)
    elif classic_el:
        report = train_classic_ol4el(exp, args)
    else:
        report = train_ol4el(exp, args)
    registry = None
    if args.metrics_out and report is not None:
        from repro.obs import registry_from_report
        registry = registry_from_report(
            report, labels={"arch": args.arch, "mode": report.mode})
    finish_observability(args, registry)
    return report


if __name__ == "__main__":
    main()
