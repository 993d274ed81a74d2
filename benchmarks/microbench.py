"""Microbenchmarks: control-plane + kernel-path costs on this host.

Emitted in the harness CSV contract (name,us_per_call,derived).  Kernel
numbers are interpret-mode (CPU) — correctness-path costs, NOT TPU perf;
TPU performance is modeled by the roofline analysis instead.
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# the shared wall-clock helper (repro.obs.timing) — this module's old
# private ``_time`` copy, now one implementation for every bench
from repro.obs.timing import timeit_us as _time


def run(quiet: bool = False) -> List[Dict]:
    """All rows run in this process.  The mesh-sharded / donated
    single-run tiers and the fleet serving row are
    ``scripts/bench_el.py`` and ``scripts/bench_fleet.py``: run those
    directly (a child started from here could not get a device this
    process already holds)."""
    rows = []

    # bandit decision latency (cloud control plane)
    from repro.core.bandit import BanditState, arm_costs, select_arm
    st = BanditState.create(10)
    costs = arm_costs(10, 10.0, 50.0)
    rng = np.random.default_rng(0)
    for i in range(10):
        st.update(i, 0.5, costs[i])
    rows.append(dict(name="bandit_select_arm",
                     us_per_call=_time(lambda: select_arm(st, 1e4, costs,
                                                          "ol4el", rng)),
                     derived="decisions/s"))

    # weighted average aggregation (1M params, 4 edges)
    from repro.federated import weighted_average
    trees = [{"w": jnp.ones((1024, 256))} for _ in range(4)]
    agg = jax.jit(lambda ts: weighted_average(ts, [1.0] * 4))
    agg(trees)[0].block_until_ready() if isinstance(agg(trees), tuple) else None
    rows.append(dict(name="aggregate_1M_params_4edges",
                     us_per_call=_time(
                         lambda: jax.block_until_ready(agg(trees)), n=20),
                     derived="params_avg"))

    # XLA blocked attention step (the dry-run fallback path), small shape
    from repro.models import layers as L
    from repro.config import ModelConfig
    cfg = ModelConfig(d_model=256, n_heads=4, n_kv_heads=4, dtype="float32")
    p = L.init_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 512, 256))
    pos = jnp.arange(512)
    att = jax.jit(lambda x: L.attention(p, cfg, x, pos, impl="blocked"))
    jax.block_until_ready(att(x))
    rows.append(dict(name="xla_blocked_attention_b1_s512_d256",
                     us_per_call=_time(lambda: jax.block_until_ready(att(x)),
                                       n=10),
                     derived="fwd"))

    # K-means E-step: Pallas interpret vs jnp ref (correctness path cost)
    from repro.kernels.kmeans_assign.ops import assign_with_dist
    from repro.kernels.kmeans_assign.ref import assign_ref
    xk = jax.random.normal(jax.random.key(2), (4096, 64))
    ck = jax.random.normal(jax.random.key(3), (3, 64))
    ref_j = jax.jit(lambda x, c: assign_ref(x, c))
    jax.block_until_ready(ref_j(xk, ck))
    rows.append(dict(name="kmeans_assign_ref_n4096_d64_k3",
                     us_per_call=_time(
                         lambda: jax.block_until_ready(ref_j(xk, ck)), n=20),
                     derived="Estep"))

    # simulator round throughput (SVM, 3 edges)
    from benchmarks.common import run_el
    t0 = time.perf_counter()
    r = run_el("svm", "ol4el", "async", 6.0, budget=1500.0, n_data=2000)
    dt = (time.perf_counter() - t0) * 1e6
    rows.append(dict(name="el_sim_svm_async_per_aggregation",
                     us_per_call=dt / max(r.n_aggregations, 1),
                     derived=f"acc={r.final_metric:.3f}"))

    # host-driven sync loop vs the fully in-graph fast path (ONE compiled
    # lax.while_loop per run): per-aggregation cost, warm in both cases
    import dataclasses as _dc
    from repro.config import get_config as _get_config
    from repro.data import make_wafer_dataset, partition_edges
    from repro.el import ELSession
    from repro.federated import ClassicExecutor
    from repro.models import build_model
    train_d, test_d = make_wafer_dataset(n=2000, seed=0)
    exp = _get_config("svm-wafer")
    svm = build_model(exp.model)
    ol = _dc.replace(exp.ol4el, mode="sync", policy="ol4el", n_edges=3,
                     budget=6000.0, heterogeneity=6.0, utility="eval_gain",
                     seed=0)
    edges = partition_edges(train_d, 3, alpha=1.0, seed=0)
    ex = ClassicExecutor(svm, edges, test_d, batch=64, lr=0.05)
    ns = [len(e["y"]) for e in edges]

    def session():
        return ELSession(ol, metric_name="accuracy", lr=0.05) \
            .with_executor(ex, n_samples=ns)

    session().run_sync()                        # warm the executor jits
    t0 = time.perf_counter()
    host = session().run_sync()
    host_us = (time.perf_counter() - t0) * 1e6 / max(host.n_aggregations, 1)
    rows.append(dict(name="el_sync_host_per_round", us_per_call=host_us,
                     derived=f"acc={host.final_metric:.3f}"))

    sess = session()
    sess.run_sync_ingraph()                     # compile the program
    t0 = time.perf_counter()
    ing = sess.run_sync_ingraph()
    ing_us = (time.perf_counter() - t0) * 1e6 / max(ing.n_aggregations, 1)
    rows.append(dict(
        name="el_sync_ingraph_per_round", us_per_call=ing_us,
        derived=f"acc={ing.final_metric:.3f},"
                f"speedup={host_us / max(ing_us, 1e-9):.1f}x_vs_host"))

    # in-graph telemetry rings (repro.obs): per-round cost of the
    # instrumented sync program vs the bare one — both warm, min-of-3
    # (the acceptance bound is <10% overhead per round)
    from repro.obs.timing import repeat_s
    sess.run_sync_ingraph(telemetry=64)         # compile instrumented
    off_us = min(repeat_s(sess.run_sync_ingraph, 3)) * 1e6 \
        / max(ing.n_aggregations, 1)
    on = sess.run_sync_ingraph(telemetry=64)
    on_us = min(repeat_s(lambda: sess.run_sync_ingraph(telemetry=64),
                         3)) * 1e6 / max(on.n_aggregations, 1)
    rows.append(dict(
        name="el_telemetry_overhead_per_round",
        us_per_call=max(on_us - off_us, 0.0),
        derived=f"on={on_us:.0f}us,off={off_us:.0f}us,overhead="
                f"{(on_us - off_us) / max(off_us, 1e-9) * 100:.1f}pct"))

    # host-driven async event queue vs the fully in-graph event-horizon
    # program (repro.el.events: argmin finish-times + masked merges, no
    # host priority queue): per-event cost, warm in both cases
    ol_async = _dc.replace(ol, mode="async")

    def async_session():
        return ELSession(ol_async, metric_name="accuracy", lr=0.05) \
            .with_executor(ex, n_samples=ns)

    async_session().run_async()                 # warm the executor jits
    t0 = time.perf_counter()
    ahost = async_session().run_async()
    ahost_us = (time.perf_counter() - t0) * 1e6 / max(ahost.n_aggregations,
                                                      1)
    rows.append(dict(name="el_async_host_per_event", us_per_call=ahost_us,
                     derived=f"acc={ahost.final_metric:.3f}"))

    asess = async_session()
    asess.run_async_ingraph()                   # compile the program
    t0 = time.perf_counter()
    aing = asess.run_async_ingraph()
    aing_us = (time.perf_counter() - t0) * 1e6 / max(aing.n_aggregations, 1)
    rows.append(dict(
        name="el_async_ingraph_per_event", us_per_call=aing_us,
        derived=f"acc={aing.final_metric:.3f},"
                f"speedup={ahost_us / max(aing_us, 1e-9):.1f}x_vs_host"))

    # ablation sweep: 4 (ucb_c × seed) cells as ONE vmapped compiled
    # program vs the sequential host-loop equivalent (the pre-sweep way
    # benchmarks ran grids); per-grid wall-clock, warm in both cases
    from repro.el.sweep import SweepSpec
    spec = SweepSpec(ucb_c=(1.0, 2.0), budget=(3000.0,), seeds=(0, 1),
                     max_rounds=128)
    t0 = time.perf_counter()
    for ccfg in spec.cell_cfgs(ol):
        ELSession(ccfg, metric_name="accuracy", lr=0.05) \
            .with_executor(ex, n_samples=ns).run_sync()
    seq_host_us = (time.perf_counter() - t0) * 1e6
    sw = session()
    sw.sweep(spec)                              # compile the sweep
    t0 = time.perf_counter()
    rep_sw = sw.sweep(spec)
    sweep_us = (time.perf_counter() - t0) * 1e6
    rows.append(dict(
        name="el_sweep_vmapped_4cells", us_per_call=sweep_us,
        derived=f"acc={float(np.nanmean(rep_sw.final_metrics())):.3f},"
                f"speedup={seq_host_us / max(sweep_us, 1e-9):.1f}"
                "x_vs_seq_host"))

    if not quiet:
        for row in rows:
            print(f"micro {row['name']:40s} {row['us_per_call']:12.1f} us  "
                  f"{row['derived']}", flush=True)
    return rows


if __name__ == "__main__":
    run()
