"""The repro.el.sweep subsystem: spec flattening, vmapped-cell
bit-equivalence with independent in-graph runs, variable-cost in-graph
semantics, report reductions, and mesh placement policy."""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.config import OL4ELConfig, get_config
from repro.data import (make_traffic_dataset, make_wafer_dataset,
                        partition_edges)
from repro.el import ELSession, SweepReport, SweepSpec
from repro.el.sweep import sweep_partition_specs
from repro.el.sweep.spec import AXIS_ORDER
from repro.federated import ClassicExecutor
from repro.models import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _svm_fixture(n=800, n_edges=3, seed=0, budget=900.0, **cfg_kw):
    train, test = make_wafer_dataset(n=n, seed=seed)
    exp = get_config("svm-wafer")
    model = build_model(exp.model)
    ol = dataclasses.replace(
        exp.ol4el, mode="sync", policy="ol4el", n_edges=n_edges,
        budget=budget, heterogeneity=4.0, utility="eval_gain", seed=seed,
        **cfg_kw)
    edges = partition_edges(train, n_edges, alpha=1.0, seed=seed)
    ex = ClassicExecutor(model, edges, test, batch=32, lr=0.05)
    init = model.init(jax.random.key(seed))
    ns = [len(e["y"]) for e in edges]
    return ol, ex, init, ns


def _session(ol, ex, init, ns) -> ELSession:
    return (ELSession(ol, metric_name="accuracy", lr=0.05)
            .with_executor(ex, init_params=init, n_samples=ns))


# ---------------------------------------------------------------------------
# SweepSpec flattening
# ---------------------------------------------------------------------------


def test_spec_defaults_inherit_cfg_and_seed_varies_fastest():
    cfg = OL4ELConfig(ucb_c=1.5, budget=777.0, heterogeneity=3.0)
    spec = SweepSpec(ucb_c=(1.0, 2.0), seeds=(0, 7))
    assert spec.n_cells == 4
    cells = spec.cells(cfg)
    # row-major, seed fastest
    assert [c["seed"] for c in cells] == [0, 7, 0, 7]
    assert [c["ucb_c"] for c in cells] == [1.0, 1.0, 2.0, 2.0]
    # empty axes default from the config
    assert all(c["budget"] == 777.0 for c in cells)
    assert all(c["heterogeneity"] == 3.0 for c in cells)
    ccfgs = spec.cell_cfgs(cfg)
    assert [c.seed for c in ccfgs] == [0, 7, 0, 7]
    # the session config's mode carries into every cell (it picks the
    # sync round vs the async event-horizon program for the whole grid)
    assert all(c.mode == cfg.mode for c in ccfgs)
    assert all(c.async_alpha == cfg.async_alpha for c in ccfgs)
    assert tuple(spec.axes(cfg)) == AXIS_ORDER


def test_spec_validation():
    with pytest.raises(ValueError, match="seed"):
        SweepSpec(seeds=())
    with pytest.raises(ValueError, match="max_rounds"):
        SweepSpec(max_rounds=0)
    with pytest.raises(ValueError, match="budget"):
        SweepSpec(budget=(0.0,))
    with pytest.raises(ValueError, match="heterogeneity"):
        SweepSpec(heterogeneity=(0.5,))
    # sequences coerce to tuples (hashable -> usable as a cache key)
    spec = SweepSpec(ucb_c=[1.0, 2.0], seeds=[0])
    assert spec.ucb_c == (1.0, 2.0) and hash(spec)


# ---------------------------------------------------------------------------
# THE acceptance property: a [k]-cell vmapped sweep is bit-identical per
# cell to k independent run_sync_ingraph runs with the same seeds (the
# bandit RNG call order is load-bearing)
# ---------------------------------------------------------------------------


def test_sweep_cells_bit_identical_to_independent_ingraph_runs():
    ol, ex, init, ns = _svm_fixture()
    # 2 policy-hyperparams × 2 budgets × 2 seeds, ONE compiled program
    spec = SweepSpec(ucb_c=(1.0, 2.0), budget=(900.0, 1300.0),
                     seeds=(0, 3), max_rounds=64)
    sess = _session(ol, ex, init, ns)
    rep = sess.sweep(spec)
    # single jit trace for the whole grid
    assert sess._sweep_program._cache_size() == 1
    assert rep.n_cells == 8

    for i, ccfg in enumerate(spec.cell_cfgs(ol)):
        ind = _session(ccfg, ex, init, ns).run_sync_ingraph(max_rounds=64)
        n = int(rep.out["n_rounds"][i])
        assert n == ind.n_aggregations > 0
        # float32 -> float64 casts are exact, so == is bit-identity
        assert np.array_equal(
            rep.out["metric"][i][:n].astype(np.float64),
            np.array([r.metric for r in ind.records]))
        assert np.array_equal(
            rep.out["interval"][i][:n].astype(np.float64),
            np.array([r.interval for r in ind.records]))
        assert np.array_equal(
            rep.out["consumed"][i][:n].astype(np.float64),
            np.array([r.total_consumed for r in ind.records]))
        assert np.array_equal(np.asarray(rep.out["arm_pulls"][i]),
                              np.asarray(ind.arm_pulls))
        assert float(rep.out["wall_time"][i]) == ind.wall_time


def test_sweep_reruns_reuse_the_compiled_program():
    ol, ex, init, ns = _svm_fixture(n=400)
    spec = SweepSpec(ucb_c=(1.0, 2.0), seeds=(0,), max_rounds=32)
    sess = _session(ol, ex, init, ns)
    r1 = sess.sweep(spec)
    prog = sess._sweep_program
    r2 = sess.sweep(spec)
    assert sess._sweep_program is prog
    assert prog._cache_size() == 1
    assert np.array_equal(r1.out["metric"], r2.out["metric"],
                          equal_nan=True)


def test_sweep_rejects_unsupported_combinations():
    ol, ex, init, ns = _svm_fixture(n=400)
    bad = dataclasses.replace(ol, policy="greedy")
    with pytest.raises(ValueError, match="policy='greedy'"):
        _session(bad, ex, init, ns).sweep(SweepSpec(seeds=(0,)))

    class NotInGraph:
        def local_train(self, params, edge, n_iters, seed):
            return params, {}

        def evaluate(self, params):
            return {"accuracy": 0.0}

    s = ELSession(OL4ELConfig(mode="sync")).with_executor(
        NotInGraph(), init_params={})
    with pytest.raises(TypeError, match="in-graph"):
        s.sweep(SweepSpec(seeds=(0,)))


# ---------------------------------------------------------------------------
# variable-cost in-graph mode (ROADMAP item)
# ---------------------------------------------------------------------------


def test_variable_cost_noise_zero_is_bitwise_fixed():
    """cost_model='variable' with zero noise must reproduce the fixed-cost
    program bit-for-bit (the noise key is drawn OUTSIDE the per-edge
    fold range, so the other RNG streams are untouched)."""
    ol, ex, init, ns = _svm_fixture()
    fixed = _session(ol, ex, init, ns).run_sync_ingraph(max_rounds=64)
    var0 = _session(
        dataclasses.replace(ol, cost_model="variable", cost_noise=0.0),
        ex, init, ns).run_sync_ingraph(max_rounds=64)
    assert fixed.n_aggregations == var0.n_aggregations
    assert [r.metric for r in fixed.records] == \
        [r.metric for r in var0.records]
    assert [r.total_consumed for r in fixed.records] == \
        [r.total_consumed for r in var0.records]
    assert fixed.arm_pulls == var0.arm_pulls


def test_variable_cost_ingraph_matches_host_charged_cost_semantics():
    """The compiled variable-cost path must charge like the host path:
    every edge pays the straggler slot max_e(expected_e · mult_e) with
    mult_e = max(0.1, 1 + noise·N(0,1)), so each round's charge is at
    least 10% of the binding edge's expected cost, and totals agree with
    the host loop statistically (the RNG streams differ)."""
    from repro.el.ingraph import sync_knobs
    ol, ex, init, ns = _svm_fixture(n=1200, cost_model="variable",
                                    cost_noise=0.3, budget=1500.0)
    ing = _session(ol, ex, init, ns).run_sync_ingraph(max_rounds=64)
    host = _session(ol, ex, init, ns).run_sync()
    assert ing.terminated_reason == host.terminated_reason == \
        "budget_exhausted"
    knobs = sync_knobs(ol)
    comp_worst = float(knobs["comp"].max())
    comm = float(ol.comm_cost)
    prev = 0.0
    for rec in ing.records:
        slot = (rec.total_consumed - prev) / ol.n_edges
        expected = rec.interval * comp_worst + comm
        assert slot >= 0.1 * expected - 1e-3
        prev = rec.total_consumed
    # same charged-cost model => totals in the same ballpark
    assert ing.total_consumed == pytest.approx(host.total_consumed,
                                               rel=0.35)


# ---------------------------------------------------------------------------
# SweepReport reductions
# ---------------------------------------------------------------------------


def _toy_report() -> SweepReport:
    """2 ucb_c × 2 seeds, hand-built round records (R=4)."""
    spec = SweepSpec(ucb_c=(1.0, 2.0), seeds=(0, 1), max_rounds=4)
    cfg = OL4ELConfig(budget=100.0, heterogeneity=1.0)
    nan = np.nan
    metric = np.array([
        [0.5, 0.6, 0.7, nan],       # cell 0: ucb 1.0 seed 0, 3 rounds
        [0.4, 0.6, nan, nan],       # cell 1: ucb 1.0 seed 1, 2 rounds
        [0.5, 0.8, 0.9, 0.9],       # cell 2: ucb 2.0 seed 0, 4 rounds
        [0.5, 0.7, 0.8, nan],       # cell 3: ucb 2.0 seed 1, 3 rounds
    ])
    consumed = np.cumsum(np.where(np.isnan(metric), 0.0, 60.0), axis=1)
    out = {
        "metric": metric,
        "consumed": consumed,
        "utility": np.zeros_like(metric),
        "interval": np.ones_like(metric, np.int32),
        "wall": consumed / 3.0,
        "n_rounds": np.array([3, 2, 4, 3]),
        "budgets_left": np.zeros((4, 3), np.float32),
        "arm_pulls": np.zeros((4, 10), np.int32),
        "wall_time": consumed[:, -1] / 3.0,
    }
    return SweepReport(spec=spec, axes=spec.axes(cfg),
                       cells=spec.cells(cfg), out=out)


def test_report_final_metrics_and_consumed_respect_termination():
    rep = _toy_report()
    assert np.allclose(rep.final_metrics(), [0.7, 0.6, 0.9, 0.8])
    assert np.allclose(rep.total_consumed(), [180.0, 120.0, 240.0, 180.0])


def test_report_learning_curves_mean_and_ci_over_seeds():
    rep = _toy_report()
    curves = rep.learning_curves()
    assert len(curves) == 2                       # one per ucb_c point
    c1 = next(c for c in curves if c["ucb_c"] == 1.0)
    assert c1["n_seeds"] == 2 and c1["rounds"] == 3
    assert np.allclose(c1["mean"], [0.45, 0.6, 0.7])
    # round 2: only seed 0 alive -> no CI; round 0: two seeds
    assert c1["ci95"][0] == pytest.approx(1.96 * 0.05 / np.sqrt(2))
    assert c1["ci95"][2] == 0.0


def test_report_pareto_frontier_is_nondominated_over_seed_means():
    rep = _toy_report()
    front = rep.pareto_frontier()
    # ucb 1.0: mean metric 0.65 @ 150; ucb 2.0: 0.85 @ 210 — both survive
    assert [p["ucb_c"] for p in front] == [1.0, 2.0]
    rows = rep.grouped_rows()
    assert {r["ucb_c"]: r["final_metric"] for r in rows} == \
        pytest.approx({1.0: 0.65, 2.0: 0.85})
    # a dominated point must be dropped
    rep.out["metric"][2:, :] = np.array([[0.3, 0.4, 0.5, 0.5],
                                         [0.3, 0.4, 0.5, np.nan]])
    front = rep.pareto_frontier()
    assert [p["ucb_c"] for p in front] == [1.0]


def test_report_learning_curves_survive_metricless_workloads():
    """With no jittable in-graph metric the metric history is all-NaN by
    design — the consumed curve must still reduce from n_rounds."""
    rep = _toy_report()
    rep.out["metric"] = np.full_like(rep.out["metric"], np.nan)
    curves = rep.learning_curves()
    c1 = next(c for c in curves if c["ucb_c"] == 1.0)
    assert np.isnan(c1["mean"]).all()
    assert np.isfinite(c1["consumed"]).all()
    assert c1["consumed"][0] == pytest.approx(60.0)


def test_report_to_rows_flat_contract():
    rows = _toy_report().to_rows()
    assert len(rows) == 4
    assert set(AXIS_ORDER) <= set(rows[0])
    assert rows[0]["n_rounds"] == 3
    assert rows[0]["final_metric"] == pytest.approx(0.7)


# ---------------------------------------------------------------------------
# kmeans (no jittable metric): host-side final-params scoring fallback
# ---------------------------------------------------------------------------


def _kmeans_sweep(impl="jnp"):
    train, test = make_traffic_dataset(n=600)
    exp = get_config("kmeans-traffic")
    model = build_model(exp.model, impl=impl)
    ol = dataclasses.replace(exp.ol4el, mode="sync", policy="ol4el",
                             n_edges=2, budget=500.0, heterogeneity=2.0,
                             utility="param_delta")
    edges = partition_edges(train, 2, alpha=2.0)
    ex = ClassicExecutor(model, edges, test, batch=128, lr=1.0)
    sess = (ELSession(ol, metric_name="f1", lr=1.0)
            .with_executor(ex, init_params=model.init(jax.random.key(1))))
    return ex, sess


def test_kmeans_sweep_scores_final_params_host_side():
    _, sess = _kmeans_sweep()
    rep = sess.sweep(SweepSpec(seeds=(0, 1), max_rounds=32))
    assert "final_metric_host" in rep.out
    finals = rep.final_metrics()
    assert finals.shape == (2,)
    assert np.isfinite(finals).all() and (finals > 0.3).all()


@pytest.mark.parametrize("impl", ("jnp", "pallas"))
def test_kmeans_batched_scores_equal_cell_by_cell_evaluate(impl):
    """The sweep scores its cells in one compiled call over the cell
    axis; each cell's F1 is the per-cell ``evaluate``'s bit for bit (the
    same E-step engine gives the same assignments, the same host
    matching the same F1) and its inertia agrees to float32 rounding."""
    ex, sess = _kmeans_sweep(impl)
    rep = sess.sweep(SweepSpec(heterogeneity=(1.0, 4.0), seeds=(0, 1, 2),
                               max_rounds=32))
    per_cell = [ex.evaluate(jax.tree.map(lambda x: x[i], rep.final_params))
                for i in range(rep.n_cells)]
    many = ex.evaluate_many(rep.final_params)
    f1 = np.array([m["f1"] for m in per_cell])
    assert len(set(f1)) > 1                  # the cells are told apart
    np.testing.assert_array_equal(many["f1"], f1)
    np.testing.assert_allclose(many["inertia"],
                               [m["inertia"] for m in per_cell], rtol=1e-6)
    np.testing.assert_array_equal(rep.out["final_metric_host"], f1)


# ---------------------------------------------------------------------------
# mesh placement policy (pure spec level) + sharded execution subprocess
# ---------------------------------------------------------------------------


def test_sweep_partition_specs_placement_and_divisibility():
    from jax.sharding import PartitionSpec as P
    key_spec, knobs = sweep_partition_specs(
        ("pod", "data", "model"), {"pod": 2, "data": 16, "model": 16},
        n_cells=64, n_edges=32)
    assert key_spec == P(("pod", "data"))
    assert knobs["comp"] == P(("pod", "data"), "model")        # [C, E]
    assert knobs["costs_k"] == P(("pod", "data"), None)        # [C, K]
    assert knobs["budget"] == P(("pod", "data"))               # [C]
    # edge dim replicates when it does not divide the model axis
    _, knobs = sweep_partition_specs(
        ("data", "model"), {"data": 4, "model": 16},
        n_cells=8, n_edges=3)
    assert knobs["comp"] == P(("data",), None)
    # grid must tile the sweep axes
    with pytest.raises(ValueError, match="does not tile"):
        sweep_partition_specs(("data", "model"), {"data": 4, "model": 2},
                              n_cells=6, n_edges=2)
    # a mesh without edge axes cannot host a sweep
    with pytest.raises(ValueError, match="edge axes"):
        sweep_partition_specs(("model",), {"model": 4},
                              n_cells=4, n_edges=2)


@pytest.mark.slow
def test_sweep_sharded_on_debug_mesh_subprocess(tmp_path):
    """The launch entry point runs the sweep sharded over a forced 2x2
    host-device mesh (sweep dim over 'data', knob edge dim over 'model')."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_SWEEP_DEVICES="4")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.sweep", "--arch", "svm-wafer",
         "--ucb-c", "1.0", "2.0", "--seeds", "0", "1", "--samples", "800",
         "--max-rounds", "32", "--edges", "2", "--mesh", "debug"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Pareto frontier" in r.stdout
    assert "4 cells" in r.stdout
