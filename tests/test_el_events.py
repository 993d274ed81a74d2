"""The repro.el.events subsystem: the compiled async event-horizon
program vs the host event queue (bit-for-bit on shared jax streams),
variable-cost semantics, horizon derivation, the async support matrix,
and async/cost-noise sweep axes vs independent in-graph runs."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.config import OL4ELConfig, get_config
from repro.data import (make_traffic_dataset, make_wafer_dataset,
                        partition_edges)
from repro.el import ELSession, SweepSpec
from repro.el.events import (ASYNC_KNOB_NAMES, async_knobs,
                             default_event_horizon)
from repro.federated import ClassicExecutor
from repro.models import build_model


def _svm_fixture(n=600, n_edges=3, seed=0, budget=700.0, mode="async",
                 utility="eval_gain", **cfg_kw):
    train, test = make_wafer_dataset(n=n, seed=seed)
    exp = get_config("svm-wafer")
    model = build_model(exp.model)
    ol = dataclasses.replace(
        exp.ol4el, mode=mode, policy="ol4el", n_edges=n_edges,
        budget=budget, heterogeneity=4.0, utility=utility, seed=seed,
        **cfg_kw)
    edges = partition_edges(train, n_edges, alpha=1.0, seed=seed)
    ex = ClassicExecutor(model, edges, test, batch=32, lr=0.05)
    init = model.init(jax.random.key(seed))
    return ol, ex, init


def _session(ol, ex, init) -> ELSession:
    return (ELSession(ol, metric_name="accuracy", lr=0.05)
            .with_executor(ex, init_params=init))


def _assert_bit_identical(ref, ing):
    """Event order, merge values (metric/utility), charged costs and
    bandit statistics must agree exactly (float64 casts of f32 values,
    so == is bit-identity)."""
    assert ref.n_aggregations == ing.n_aggregations > 0
    for t, (a, b) in enumerate(zip(ref.records, ing.records)):
        assert a.edge == b.edge, t
        assert a.interval == b.interval, t
        assert a.wall_time == b.wall_time, t
        assert a.total_consumed == b.total_consumed, t
        assert a.metric == b.metric or (
            np.isnan(a.metric) and np.isnan(b.metric)), t
        assert a.utility == b.utility, t
    assert ref.arm_pulls == ing.arm_pulls
    assert ref.terminated_reason == ing.terminated_reason
    assert ref.final_metric == ing.final_metric


# ---------------------------------------------------------------------------
# knobs + horizon
# ---------------------------------------------------------------------------


def test_async_knobs_shapes_and_noise_gating():
    cfg = OL4ELConfig(mode="async", n_edges=3, heterogeneity=4.0,
                      cost_noise=0.3)                 # cost_model=fixed
    knobs = async_knobs(cfg)
    assert set(knobs) == set(ASYNC_KNOB_NAMES)
    assert knobs["costs_ek"].shape == (3, cfg.max_interval)
    assert knobs["comp"].shape == (3,)
    # interval-1 cost of every edge == its min cost
    np.testing.assert_allclose(knobs["costs_ek"][:, 0],
                               knobs["min_edge_cost"])
    # noise only applies in variable-cost mode (host realized_cost rule)
    assert knobs["cost_noise"] == 0.0
    var = async_knobs(dataclasses.replace(cfg, cost_model="variable"))
    assert var["cost_noise"] == np.float32(0.3)
    assert knobs["async_alpha"] == np.float32(0.5)


def test_default_event_horizon_scales_with_budget_and_never_truncates():
    cfg = OL4ELConfig(mode="async", n_edges=2, budget=600.0,
                      comp_cost=10.0, comm_cost=50.0, heterogeneity=1.0)
    h = default_event_horizon(cfg)
    assert h == 2 * (int(600.0 // 60.0) + 1)
    assert default_event_horizon(
        dataclasses.replace(cfg, budget=6000.0)) > h
    # variable-cost blocks can realize at the 0.1 multiplier floor
    assert default_event_horizon(
        dataclasses.replace(cfg, cost_model="variable",
                            cost_noise=0.5)) >= 10 * (h - 2)
    # a real run under the derived horizon terminates on budget, not
    # on the horizon (no silent truncation)
    ol, ex, init = _svm_fixture()
    rep = _session(ol, ex, init).run_async_ingraph()
    assert rep.terminated_reason == "budget_exhausted"
    assert rep.n_aggregations < default_event_horizon(ol)


# ---------------------------------------------------------------------------
# THE acceptance property: the compiled event-horizon program is
# bit-identical to the host priority-queue loop on the same jax RNG
# streams in fixed-cost mode (event order, merge values, charged costs)
# ---------------------------------------------------------------------------


def test_async_ingraph_bit_identical_to_host_event_queue_fixed_cost():
    ol, ex, init = _svm_fixture()
    ref = _session(ol, ex, init).run_async(rng_streams="jax")
    ing = _session(ol, ex, init).run_async_ingraph()
    assert ref.terminated_reason == "budget_exhausted"
    # a real async trace: multiple edges complete blocks, out of lockstep
    assert len({r.edge for r in ref.records}) == ol.n_edges
    _assert_bit_identical(ref, ing)
    # and the total charge equals the simulated wall-clock per edge sum
    assert ing.total_consumed == pytest.approx(
        sum(r.total_consumed - p for r, p in
            zip(ing.records, [0.0] + [r.total_consumed
                                      for r in ing.records[:-1]])))


def test_async_ingraph_bit_identical_param_delta():
    ol, ex, init = _svm_fixture(utility="param_delta")
    ref = _session(ol, ex, init).run_async(rng_streams="jax")
    ing = _session(ol, ex, init).run_async_ingraph()
    _assert_bit_identical(ref, ing)


def test_async_ingraph_variable_cost_bit_identical_and_statistical():
    """Variable-cost mode shares the jax noise stream, so even the noisy
    paths agree bit-for-bit; vs the legacy numpy host loop the agreement
    is statistical (same charged-cost model, different streams)."""
    ol, ex, init = _svm_fixture(n=800, budget=900.0,
                                cost_model="variable", cost_noise=0.3)
    ref = _session(ol, ex, init).run_async(rng_streams="jax")
    ing = _session(ol, ex, init).run_async_ingraph()
    _assert_bit_identical(ref, ing)
    # every block's charge is at least 10% of its expected cost
    knobs = async_knobs(ol)
    prev = 0.0
    for rec in ing.records:
        charge = rec.total_consumed - prev
        expected = (rec.interval * knobs["comp"][rec.edge]
                    + knobs["comm"][rec.edge])
        assert charge >= 0.1 * expected - 1e-3
        prev = rec.total_consumed
    host = _session(ol, ex, init).run_async()
    assert host.terminated_reason == ing.terminated_reason == \
        "budget_exhausted"
    assert ing.total_consumed == pytest.approx(host.total_consumed,
                                               rel=0.35)
    assert ing.final_metric > 0.5 and host.final_metric > 0.5


def test_async_variable_noise_zero_is_bitwise_fixed():
    ol, ex, init = _svm_fixture()
    fixed = _session(ol, ex, init).run_async_ingraph()
    var0 = _session(
        dataclasses.replace(ol, cost_model="variable", cost_noise=0.0),
        ex, init).run_async_ingraph()
    _assert_bit_identical(fixed, var0)


# ---------------------------------------------------------------------------
# K-event waves: batched dispatch is an order-equivalent reformulation —
# every K > 1 program must reproduce the single-event (K=1) trajectory
# bit for bit (merge values, charged costs, arm pulls, event order)
# ---------------------------------------------------------------------------


def _assert_same_params(a, b):
    for pa, pb in zip(jax.tree.leaves(a.final_params),
                      jax.tree.leaves(b.final_params)):
        assert np.array_equal(np.asarray(pa), np.asarray(pb))


@pytest.mark.parametrize("batch_k", [2, 3])   # 3 == n_edges (full wave)
@pytest.mark.parametrize("cost_kw", [
    {},                                               # fixed cost
    {"cost_model": "variable", "cost_noise": 0.3},    # noisy charges
], ids=["fixed", "variable"])
def test_async_k_waves_bit_identical_to_single_event(batch_k, cost_kw):
    ol, ex, init = _svm_fixture(**cost_kw)
    base = _session(dataclasses.replace(ol, async_batch_k=1),
                    ex, init).run_async_ingraph()
    wave = _session(dataclasses.replace(ol, async_batch_k=batch_k),
                    ex, init).run_async_ingraph()
    assert base.terminated_reason == "budget_exhausted"
    _assert_bit_identical(base, wave)
    _assert_same_params(base, wave)


def test_async_k1_is_the_auto_default_replicated():
    """async_batch_k=0 (auto) resolves to 1 off-mesh: the default
    program IS the K=1 special case, byte for byte."""
    ol, ex, init = _svm_fixture()
    auto = _session(ol, ex, init).run_async_ingraph()     # batch_k=0
    k1 = _session(dataclasses.replace(ol, async_batch_k=1),
                  ex, init).run_async_ingraph()
    _assert_bit_identical(auto, k1)
    _assert_same_params(auto, k1)


def test_async_k_waves_same_tick_tie_break_matches_argmin_order():
    """Homogeneous fleet (heterogeneity=0): edges repeatedly finish at
    the SAME wall-clock tick.  The wave's within-gap ordering must
    reproduce argmin's lowest-index-first pops exactly — a strict-<
    gap predicate or an unstable top-k would reorder these events."""
    ol, ex, init = _svm_fixture(n_edges=4, seed=3, budget=400.0)
    ol = dataclasses.replace(ol, heterogeneity=0.0)
    base = _session(dataclasses.replace(ol, async_batch_k=1),
                    ex, init).run_async_ingraph()
    # the fixture really exercises ties: some consecutive events share
    # a wall-clock stamp
    walls = [r.wall_time for r in base.records]
    assert any(a == b for a, b in zip(walls, walls[1:]))
    for k in (2, 4):
        wave = _session(dataclasses.replace(ol, async_batch_k=k),
                        ex, init).run_async_ingraph()
        _assert_bit_identical(base, wave)
        _assert_same_params(base, wave)


def test_resolve_async_batch_k_explicit_and_auto():
    from repro.el.events import resolve_async_batch_k
    cfg = OL4ELConfig(mode="async", n_edges=3, heterogeneity=4.0)
    # auto: replicated (no mesh) stays single-event
    assert resolve_async_batch_k(cfg, mesh=None) == 1
    # explicit K clamps to the fleet size
    assert resolve_async_batch_k(
        dataclasses.replace(cfg, async_batch_k=2)) == 2
    assert resolve_async_batch_k(
        dataclasses.replace(cfg, async_batch_k=64)) == 3


def test_async_kmeans_param_delta_host_scoring():
    """No jittable F1 metric: the program runs with NaN metric history
    and the report scores final params host-side; still bit-identical
    to the reference queue."""
    train, test = make_traffic_dataset(n=600)
    exp = get_config("kmeans-traffic")
    model = build_model(exp.model)
    ol = dataclasses.replace(exp.ol4el, mode="async", policy="ol4el",
                             n_edges=2, budget=500.0, heterogeneity=2.0,
                             utility="param_delta")
    edges = partition_edges(train, 2, alpha=2.0)
    ex = ClassicExecutor(model, edges, test, batch=128, lr=1.0)
    init = model.init(jax.random.key(1))

    def sess():
        return (ELSession(ol, metric_name="f1", lr=1.0)
                .with_executor(ex, init_params=init))

    ref = sess().run_async(rng_streams="jax")
    ing = sess().run_async_ingraph()
    _assert_bit_identical(ref, ing)
    assert ing.final_metric > 0.5
    assert all(np.isnan(r.metric) for r in ing.records)


# ---------------------------------------------------------------------------
# support matrix + session plumbing
# ---------------------------------------------------------------------------


def test_async_ingraph_rejects_unsupported_combinations():
    ol, ex, init = _svm_fixture()
    with pytest.raises(ValueError, match="policy='greedy'"):
        _session(dataclasses.replace(ol, policy="greedy"), ex,
                 init).run_async_ingraph()

    class NotInGraph:
        def local_train(self, params, edge, n_iters, seed):
            return params, {}

        def evaluate(self, params):
            return {"accuracy": 0.0}

    s = ELSession(OL4ELConfig(mode="async")).with_executor(
        NotInGraph(), init_params={})
    with pytest.raises(TypeError, match="in-graph"):
        s.run_async_ingraph()
    with pytest.raises(ValueError, match="rng_streams"):
        _session(ol, ex, init).run_async(rng_streams="bogus")


def test_policies_registry_records_ingraph_modes():
    from repro.el import policies
    assert policies.ingraph_modes("ol4el") == ("sync", "async")
    assert policies.ingraph_modes("greedy") == ()
    assert policies.ingraph_modes("nope") == ()


def test_async_ingraph_program_reused_across_knob_changes():
    """ucb_c/budget/heterogeneity/cost_noise/async_alpha/seed are traced
    inputs — changing them must NOT rebuild or retrace the program."""
    ol, ex, init = _svm_fixture()
    s = _session(ol, ex, init)
    r1 = s.run_async_ingraph(max_events=64)
    prog = s._async_fastpath
    s.cfg = dataclasses.replace(s.cfg, ucb_c=0.5, budget=900.0, seed=5,
                                async_alpha=0.3)
    r2 = s.run_async_ingraph(max_events=64)
    assert s._async_fastpath is prog
    assert prog._cache_size() == 1
    assert r2.n_aggregations > 0
    assert r2.total_consumed != r1.total_consumed


def test_session_sync_cfg_coerced_for_async_ingraph():
    ol, ex, init = _svm_fixture()
    rep = _session(dataclasses.replace(ol, mode="sync"), ex,
                   init).run_async_ingraph(max_events=32)
    assert rep.mode == "async"
    assert rep.n_aggregations > 0
    # per-event records carry the event edge
    assert {r.edge for r in rep.records} <= set(range(ol.n_edges))


# ---------------------------------------------------------------------------
# async sweeps: per-cell == independent run_async_ingraph (incl. the
# async_alpha axis), mirroring test_el_sweep.py's sync acceptance
# ---------------------------------------------------------------------------


def test_async_sweep_cells_bit_identical_to_independent_runs():
    ol, ex, init = _svm_fixture()
    spec = SweepSpec(async_alpha=(0.3, 0.6), seeds=(0, 3), max_rounds=48)
    sess = _session(ol, ex, init)
    rep = sess.sweep(spec)
    assert sess._sweep_program._cache_size() == 1
    assert rep.n_cells == 4
    for i, ccfg in enumerate(spec.cell_cfgs(ol)):
        assert ccfg.mode == "async"
        ind = _session(ccfg, ex, init).run_async_ingraph(max_events=48)
        n = int(rep.out["n_rounds"][i])
        assert n == ind.n_aggregations > 0
        assert np.array_equal(
            rep.out["metric"][i][:n].astype(np.float64),
            np.array([r.metric for r in ind.records]))
        assert np.array_equal(rep.out["edge"][i][:n],
                              np.array([r.edge for r in ind.records]))
        assert np.array_equal(
            rep.out["interval"][i][:n].astype(np.float64),
            np.array([r.interval for r in ind.records]))
        assert np.array_equal(
            rep.out["consumed"][i][:n].astype(np.float64),
            np.array([r.total_consumed for r in ind.records]))
        assert np.array_equal(
            np.asarray(rep.out["arm_pulls"][i]).sum(axis=0),
            np.asarray(ind.arm_pulls))
        assert float(rep.out["wall_time"][i]) == ind.wall_time


def test_sync_sweep_cost_noise_axis_matches_independent_runs():
    """The promoted cost_noise axis (ROADMAP item): a fixed+variable
    grid runs as one compiled program, each cell bit-identical to an
    independent run_sync_ingraph with that cell's config."""
    ol, ex, init = _svm_fixture(mode="sync")
    spec = SweepSpec(cost_noise=(0.0, 0.3), seeds=(0, 1), max_rounds=48)
    rep = _session(ol, ex, init).sweep(spec)
    assert rep.n_cells == 4
    for i, ccfg in enumerate(spec.cell_cfgs(ol)):
        assert ccfg.cost_model == ("variable" if ccfg.cost_noise > 0
                                   else "fixed")
        ind = _session(ccfg, ex, init).run_sync_ingraph(max_rounds=48)
        n = int(rep.out["n_rounds"][i])
        assert n == ind.n_aggregations > 0
        assert np.array_equal(
            rep.out["metric"][i][:n].astype(np.float64),
            np.array([r.metric for r in ind.records]))
        assert np.array_equal(
            rep.out["consumed"][i][:n].astype(np.float64),
            np.array([r.total_consumed for r in ind.records]))


def test_sweep_inherited_dormant_noise_stays_dormant():
    """A fixed-cost session with a dormant cfg.cost_noise must sweep
    exactly like its single runs: only an EXPLICIT cost_noise axis flips
    cells to cost_model='variable' (review regression)."""
    cfg = OL4ELConfig(mode="sync", cost_model="fixed", cost_noise=0.3)
    cells = SweepSpec(ucb_c=(1.0, 2.0)).cell_cfgs(cfg)
    assert all(c.cost_model == "fixed" for c in cells)
    # the knob derivation then keeps the noise gated off
    from repro.el.ingraph import sync_knobs
    assert all(sync_knobs(c)["cost_noise"] == 0.0 for c in cells)
    # an explicit axis does activate it
    cells = SweepSpec(cost_noise=(0.0, 0.3)).cell_cfgs(cfg)
    assert [c.cost_model for c in cells] == ["fixed", "variable"]


def test_async_ingraph_default_horizon_does_not_recompile_per_knob():
    """With max_events=None the derived horizon is bucketed before it
    enters the compile-cache key — knob changes (budget included) must
    reuse the program (review regression)."""
    ol, ex, init = _svm_fixture()
    s = _session(ol, ex, init)
    s.run_async_ingraph()
    prog = s._async_fastpath
    s.cfg = dataclasses.replace(s.cfg, budget=900.0, ucb_c=0.5)
    rep = s.run_async_ingraph()
    assert s._async_fastpath is prog
    assert prog._cache_size() == 1
    assert rep.terminated_reason == "budget_exhausted"


def test_sweep_spec_new_axes_validation():
    with pytest.raises(ValueError, match="cost_noise"):
        SweepSpec(cost_noise=(-0.1,))
    with pytest.raises(ValueError, match="async_alpha"):
        SweepSpec(async_alpha=(0.0,))
    with pytest.raises(ValueError, match="async_alpha"):
        SweepSpec(async_alpha=(1.5,))
    with pytest.raises(ValueError, match="async_batch_k"):
        SweepSpec(async_batch_k=(-1,))
    spec = SweepSpec(async_alpha=[0.25, 0.75], cost_noise=[0.1])
    assert spec.async_alpha == (0.25, 0.75) and hash(spec)
    assert spec.n_cells == 2


def test_sweep_spec_per_batch_k_splits_the_structural_axis():
    spec = SweepSpec(async_batch_k=(1, 2), seeds=(0, 3), max_rounds=48)
    subs = spec.per_batch_k()
    assert [k for k, _ in subs] == [1, 2]
    assert all(s.async_batch_k == (k,) for k, s in subs)
    assert sum(s.n_cells for _, s in subs) == spec.n_cells == 4
    # single-valued (or absent) axis: no split at all
    assert SweepSpec(seeds=(0,)).per_batch_k()[0][1] is not None
    assert len(SweepSpec(async_batch_k=(2,)).per_batch_k()) == 1


def test_async_sweep_batch_k_axis_is_a_pure_throughput_axis():
    """async_batch_k is semi-structural: the sweep splits into one
    compiled sub-program per K, and — K being order-equivalent — the
    K=1 and K=2 blocks of the grid must be bit-identical to each other
    and to the independent single runs."""
    ol, ex, init = _svm_fixture()
    spec = SweepSpec(async_batch_k=(1, 2), seeds=(0, 3), max_rounds=48)
    sess = _session(ol, ex, init)
    rep = sess.sweep(spec)
    assert rep.n_cells == 4
    out = rep.out
    # axis order puts async_batch_k slowest: cells 0,1 are K=1 seeds
    # (0,3); cells 2,3 the same seeds at K=2
    for f in ("n_rounds", "metric", "edge", "consumed", "wall_time"):
        assert np.array_equal(out[f][:2], out[f][2:],
                              equal_nan=(f == "metric")), f
    for i, ccfg in enumerate(spec.cell_cfgs(ol)[:2]):
        ind = _session(ccfg, ex, init).run_async_ingraph(max_events=48)
        n = int(out["n_rounds"][i])
        assert n == ind.n_aggregations > 0
        assert np.array_equal(
            out["metric"][i][:n].astype(np.float64),
            np.array([r.metric for r in ind.records]))
        assert np.array_equal(out["edge"][i][:n],
                              np.array([r.edge for r in ind.records]))


def test_async_sweep_partition_specs_costs_ek_placement():
    from jax.sharding import PartitionSpec as P
    from repro.el.sweep import sweep_partition_specs
    key_spec, knobs = sweep_partition_specs(
        ("data", "model"), {"data": 4, "model": 16},
        n_cells=8, n_edges=32, mode="async")
    assert key_spec == P(("data",))
    assert knobs["costs_ek"] == P(("data",), "model", None)  # [C, E, K]
    assert knobs["async_alpha"] == P(("data",))              # [C]
    assert knobs["cost_noise"] == P(("data",))
    assert knobs["comp"] == P(("data",), "model")
