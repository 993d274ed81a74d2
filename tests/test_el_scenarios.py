"""The repro.el.scenarios subsystem: ScenarioSpec validation and
structural hashing, host-side schedule materialization, the scenario
knob surface, scenario-off bit-identity of the compiled programs
(sync, async K in {1,4}, fleet cohort — replicated and on a 2x2 debug
mesh), dead-edge zero-charging, the host reference replay oracle, the
in-graph policy switch / churn sweep axes, the shared CLI glue, and
the support-matrix error messages."""

import argparse
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.config import OL4ELConfig
from repro.el import ELSession, FleetServer, SweepSpec, TenantRun
from repro.el.events import (ASYNC_KNOB_NAMES, async_knob_names,
                             async_knobs, make_async_program)
from repro.el.ingraph import (KNOB_NAMES, check_ingraph_support,
                              make_sync_program, support_matrix,
                              sync_knob_names, sync_knobs)
from repro.el.scenarios import (ChurnSpec, CostSpec, ScenarioSpec,
                                as_scenario, verify_sync_replay)
from repro.el.scenarios.baselines import (INGRAPH_POLICY_ORDER,
                                          ingraph_policy_id)
from repro.el.scenarios.cli import add_scenario_args, scenario_from_args
from repro.el.scenarios.schedule import (SCENARIO_KNOB_NAMES,
                                         activity_schedule, cost_schedule,
                                         scenario_knob_names,
                                         scenario_knobs)
from repro.launch.classic import classic_fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def svm():
    return classic_fixture("svm-wafer", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


def _cfg(fx, mode="sync", scenario=None, **kw):
    kw.setdefault("budget", 700.0)
    kw.setdefault("policy", "ol4el")
    return dataclasses.replace(
        fx["exp"].ol4el, mode=mode, n_edges=4,
        utility=fx["utility"], scenario=scenario, **kw)


def _session(fx, cfg):
    return (ELSession(cfg, metric_name=fx["metric"])
            .with_executor(fx["executor"],
                           init_params=fx["init_params"],
                           n_samples=(fx["n_samples"]
                                      if cfg.mode == "sync" else None)))


def _sync_out(fx, cfg, max_rounds=48):
    """Drive make_sync_program directly (the raw out dict carries the
    per-round scenario histories the session report does not)."""
    ex = fx["executor"]
    prog = jax.jit(make_sync_program(
        ex.model, ex.edge_data, ex.eval_set, cfg, lr=ex.lr,
        batch=ex.batch,
        n_samples=np.asarray(fx["n_samples"], np.float64),
        max_rounds=max_rounds))
    _, out = prog(fx["init_params"], jax.random.key(cfg.seed + 17),
                  sync_knobs(cfg))
    return jax.tree.map(np.asarray, out)


# ---------------------------------------------------------------------------
# ScenarioSpec: validation, period, structural residue
# ---------------------------------------------------------------------------


def test_spec_validation_and_period():
    with pytest.raises(ValueError, match="rate"):
        ChurnSpec(rate=1.0)
    with pytest.raises(ValueError, match="kind"):
        ChurnSpec(kind="bogus")
    with pytest.raises(ValueError, match="trace"):
        ChurnSpec(kind="trace")
    with pytest.raises(ValueError, match="alpha"):
        CostSpec(alpha=1.0)
    with pytest.raises(ValueError, match="positive"):
        CostSpec(kind="trace", trace=((1.0, -1.0),))
    with pytest.raises(ValueError, match="drift"):
        ScenarioSpec(drift=-0.1)
    # period: lcm of the present parts; 1 when nothing is scheduled
    assert ScenarioSpec().period == 1
    assert ScenarioSpec(churn=ChurnSpec(period=6),
                        cost=CostSpec(period=4)).period == 12
    # trace rows pin the period to the trace length
    tr = ChurnSpec(kind="trace", trace=((1, 1), (1, 0), (0, 1)))
    assert tr.period == 3


def test_spec_is_hashable_and_structural_drops_knob_values():
    a = ScenarioSpec(churn=ChurnSpec(rate=0.3, seed=5),
                     cost=CostSpec(kind="lognormal", sigma=0.9),
                     drift=0.02)
    b = ScenarioSpec(churn=ChurnSpec(rate=0.05, seed=11),
                     cost=CostSpec(kind="pareto", alpha=3.0))
    assert hash(a) != hash(ScenarioSpec())
    # rates/seeds/kinds are knob values -> same executable bucket
    assert a.structural() == b.structural()
    assert a.structural() != ScenarioSpec(
        churn=ChurnSpec(period=32)).structural()


def test_as_scenario_normalization():
    assert as_scenario(None) is None
    assert as_scenario(False) is None
    assert as_scenario(True) == ScenarioSpec()
    s = ScenarioSpec(drift=0.1)
    assert as_scenario(s) is s
    with pytest.raises(TypeError, match="ScenarioSpec"):
        as_scenario("churn")


# ---------------------------------------------------------------------------
# schedule materialization
# ---------------------------------------------------------------------------


def test_activity_schedule_min_active_and_determinism():
    ch = ChurnSpec(rate=0.9, period=32, min_active=2, seed=3)
    act = activity_schedule(ch, 4, 32)
    assert act.shape == (32, 4) and act.dtype == np.float32
    assert set(np.unique(act)) <= {0.0, 1.0}
    assert (act.sum(axis=1) >= 2).all()           # revival floor
    np.testing.assert_array_equal(act, activity_schedule(ch, 4, 32))
    # None => always-on
    assert activity_schedule(None, 3, 8).min() == 1.0
    with pytest.raises(ValueError, match="edges"):
        activity_schedule(ChurnSpec(kind="trace", trace=((1, 1),)), 3, 1)


def test_cost_schedule_kinds_and_tiling():
    par = cost_schedule(CostSpec(kind="pareto", alpha=2.0, period=16),
                        4, 16)
    assert par.shape == (16, 4) and (par >= 1.0).all()   # spikes only
    logn = cost_schedule(CostSpec(kind="lognormal", sigma=0.5,
                                  period=16), 4, 16)
    assert (logn > 0).all() and not (logn >= 1.0).all()
    # shorter part tiles up to the combined lcm period
    tiled = cost_schedule(CostSpec(kind="trace",
                                   trace=((2.0, 1.0), (1.0, 3.0))), 2, 6)
    assert tiled.shape == (6, 2)
    np.testing.assert_array_equal(tiled[:2], tiled[2:4])


# ---------------------------------------------------------------------------
# knob surface: scenario=None keeps the pre-scenario traced signature
# ---------------------------------------------------------------------------


def test_knob_names_scenario_off_are_the_pre_scenario_tuples():
    """The scenario-off programs take EXACTLY the historical knobs —
    the traced signature (and thus the compiled program) is unchanged."""
    off = OL4ELConfig(mode="sync")
    assert off.scenario is None
    assert sync_knob_names(off) == KNOB_NAMES == (
        "ucb_c", "budget", "comp", "comm", "costs_k", "min_edge_cost",
        "cost_noise")
    assert async_knob_names(dataclasses.replace(off, mode="async")) \
        == ASYNC_KNOB_NAMES == (
            "ucb_c", "budget", "comp", "comm", "costs_ek",
            "min_edge_cost", "cost_noise", "async_alpha", "event_cap")
    assert set(sync_knobs(off)) == set(KNOB_NAMES)


def test_knob_names_and_arrays_with_scenario():
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.2, period=8),
                       cost=CostSpec(period=8), drift=0.01)
    cfg = OL4ELConfig(mode="sync", n_edges=3, scenario=scn)
    assert sync_knob_names(cfg) == KNOB_NAMES + SCENARIO_KNOB_NAMES \
        + ("policy_id",)
    assert scenario_knob_names("async") == SCENARIO_KNOB_NAMES
    knobs = scenario_knobs(cfg)
    assert knobs["scn_active"].shape == (8, 3)
    assert knobs["scn_mult"].shape == (8, 3)
    assert knobs["scn_drift"] == np.float32(0.01)
    assert knobs["policy_id"] == np.int32(0)           # ol4el = branch 0
    acfg = dataclasses.replace(cfg, mode="async")
    assert "policy_id" not in scenario_knobs(acfg)
    assert async_knob_names(acfg) == ASYNC_KNOB_NAMES \
        + SCENARIO_KNOB_NAMES
    # full sync_knobs picks the scenario arrays up automatically
    assert set(sync_knobs(cfg)) == set(sync_knob_names(cfg))


def test_policy_switch_order_and_registry_parity():
    from repro.el import policies as el_policies
    assert INGRAPH_POLICY_ORDER == ("ol4el", "task_alloc", "delay_energy")
    for i, name in enumerate(INGRAPH_POLICY_ORDER):
        assert ingraph_policy_id(name) == i
        assert name in el_policies.available()       # host twins exist
    with pytest.raises(ValueError, match="greedy"):
        ingraph_policy_id("greedy")


# ---------------------------------------------------------------------------
# scenario-off bit-identity (THE hard correctness bar): with
# scenario=None the compiled programs reproduce the pre-scenario
# behavior bit-for-bit.  Anchors that predate the scenario engine:
# the async host event queue on shared jax RNG streams, and fleet
# cohorts vs independent single runs.
# ---------------------------------------------------------------------------


def _assert_async_bit_identical(ref, ing):
    assert ref.n_aggregations == ing.n_aggregations > 0
    for t, (a, b) in enumerate(zip(ref.records, ing.records)):
        assert a.edge == b.edge, t
        assert a.interval == b.interval, t
        assert a.wall_time == b.wall_time, t
        assert a.total_consumed == b.total_consumed, t
        assert a.utility == b.utility, t
    assert ref.arm_pulls == ing.arm_pulls
    assert ref.terminated_reason == ing.terminated_reason
    assert ref.final_metric == ing.final_metric


@pytest.mark.parametrize("batch_k", [1, 4])
def test_scenario_off_async_bit_identical_to_host_queue(svm, batch_k):
    cfg = _cfg(svm, "async", scenario=None, budget=500.0,
               async_batch_k=batch_k)
    ref = _session(svm, cfg).run_async(rng_streams="jax")
    ing = _session(svm, cfg).run_async_ingraph()
    _assert_async_bit_identical(ref, ing)


def test_scenario_off_sync_and_explicit_none_agree(svm):
    """scenario=None is the dataclass default; spelling it explicitly
    (or via as_scenario(False)) must hit the identical compiled run."""
    base = _cfg(svm, "sync", budget=600.0)
    out_a = _sync_out(svm, base)
    out_b = _sync_out(svm, dataclasses.replace(
        base, scenario=as_scenario(False)))
    assert set(out_a) == set(out_b)
    assert "active_edges" not in out_a       # scenario hist is absent
    for k in out_a:
        np.testing.assert_array_equal(np.asarray(out_a[k]),
                                      np.asarray(out_b[k]))


def test_scenario_off_fleet_cohort_bit_identical(svm):
    cfgs = [_cfg(svm, "sync", budget=b, seed=s, scenario=None)
            for b, s in [(600.0, 0), (750.0, 1)]]
    srv = FleetServer(n_slots=2, rounds_per_wave=4)
    ids = [srv.submit(TenantRun(
               cfg=c, executor=svm["executor"],
               metric_name=svm["metric"], n_samples=svm["n_samples"],
               init_params=svm["init_params"])) for c in cfgs]
    reports = srv.drain()
    for tid, c in zip(ids, cfgs):
        ref = _session(svm, c).run_sync_ingraph()
        r = reports[tid]
        assert r.n_aggregations == ref.n_aggregations > 0
        assert r.total_consumed == ref.total_consumed
        assert r.wall_time == ref.wall_time
        assert r.arm_pulls == ref.arm_pulls
        for pa, pb in zip(jax.tree.leaves(ref.final_params),
                          jax.tree.leaves(r.final_params)):
            assert np.array_equal(np.asarray(pa), np.asarray(pb))


_MESH_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, numpy as np
    assert jax.device_count() == 4, jax.devices()
    from repro.el import ELSession
    from repro.el.scenarios import ScenarioSpec, ChurnSpec
    from repro.launch.classic import classic_fixture
    from repro.launch.mesh import make_debug_mesh

    fx = classic_fixture("svm-wafer", samples=128, n_edges=4,
                         alpha=100.0, data_seed=0)
    cfg = dataclasses.replace(
        fx["exp"].ol4el, mode="sync", policy="ol4el", n_edges=4,
        utility=fx["utility"], budget=600.0, scenario=None)
    mesh = make_debug_mesh(2, 2)

    def run(mesh_):
        s = (ELSession(cfg, metric_name=fx["metric"])
             .with_executor(fx["executor"],
                            init_params=fx["init_params"],
                            n_samples=fx["n_samples"]))
        return s.run_sync_ingraph(mesh=mesh_)

    rep = run(None)
    mrep = run(mesh)
    assert mrep.n_aggregations == rep.n_aggregations > 0
    assert mrep.total_consumed == rep.total_consumed
    assert mrep.arm_pulls == rep.arm_pulls

    # scenario path on the mesh: compiles and respects the schedule
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.3, period=16))
    scfg = dataclasses.replace(cfg, scenario=scn)
    s = (ELSession(scfg, metric_name=fx["metric"])
         .with_executor(fx["executor"], init_params=fx["init_params"],
                        n_samples=fx["n_samples"]))
    srep = s.run_sync_ingraph(mesh=mesh)
    assert srep.n_aggregations > 0
    print("SCENARIO-MESH-OK", rep.n_aggregations, srep.n_aggregations)
""")


@pytest.mark.slow
def test_scenario_mesh_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               REPRO_EL_CONTRACTS="1",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SCENARIO-MESH-OK" in r.stdout


# ---------------------------------------------------------------------------
# scenario semantics in the compiled sync program
# ---------------------------------------------------------------------------


def test_dead_edges_run_zero_work_and_are_not_charged(svm):
    """An edge dropped by the churn trace for the WHOLE run keeps its
    full budget (zero charge), and every round's active count matches
    the schedule — the mask-aware aggregation skipped it correctly."""
    trace = ((1, 1, 1, 0),) * 4                   # edge 3 always out
    scn = ScenarioSpec(churn=ChurnSpec(kind="trace", trace=trace))
    cfg = _cfg(svm, "sync", scenario=scn, budget=600.0)
    out = _sync_out(svm, cfg)
    n = int(out["n_rounds"])
    assert n > 0
    np.testing.assert_array_equal(out["active_edges"][:n],
                                  np.full(n, 3, np.int32))
    # dead edge: budget untouched; live edges: charged
    assert float(out["budgets_left"][3]) == 600.0
    assert (np.asarray(out["budgets_left"][:3]) < 600.0).all()


def test_identity_scenario_runs_all_edges_active(svm):
    """ScenarioSpec() (the identity scenario) takes the scenario-path
    program but schedules nothing: all edges active every round, no
    drift, unit multipliers — and the policy switch runs branch 0."""
    cfg = _cfg(svm, "sync", scenario=ScenarioSpec(), budget=600.0)
    out = _sync_out(svm, cfg)
    n = int(out["n_rounds"])
    assert n > 0
    np.testing.assert_array_equal(out["active_edges"][:n],
                                  np.full(n, 4, np.int32))


def _run_program(fx, cfg):
    """Final params and raw out dict of one compiled run: sync over 48
    rounds (n_samples-weighted), async as single events over 64."""
    ex = fx["executor"]
    args = (ex.model, ex.edge_data, ex.eval_set, cfg)
    if cfg.mode == "sync":
        prog = make_sync_program(
            *args, lr=ex.lr, batch=ex.batch, max_rounds=48,
            n_samples=np.asarray(fx["n_samples"], np.float64))
        knobs = sync_knobs(cfg)
    else:
        prog = make_async_program(*args, lr=ex.lr, batch=ex.batch,
                                  max_events=64, batch_k=1)
        knobs = async_knobs(cfg)
    return jax.tree.map(np.asarray, jax.jit(prog)(
        fx["init_params"], jax.random.key(cfg.seed + 17), knobs))


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_identity_scenario_equals_no_scenario_bit_for_bit(svm, mode):
    """Under ScenarioSpec() every edge is active, nothing drifts and
    every multiplier is 1, so each scenario branch of the round and
    event bodies must be an exact identity: the final params and every
    out key both runs share equal the scenario=None run's bit for bit
    (NaN equal to NaN)."""
    cfg = _cfg(svm, mode)
    params, out = _run_program(svm, cfg)
    s_params, s_out = _run_program(
        svm, dataclasses.replace(cfg, scenario=ScenarioSpec()))
    assert int(out["n_rounds"]) > 0
    assert set(out) < set(s_out)        # the scenario only adds keys
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(s_params)):
        np.testing.assert_array_equal(a, b)
    for key in out:
        np.testing.assert_array_equal(out[key], s_out[key], err_msg=key)


def test_churn_reference_replay_matches_event_for_event(svm):
    """Acceptance bar: the host-side numpy replay of a churn schedule
    agrees with the compiled program event-for-event — termination
    round and per-round active-edge counts exactly, budget/wall
    bookkeeping to float32 round-off."""
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.3, period=16),
                       cost=CostSpec(kind="lognormal", sigma=0.4,
                                     period=16))
    cfg = _cfg(svm, "sync", scenario=scn, budget=700.0)
    out = _sync_out(svm, cfg, max_rounds=64)
    ref = verify_sync_replay(cfg, out, 64)
    assert int(ref["n_rounds"]) == int(out["n_rounds"]) > 0
    # churn actually happened (not a degenerate always-on schedule)
    n = int(out["n_rounds"])
    assert out["active_edges"][:n].min() < 4


def test_replay_oracle_rejects_noisy_costs(svm):
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.2))
    cfg = _cfg(svm, "sync", scenario=scn, cost_model="variable",
               cost_noise=0.2)
    with pytest.raises(ValueError, match="cost_noise"):
        verify_sync_replay(cfg, {"interval": np.zeros(4)}, 4)


def test_async_scenario_requires_single_event_waves(svm):
    from repro.el.events.knobs import resolve_async_batch_k
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.2, period=8))
    cfg = _cfg(svm, "async", scenario=scn)
    assert resolve_async_batch_k(cfg) == 1           # auto pins to 1
    bad = dataclasses.replace(cfg, async_batch_k=4)
    with pytest.raises(ValueError, match="async_batch_k"):
        _session(svm, bad).run_async_ingraph()
    rep = _session(svm, cfg).run_async_ingraph(max_events=128)
    assert rep.n_aggregations > 0


# ---------------------------------------------------------------------------
# sweep axes: policy switch + churn rate as vmapped cell axes
# ---------------------------------------------------------------------------


def test_policy_axis_sweeps_baselines_in_one_program(svm):
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.25, period=16))
    cfg = _cfg(svm, "sync", scenario=scn, budget=600.0)
    spec = SweepSpec(policy=INGRAPH_POLICY_ORDER, max_rounds=48)
    sess = _session(svm, cfg)
    rep = sess.sweep(spec)
    assert rep.n_cells == 3
    assert sess._sweep_program._cache_size() == 1    # ONE executable
    assert (np.asarray(rep.out["n_rounds"]) > 0).all()
    # the ol4el cell is bit-identical to an independent scenario run
    ind = _sync_out(svm, cfg, max_rounds=48)
    i = list(INGRAPH_POLICY_ORDER).index("ol4el")
    assert int(rep.out["n_rounds"][i]) == int(ind["n_rounds"])
    n = int(ind["n_rounds"])
    np.testing.assert_array_equal(rep.out["interval"][i][:n],
                                  ind["interval"][:n])
    np.testing.assert_array_equal(rep.out["consumed"][i][:n],
                                  ind["consumed"][:n])
    # the baselines take different allocation trajectories
    iv = [tuple(np.asarray(rep.out["interval"][j]
                           )[:int(rep.out["n_rounds"][j])])
          for j in range(3)]
    assert len(set(iv)) >= 2


def test_churn_rate_axis_redraws_the_activity_schedule(svm):
    scn = ScenarioSpec(churn=ChurnSpec(rate=0.1, period=16))
    cfg = _cfg(svm, "sync", scenario=scn, budget=600.0)
    spec = SweepSpec(churn_rate=(0.0, 0.6), max_rounds=48)
    rep = _session(svm, cfg).sweep(spec)
    assert rep.n_cells == 2
    n0, n1 = (int(x) for x in rep.out["n_rounds"])
    act0 = np.asarray(rep.out["active_edges"][0][:n0])
    act1 = np.asarray(rep.out["active_edges"][1][:n1])
    assert (act0 == 4).all()                  # rate 0: nobody drops
    assert act1.min() < 4                     # rate 0.6: churn bites


def test_scenario_axes_require_a_scenario_config(svm):
    cfg = _cfg(svm, "sync", scenario=None)
    with pytest.raises(ValueError, match="identity ScenarioSpec"):
        SweepSpec(policy=("ol4el", "task_alloc")).cell_cfgs(cfg)
    with pytest.raises(ValueError, match="churn"):
        SweepSpec(churn_rate=(0.1,)).cell_cfgs(
            dataclasses.replace(cfg, scenario=ScenarioSpec()))
    with pytest.raises(ValueError, match="policy"):
        SweepSpec(policy=("bogus",))


# ---------------------------------------------------------------------------
# structural keys: scenario joins compile-cache / cohort bucketing
# ---------------------------------------------------------------------------


def test_structural_cfg_buckets_scenario_points_together(svm):
    key = ELSession._structural_cfg
    a = _cfg(svm, "sync", scenario=ScenarioSpec(
        churn=ChurnSpec(rate=0.1, seed=0)))
    b = _cfg(svm, "sync", scenario=ScenarioSpec(
        churn=ChurnSpec(rate=0.5, seed=9)))
    assert key(a) == key(b)                    # rates are knob values
    # the policy switch traces every branch: policy is a knob value too
    c = dataclasses.replace(a, policy="task_alloc")
    assert key(a) == key(c)
    # scenario on vs off are different executables
    assert key(a) != key(_cfg(svm, "sync", scenario=None))
    # but scenario-off policy stays structural (separate host programs)
    off_a = _cfg(svm, "sync", scenario=None)
    off_b = dataclasses.replace(off_a, policy="greedy")
    assert key(off_a) != key(off_b)


# ---------------------------------------------------------------------------
# CLI glue (shared by repro.launch.train / repro.launch.sweep)
# ---------------------------------------------------------------------------


def _parse(argv):
    ap = argparse.ArgumentParser()
    add_scenario_args(ap)
    return ap.parse_args(argv)


def test_cli_defaults_build_no_scenario():
    scn, base = scenario_from_args(_parse([]))
    assert scn is None and base == "fixed"
    scn, base = scenario_from_args(_parse(["--cost-model", "variable"]))
    assert scn is None and base == "variable"


def test_cli_flags_round_trip_to_scenario_spec(tmp_path):
    scn, base = scenario_from_args(_parse(
        ["--churn", "0.2", "--churn-period", "8",
         "--cost-model", "pareto", "--drift", "0.01"]))
    assert base == "fixed"
    assert scn == ScenarioSpec(churn=ChurnSpec(rate=0.2, period=8),
                               cost=CostSpec(kind="pareto", period=8),
                               drift=0.01)
    # trace file: one column broadcasts per-slot multipliers
    p = tmp_path / "times.txt"
    p.write_text("1.0\n2.5\n1.5\n")
    scn, _ = scenario_from_args(_parse(["--cost-model", f"trace:{p}"]))
    assert scn.cost.kind == "trace" and scn.cost.period == 3
    assert scn.cost.trace == ((1.0,), (2.5,), (1.5,))
    with pytest.raises(SystemExit):
        _parse(["--cost-model", "bogus"])


# ---------------------------------------------------------------------------
# support matrix: the front door names the whole menu
# ---------------------------------------------------------------------------


def test_support_matrix_enumerates_scenario_and_cost_models():
    menu = support_matrix()
    for token in ("scenario", "ScenarioSpec", "pareto", "lognormal",
                  "trace:<path>", "task_alloc", "delay_energy",
                  "'fixed', 'variable'"):
        assert token in menu, token


def test_check_support_scenario_error_messages(svm):
    ex = svm["executor"]
    # a scenario cost KIND on cfg.cost_model: redirected to ScenarioSpec
    with pytest.raises(ValueError, match="CostSpec"):
        check_ingraph_support(_cfg(svm, "sync", cost_model="pareto"), ex)
    # baseline policy without a scenario: names the identity spelling
    with pytest.raises(ValueError, match="identity scenario"):
        check_ingraph_support(
            _cfg(svm, "sync", policy="task_alloc", scenario=None), ex)
    # the policy switch is sync-only
    with pytest.raises(ValueError, match="policy switch"):
        check_ingraph_support(
            _cfg(svm, "async", policy="delay_energy",
                 scenario=ScenarioSpec()), ex)
    # a non-spec scenario object is a TypeError with the menu attached
    with pytest.raises(TypeError, match="supported in-graph matrix"):
        check_ingraph_support(
            _cfg(svm, "sync", scenario="churn"), ex)
    # every rejection carries the full menu
    try:
        check_ingraph_support(_cfg(svm, "sync", cost_model="pareto"), ex)
    except ValueError as e:
        assert "supported in-graph matrix" in str(e)
