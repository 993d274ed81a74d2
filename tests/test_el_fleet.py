"""Multi-tenant EL-as-a-service: cohort bucketing (one compile per
structure), slot waves with mid-flight refill, masked-slot freezing,
priority admission, streamed deltas, shared compile cache, lifecycle —
and the correctness bar: every tenant bit-identical to an independent
``run_sync_ingraph`` / ``run_async_ingraph`` of that tenant alone."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.el import (ELSession, FleetServer, ReportReady, RoundDelta,
                      TenantRun)
from repro.el.sweep.engine import make_cell_batch
from repro.launch.classic import classic_fixture

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def svm():
    return classic_fixture("svm-wafer", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


@pytest.fixture(scope="module")
def kmeans():
    return classic_fixture("kmeans-traffic", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


def _cfg(fx, mode, budget, ucb_c, seed):
    return dataclasses.replace(
        fx["exp"].ol4el, mode=mode, policy="ol4el", n_edges=4,
        utility=fx["utility"], budget=float(budget), ucb_c=float(ucb_c),
        seed=int(seed))


def _tenant(fx, cfg, **kw):
    return TenantRun(
        cfg=cfg, executor=fx["executor"], metric_name=fx["metric"],
        n_samples=fx["n_samples"] if cfg.mode == "sync" else None,
        init_params=fx["init_params"], **kw)


def _ref(fx, cfg):
    """The independent single run the fleet must reproduce bit-for-bit."""
    s = (ELSession(cfg, metric_name=fx["metric"])
         .with_executor(fx["executor"], init_params=fx["init_params"],
                        n_samples=(fx["n_samples"] if cfg.mode == "sync"
                                   else None)))
    return (s.run_sync_ingraph() if cfg.mode == "sync"
            else s.run_async_ingraph())


def _records_equal(a, b):
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        for x, y in zip(dataclasses.astuple(ra), dataclasses.astuple(rb)):
            if x != y and not (isinstance(x, float)
                               and np.isnan(x) and np.isnan(y)):
                return False
    return True


def _assert_reports_identical(ref, fleet):
    assert fleet.final_metric == ref.final_metric
    assert fleet.n_aggregations == ref.n_aggregations
    assert fleet.total_consumed == ref.total_consumed
    assert fleet.wall_time == ref.wall_time
    assert fleet.terminated_reason == ref.terminated_reason
    assert fleet.arm_pulls == ref.arm_pulls
    assert _records_equal(fleet.records, ref.records)
    for x, y in zip(jax.tree.leaves(ref.final_params),
                    jax.tree.leaves(fleet.final_params)):
        assert np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True)


def _host_view(tree):
    """Comparable host copy of a carry (PRNG keys via their raw data)."""
    return [np.asarray(jax.random.key_data(x)
                       if jax.dtypes.issubdtype(x.dtype,
                                                jax.dtypes.prng_key)
                       else x)
            for x in jax.tree.leaves(tree)]


# ---------------------------------------------------------------------------
# the steppable cell batch (fleet data plane)
# ---------------------------------------------------------------------------


def test_masked_slot_is_bit_frozen(svm):
    """Satellite bar: an inactive slot runs ZERO iterations per wave —
    bandit state, consumed budget, RNG key and history are byte-frozen,
    and its presence does not perturb the active slots either."""
    from repro.el.ingraph import sync_knobs
    ex = svm["executor"]
    cfg0 = _cfg(svm, "sync", 900.0, 1.0, 0)
    cfg1 = _cfg(svm, "sync", 1200.0, 0.5, 1)
    cb = make_cell_batch(ex.model, ex.edge_data, ex.eval_set, cfg0,
                         n_slots=2, rounds_per_wave=4, lr=ex.lr,
                         batch=ex.batch,
                         n_samples=np.asarray(svm["n_samples"], float),
                         metric_name=svm["metric"], horizon=64)
    rows = [{k: jnp.asarray(v) for k, v in sync_knobs(c).items()}
            for c in (cfg0, cfg1)]
    kst = {k: jnp.stack([rows[0][k], rows[1][k]]) for k in rows[0]}
    init = svm["init_params"]

    def carries():
        c0 = cb.init_slot(init, jax.random.key(cfg0.seed + 17), rows[0])
        c1 = cb.init_slot(init, jax.random.key(cfg1.seed + 17), rows[1])
        return cb.place(cb.broadcast(c0), c1, jnp.int32(1)), c1

    stacked, c1 = carries()
    before = _host_view(c1)
    stacked, running = cb.step(stacked, kst,
                               jnp.asarray([True, False]))
    # slot 1 (masked): bit-frozen — zero body iterations
    after = _host_view(cb.take_slot(stacked, jnp.int32(1)))
    for x, y in zip(before, after):
        assert np.array_equal(x, y, equal_nan=True)
    assert int(np.asarray(stacked["t"])[1]) == 0
    assert not bool(np.asarray(running)[1])
    # slot 0 (active): advanced, budget charged
    assert int(np.asarray(stacked["t"])[0]) == 4
    masked_view = _host_view(cb.take_slot(stacked, jnp.int32(0)))

    # the same wave with BOTH slots live: slot 0's trajectory must not
    # change — active cells are independent of their neighbors' masks
    stacked2, _ = carries()
    stacked2, _ = cb.step(stacked2, kst, jnp.asarray([True, True]))
    both_view = _host_view(cb.take_slot(stacked2, jnp.int32(0)))
    for x, y in zip(masked_view, both_view):
        assert np.array_equal(x, y, equal_nan=True)
    assert int(np.asarray(stacked2["t"])[1]) > 0   # neighbor really ran


# ---------------------------------------------------------------------------
# fleet bit-identity (the correctness bar)
# ---------------------------------------------------------------------------


def _serve(fx, cfgs, n_slots, rounds_per_wave, **server_kw):
    srv = FleetServer(n_slots=n_slots, rounds_per_wave=rounds_per_wave,
                      **server_kw)
    deltas, order = {}, []
    def sub(ev):
        if isinstance(ev, RoundDelta):
            deltas.setdefault(ev.tenant_id, []).append(ev.record)
        else:
            order.append(ev.tenant_id)
    srv.subscribe(sub)
    ids = [srv.submit(_tenant(fx, c)) for c in cfgs]
    reports = srv.drain()
    return srv, ids, reports, deltas, order


def test_sync_fleet_bit_identical_with_refill(svm):
    """3 tenants through 2 slots (forces mid-flight refill), short waves
    (forces multi-wave runs): every report — records, params, pulls —
    equals an independent run_sync_ingraph of that tenant alone, and the
    streamed deltas ARE the report's records."""
    cfgs = [_cfg(svm, "sync", 1200.0, 1.0, 0),
            _cfg(svm, "sync", 1500.0, 0.5, 1),
            _cfg(svm, "sync", 900.0, 2.0, 2)]
    srv, ids, reports, deltas, _ = _serve(svm, cfgs, 2, 5)
    st = srv.stats()
    assert st["compiles"] == 1                   # one cohort, one program
    # wave-batched data plane: admits land as ONE place_many scatter per
    # admitting wave, finalizes as ONE take_many gather per finalizing
    # wave — 3 tenants with refill must NOT cost 3 dispatches a side
    assert 1 <= st["place_dispatches"] <= st["waves"]
    assert 1 <= st["gather_dispatches"] <= st["waves"]
    assert st["place_dispatches"] < len(cfgs)    # tenants batched together
    for tid, cfg in zip(ids, cfgs):
        _assert_reports_identical(_ref(svm, cfg), reports[tid])
        assert _records_equal(deltas[tid], reports[tid].records)
        assert reports[tid].n_aggregations > 5   # multi-wave really hit


def test_async_fleet_bit_identical_with_refill(kmeans):
    cfgs = [_cfg(kmeans, "async", 800.0, 1.0, 3),
            _cfg(kmeans, "async", 900.0, 0.7, 4),
            _cfg(kmeans, "async", 700.0, 1.5, 5)]
    srv, ids, reports, deltas, _ = _serve(kmeans, cfgs, 2, 5)
    st = srv.stats()
    assert st["compiles"] == 1                   # one padded horizon
    assert 1 <= st["place_dispatches"] <= st["waves"]
    assert 1 <= st["gather_dispatches"] <= st["waves"]
    for tid, cfg in zip(ids, cfgs):
        _assert_reports_identical(_ref(kmeans, cfg), reports[tid])
        assert _records_equal(deltas[tid], reports[tid].records)
        assert reports[tid].n_aggregations > 5


def test_report_ready_follows_final_delta(svm):
    cfgs = [_cfg(svm, "sync", 900.0, 1.0, 7)]
    srv = FleetServer(n_slots=1, rounds_per_wave=4)
    events = []
    srv.subscribe(events.append)
    tid = srv.submit(_tenant(svm, cfgs[0]))
    srv.drain()
    kinds = [type(e).__name__ for e in events]
    assert kinds[-1] == "ReportReady" and kinds[:-1] == \
        ["RoundDelta"] * (len(events) - 1)
    assert all(e.tenant_id == tid for e in events)


# ---------------------------------------------------------------------------
# cohorts, admission, cache
# ---------------------------------------------------------------------------


def test_cohort_bucketing_one_compile_per_structure(svm, kmeans):
    srv = FleetServer(n_slots=2, rounds_per_wave=8)
    for i in range(3):                      # one sync structure...
        srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0 + 300 * i,
                                     1.0, 10 + i)))
    for i in range(2):                      # ...one async structure
        srv.submit(_tenant(kmeans, _cfg(kmeans, "async", 800.0 + 50 * i,
                                        1.0, 20 + i)))
    reports = srv.drain()
    st = srv.stats()
    assert len(reports) == 5
    assert st["cohorts"] == 2
    assert st["compiles"] == 2              # ONE program per cohort
    assert st["tenants_done"] == 5 and st["tenants_active"] == 0


def test_priority_admission_order(svm):
    """Higher priority admits first through a single slot; ties FIFO."""
    srv = FleetServer(n_slots=1, rounds_per_wave=64)
    order = []
    srv.subscribe(lambda ev: order.append(ev.tenant_id)
                  if isinstance(ev, ReportReady) else None)
    low = srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 0),
                             priority=0))
    high = srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 1),
                              priority=5))
    mid = srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 2),
                             priority=1))
    srv.drain()
    assert order == [high, mid, low]


def test_shared_compile_cache_with_session(svm):
    """FleetServer(cache=session.compile_cache): cohort programs and the
    session's verification runs pool one cache — and a second server on
    the same pool reuses the cohort program without recompiling."""
    cfg = _cfg(svm, "sync", 900.0, 1.0, 3)
    sess = (ELSession(cfg, metric_name=svm["metric"])
            .with_executor(svm["executor"],
                           init_params=svm["init_params"],
                           n_samples=svm["n_samples"]))
    cache = sess.compile_cache
    srv = FleetServer(n_slots=2, rounds_per_wave=8, cache=cache)
    tid = srv.submit(_tenant(svm, cfg))
    fleet_report = srv.drain()[tid]
    assert srv.compiles == 1 and len(cache) == 1
    ref = sess.run_sync_ingraph()            # lands in the SAME pool
    assert len(cache) == 2
    _assert_reports_identical(ref, fleet_report)

    srv2 = FleetServer(n_slots=2, rounds_per_wave=8, cache=cache)
    hits_before = cache.hits
    tid2 = srv2.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 2.0, 9)))
    srv2.drain()
    assert srv2.compiles == 0                # cohort program came cached
    assert cache.hits > hits_before

    srv.close()                              # shared pool NOT cleared
    assert len(cache) == 2


def test_server_close_releases_and_refuses(svm):
    srv = FleetServer(n_slots=2, rounds_per_wave=8)
    tid = srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 4)))
    srv.drain()
    srv.close()
    assert srv.report(tid) is not None       # delivered reports survive
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 5)))
    srv.close()                              # idempotent


def test_duplicate_tenant_id_rejected(svm):
    srv = FleetServer(n_slots=2)
    srv.submit(_tenant(svm, _cfg(svm, "sync", 600.0, 1.0, 0),
                       tenant_id="dup"))
    with pytest.raises(ValueError, match="dup"):
        srv.submit(_tenant(svm, _cfg(svm, "sync", 900.0, 1.0, 1),
                           tenant_id="dup"))


# ---------------------------------------------------------------------------
# mesh-sharded fleet (subprocess: forced 4-device host, 2x2 debug mesh)
# ---------------------------------------------------------------------------

_MESH_FLEET_SCRIPT = textwrap.dedent("""
    import dataclasses, sys
    import jax, numpy as np
    assert jax.device_count() == 4, jax.devices()
    from repro.el import ELSession, FleetServer, TenantRun
    from repro.launch.classic import classic_fixture
    from repro.launch.mesh import make_debug_mesh

    mode = sys.argv[1]
    arch = "svm-wafer" if mode == "sync" else "kmeans-traffic"
    fx = classic_fixture(arch, samples=128, n_edges=4, alpha=100.0,
                         data_seed=0)
    cfgs = [dataclasses.replace(
                fx["exp"].ol4el, mode=mode, policy="ol4el", n_edges=4,
                utility=fx["utility"], budget=b, ucb_c=u, seed=s)
            for b, u, s in [(800.0, 1.0, 0), (900.0, 0.5, 1),
                            (700.0, 2.0, 2)]]
    ns = fx["n_samples"] if mode == "sync" else None

    srv = FleetServer(n_slots=2, rounds_per_wave=5,
                      mesh=make_debug_mesh(2, 2))
    ids = [srv.submit(TenantRun(
               cfg=c, executor=fx["executor"], metric_name=fx["metric"],
               n_samples=ns, init_params=fx["init_params"]))
           for c in cfgs]
    reports = srv.drain()

    for tid, c in zip(ids, cfgs):
        s = (ELSession(c, metric_name=fx["metric"])
             .with_executor(fx["executor"],
                            init_params=fx["init_params"], n_samples=ns))
        ref = (s.run_sync_ingraph() if mode == "sync"
               else s.run_async_ingraph())
        r = reports[tid]
        assert r.n_aggregations == ref.n_aggregations > 0
        assert r.total_consumed == ref.total_consumed
        assert r.wall_time == ref.wall_time
        assert r.arm_pulls == ref.arm_pulls
        for a, b in zip(ref.records, r.records):
            ta, tb = dataclasses.astuple(a), dataclasses.astuple(b)
            assert all(x == y or (isinstance(x, float) and np.isnan(x)
                                  and np.isnan(y))
                       for x, y in zip(ta, tb)), (ta, tb)
        for pa, pb in zip(jax.tree.leaves(ref.final_params),
                          jax.tree.leaves(r.final_params)):
            assert np.array_equal(np.asarray(pa), np.asarray(pb))
    print("FLEET-MESH-BIT-IDENTICAL", mode,
          [reports[t].n_aggregations for t in ids])
""")


def _run_mesh_fleet(mode: str):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    return subprocess.run(
        [sys.executable, "-c", _MESH_FLEET_SCRIPT, mode],
        capture_output=True, text=True, env=env, timeout=900)


@pytest.mark.slow
def test_sync_fleet_on_debug_mesh_bit_identical_subprocess():
    r = _run_mesh_fleet("sync")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET-MESH-BIT-IDENTICAL sync" in r.stdout


@pytest.mark.slow
def test_async_fleet_on_debug_mesh_bit_identical_subprocess():
    r = _run_mesh_fleet("async")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET-MESH-BIT-IDENTICAL async" in r.stdout
