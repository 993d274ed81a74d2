"""The mesh-sharded single-run data plane: placement policy (pure
spec level), sharded bit-identity vs the unsharded compiled programs
(subprocess debug mesh), buffer donation, mesh-aware compile-cache keys,
and Pallas-backed K-means local blocks."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_config
from repro.data import (make_traffic_dataset, make_wafer_dataset,
                        partition_edges)
from repro.el import ELSession
from repro.federated import ClassicExecutor
from repro.models import build_model
from repro.sharding import (EL_EDGE_KNOBS, EL_SCALAR_KNOBS,
                            el_edge_dim_axes, el_run_partition_specs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# placement policy (pure — no devices)
# ---------------------------------------------------------------------------


def test_el_edge_dim_axes_tiles_or_replicates():
    sizes = {"pod": 2, "data": 16, "model": 16}
    # 64 edges tile the 32-way (pod, data) edge axes
    assert el_edge_dim_axes(("pod", "data", "model"), sizes, 64) == \
        ("pod", "data")
    # a fleet that does not tile replicates (resolver-style fallback)
    assert el_edge_dim_axes(("pod", "data", "model"), sizes, 3) is None
    # no edge axes at all -> replicate
    assert el_edge_dim_axes(("model",), {"model": 4}, 8) is None
    # single-device edge axes -> nothing to shard over
    assert el_edge_dim_axes(("data", "model"), {"data": 1, "model": 1},
                            8) is None


def test_el_run_partition_specs_data_plane_vs_control_plane():
    from repro.el.events.knobs import ASYNC_KNOB_NAMES
    from repro.el.ingraph import KNOB_NAMES
    edge_spec, knobs = el_run_partition_specs(
        ("data", "model"), {"data": 2, "model": 2}, 8, KNOB_NAMES)
    assert edge_spec == P(("data",))
    # the control plane replicates — every knob, scalar or per-edge
    assert set(knobs) == set(KNOB_NAMES)
    assert all(s == P() for s in knobs.values())
    # the shared knob-layout classification covers both programs' knobs
    assert set(EL_EDGE_KNOBS) < set(KNOB_NAMES)
    assert set(EL_EDGE_KNOBS) < set(ASYNC_KNOB_NAMES)
    assert set(EL_SCALAR_KNOBS) & set(ASYNC_KNOB_NAMES) == \
        {"ucb_c", "budget", "cost_noise", "async_alpha", "event_cap"}
    # non-tiling fleet: edge dim replicated
    edge_spec, _ = el_run_partition_specs(
        ("data", "model"), {"data": 2, "model": 2}, 3, KNOB_NAMES)
    assert edge_spec == P(None)


def test_el_stacked_param_specs_resolver_layout():
    """[E, ...]-stacked params: edge dim over (pod, data); tensor dims by
    the per-arch name+shape resolver (divisible heads -> 'model', classic
    names replicate)."""
    from repro.sharding import el_stacked_param_specs
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # 1-device mesh: every dim replicates (nothing tiles)
    tree = {"w": jax.ShapeDtypeStruct((4, 59, 8), np.float32)}
    specs = el_stacked_param_specs(mesh, 4, tree)
    assert specs["w"] == P(None, None, None)


# ---------------------------------------------------------------------------
# shared fixture
# ---------------------------------------------------------------------------


def _svm_fixture(n=800, n_edges=4, seed=0, budget=900.0, **cfg_kw):
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
    return ol, model, ex, init, ns


def _session(ol, ex, init, ns) -> ELSession:
    return (ELSession(ol, metric_name="accuracy", lr=0.05)
            .with_executor(ex, init_params=init, n_samples=ns))


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


def test_donated_params_buffer_is_invalidated_and_reuse_raises():
    ol, model, ex, _, ns = _svm_fixture()
    init = model.init(jax.random.key(0))
    sess = _session(ol, ex, init, ns)
    rep = sess.run_sync_ingraph(max_rounds=16, donate=True)
    assert rep.n_aggregations > 0
    # the donated buffers are really gone (XLA aliased them into the
    # output params instead of copying the fleet's parameters)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(init))
    # and the session refuses to silently reuse them
    with pytest.raises(RuntimeError, match="donated"):
        sess.run_sync_ingraph(max_rounds=16)


def test_donated_run_is_bit_identical_to_undonated():
    ol, model, ex, init, ns = _svm_fixture()
    base = _session(ol, ex, init, ns).run_sync_ingraph(max_rounds=32)
    fresh = model.init(jax.random.key(0))
    don = _session(ol, ex, fresh, ns).run_sync_ingraph(max_rounds=32,
                                                       donate=True)
    assert base.n_aggregations == don.n_aggregations > 0
    assert [r.metric for r in base.records] == \
        [r.metric for r in don.records]
    assert [r.total_consumed for r in base.records] == \
        [r.total_consumed for r in don.records]
    assert base.arm_pulls == don.arm_pulls

    ol_async = dataclasses.replace(ol, mode="async")
    base = _session(ol_async, ex, init, ns).run_async_ingraph(max_events=48)
    fresh = model.init(jax.random.key(0))
    don = _session(ol_async, ex, fresh, ns).run_async_ingraph(
        max_events=48, donate=True)
    assert base.n_aggregations == don.n_aggregations > 0
    assert [r.metric for r in base.records] == \
        [r.metric for r in don.records]
    assert base.arm_pulls == don.arm_pulls


# ---------------------------------------------------------------------------
# compile-cache identity: mesh and donation are part of the key
# ---------------------------------------------------------------------------


def test_compile_cache_keys_carry_mesh_and_donation_identity():
    ol, model, ex, init, ns = _svm_fixture(n=400)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sess = _session(ol, ex, init, ns)
    r_plain = sess.run_sync_ingraph(max_rounds=16)
    prog_plain = sess._fastpath
    r_mesh = sess.run_sync_ingraph(max_rounds=16, mesh=mesh)
    prog_mesh = sess._fastpath
    # two meshes (None vs a real one) must not share a cache entry ...
    assert prog_mesh is not prog_plain
    assert len(sess._programs) == 2
    # ... and re-running the first must REUSE its entry, not thrash
    sess.run_sync_ingraph(max_rounds=16)
    assert sess._fastpath is prog_plain
    assert len(sess._programs) == 2
    # a second session run on the same mesh object also reuses
    sess.run_sync_ingraph(max_rounds=16, mesh=mesh)
    assert sess._fastpath is prog_mesh
    # donation compiles its own (aliased) executable
    sess.run_sync_ingraph(max_rounds=16, donate=True)
    assert len(sess._programs) == 3
    # on one device the mesh program is the same math — same results
    assert [r.metric for r in r_plain.records] == \
        [r.metric for r in r_mesh.records]


# ---------------------------------------------------------------------------
# sharded bit-identity (subprocess: forced 4-device host, 2x2 debug mesh)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import dataclasses, sys
    import jax, numpy as np
    assert jax.device_count() == 4, jax.devices()
    from repro.config import get_config
    from repro.data import make_wafer_dataset, partition_edges
    from repro.el import ELSession
    from repro.federated import ClassicExecutor
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model

    mode = sys.argv[1]
    batch_k = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    train, test = make_wafer_dataset(n=800, seed=0)
    exp = get_config("svm-wafer")
    model = build_model(exp.model)
    ol = dataclasses.replace(
        exp.ol4el, mode=mode, policy="ol4el", n_edges=4, budget=900.0,
        heterogeneity=4.0, utility="eval_gain", seed=0)
    edges = partition_edges(train, 4, alpha=1.0, seed=0)
    ex = ClassicExecutor(model, edges, test, batch=32, lr=0.05)
    init = model.init(jax.random.key(0))
    ns = [len(e["y"]) for e in edges]

    def run(mesh, cfg=ol):
        s = (ELSession(cfg, metric_name="accuracy", lr=0.05)
             .with_executor(ex, init_params=init, n_samples=ns))
        if mode == "sync":
            return s.run_sync_ingraph(max_rounds=32, mesh=mesh)
        return s.run_async_ingraph(max_events=64, mesh=mesh)

    # the reference is always the replicated K=1 program; an explicit
    # batch_k pins the sharded run's wave width (0 = auto-tuned)
    ol_mesh = (ol if not batch_k
               else dataclasses.replace(ol, async_batch_k=batch_k))
    r0 = run(None)
    r1 = run(make_debug_mesh(2, 2), ol_mesh)
    assert r0.n_aggregations == r1.n_aggregations > 0
    for field in ("metric", "utility", "interval", "total_consumed",
                  "wall_time"):
        a = [getattr(r, field) for r in r0.records]
        b = [getattr(r, field) for r in r1.records]
        assert a == b, (field, a[:4], b[:4])
    assert r0.arm_pulls == r1.arm_pulls
    for pa, pb in zip(jax.tree.leaves(r0.final_params),
                      jax.tree.leaves(r1.final_params)):
        assert np.array_equal(np.asarray(pa), np.asarray(pb))
    print("BIT-IDENTICAL", mode, r0.n_aggregations)
""")


def _run_sharded_subprocess(mode: str, batch_k: int = 0):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    return subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, mode, str(batch_k)],
        capture_output=True, text=True, env=env, timeout=900)


@pytest.mark.slow
def test_sync_sharded_run_bit_identical_to_unsharded_subprocess():
    r = _run_sharded_subprocess("sync")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BIT-IDENTICAL sync" in r.stdout


@pytest.mark.slow
def test_async_sharded_run_bit_identical_to_unsharded_subprocess():
    # batch_k=0 auto-tunes on the 2x2 mesh (min(4, n_edges) = 4), so
    # this also pins sharded K=4 waves == replicated K=1 pops
    r = _run_sharded_subprocess("async")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BIT-IDENTICAL async" in r.stdout


@pytest.mark.slow
def test_async_sharded_k2_waves_bit_identical_to_unsharded_k1():
    """Explicit async_batch_k=2 on the 2x2 debug mesh: partial waves
    (K strictly between 1 and n_edges) against the replicated
    single-event reference."""
    r = _run_sharded_subprocess("async", batch_k=2)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BIT-IDENTICAL async" in r.stdout


# ---------------------------------------------------------------------------
# Pallas-backed K-means local blocks inside the compiled programs
# ---------------------------------------------------------------------------


def _kmeans_session(impl: str) -> ELSession:
    train, test = make_traffic_dataset(n=600)
    exp = get_config("kmeans-traffic")
    model = build_model(exp.model, impl=impl)
    ol = dataclasses.replace(exp.ol4el, mode="sync", policy="ol4el",
                             n_edges=2, budget=500.0, heterogeneity=2.0,
                             utility="param_delta", seed=0)
    edges = partition_edges(train, 2, alpha=2.0)
    ex = ClassicExecutor(model, edges, test, batch=128, lr=1.0)
    return (ELSession(ol, metric_name="f1", lr=1.0)
            .with_executor(ex, init_params=model.init(jax.random.key(1))))


def test_kmeans_pallas_local_block_runs_ingraph_and_matches_jnp():
    """impl='pallas' routes the in-graph local block's E-step through the
    kmeans_assign kernel (interpret mode on CPU) under the program's
    vmap/scan; with identical assignments the Lloyd centers — and the
    whole run — match the jnp path."""
    rep_jnp = _kmeans_session("jnp").run_sync_ingraph(max_rounds=12)
    rep_pal = _kmeans_session("pallas").run_sync_ingraph(max_rounds=12)
    assert rep_pal.n_aggregations == rep_jnp.n_aggregations > 0
    assert rep_pal.final_metric == pytest.approx(rep_jnp.final_metric,
                                                 abs=0.02)
    assert [r.interval for r in rep_pal.records] == \
        [r.interval for r in rep_jnp.records]


def test_kmeans_impl_validation_and_back_compat():
    cfg = get_config("kmeans-traffic").model
    with pytest.raises(ValueError, match="impl"):
        build_model(cfg, impl="cuda")
    assert build_model(cfg, use_kernel=True).impl == "pallas"
    assert build_model(cfg).impl == "jnp"


# ---------------------------------------------------------------------------
# launch plumbing: Auto-axis meshes, no 1x1 fallback, the compile cache
# ---------------------------------------------------------------------------


def test_launch_meshes_have_auto_axes(monkeypatch):
    """``with_sharding_constraint`` only accepts Auto mesh axes, and
    ``jax.make_mesh`` defaults to Explicit: both launch meshes must ask
    for Auto."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    monkeypatch.setenv("REPRO_DEBUG_MESH", "1")
    for mesh in (make_debug_mesh(1, 1), make_production_mesh()):
        assert mesh.axis_names == ("data", "model")
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_train_debug_mesh_on_one_device_is_an_error(monkeypatch):
    import argparse

    from repro.launch.train import _build_mesh
    # a worker that imported repro.launch.dryrun sees its placeholder
    # fleet: pin the count this test is about
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with pytest.raises(SystemExit, match="needs >= 2 devices"):
        _build_mesh(argparse.Namespace(mesh="debug"))


def test_use_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    from repro.launch.hostdev import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
