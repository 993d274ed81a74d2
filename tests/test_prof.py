"""repro.obs.prof — the performance observatory's static half: HLO
collective census parsing, ProgramProfile extraction via AOT lowering,
declarative CollectiveContract checks (census + donation aliasing), and
the profile's journey through the session/fleet wiring into
``ELReport.telemetry["profile"]`` and the ProgramCache."""

import dataclasses
import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.el import ELSession, FleetServer, TenantRun
from repro.launch.classic import classic_fixture
from repro.obs import prof as obs_prof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def svm():
    return classic_fixture("svm-wafer", samples=128, n_edges=4,
                           alpha=100.0, data_seed=0)


def _cfg(fx, mode, budget, seed=0):
    return dataclasses.replace(
        fx["exp"].ol4el, mode=mode, policy="ol4el", n_edges=4,
        utility=fx["utility"], budget=float(budget), seed=seed)


def _session(fx, cfg, init=None):
    return (ELSession(cfg, metric_name=fx["metric"])
            .with_executor(fx["executor"],
                           init_params=(fx["init_params"]
                                        if init is None else init),
                           n_samples=(fx["n_samples"]
                                      if cfg.mode == "sync" else None)))


# -- HLO census parsing -----------------------------------------------------


def test_type_bytes():
    assert obs_prof._type_bytes("f32[4,8]") == 4 * 8 * 4
    assert obs_prof._type_bytes("f32[8]{0}") == 32
    # tuple results sum their elements
    assert obs_prof._type_bytes("(f32[8]{0}, u32[2])") == 32 + 8
    assert obs_prof._type_bytes("pred[]") == 1
    assert obs_prof._type_bytes("token[]") == 0


def test_parse_collectives_synthetic_hlo():
    hlo = textwrap.dedent("""\
        ENTRY %main {
          %ag1 = f32[4,480]{1,0} all-gather(f32[1,480]{1,0} %p), dimensions={0}
          %ag2 = f32[4,480]{1,0} all-gather(f32[1,480]{1,0} %q), dimensions={0}
          %ar = f32[10]{0} all-reduce(f32[10]{0} %r), to_apply=%sum
          %add = f32[10]{0} add(f32[10]{0} %ar, f32[10]{0} %r)
        }
    """)
    census = obs_prof.parse_collectives(hlo)
    assert census["per_op"]["all-gather"]["count"] == 2
    assert census["per_op"]["all-gather"]["bytes"] == 2 * 4 * 480 * 4
    assert census["per_op"]["all-reduce"]["count"] == 1
    assert census["per_op"]["all-reduce"]["bytes"] == 40
    assert census["bytes_per_device"] == 2 * 4 * 480 * 4 + 40


def test_parse_collectives_counts_start_once_skips_done():
    hlo = ("  %s = f32[16]{0} all-gather-start(f32[4]{0} %x)\n"
           "  %d = f32[16]{0} all-gather-done(f32[16]{0} %s)\n")
    census = obs_prof.parse_collectives(hlo)
    # the async -start form is the collective; -done is bookkeeping
    assert census["per_op"]["all-gather"]["count"] == 1
    assert obs_prof.parse_collectives("no collectives here")["per_op"] == {}


def test_parse_collectives_tpu_tiled_layouts():
    """TPU HLO prints tiled layouts (``{1,0:T(8,128)S(1)}``) in result
    types; the census must still see those collectives, and a fusion
    that merely consumes one is not a collective."""
    hlo = textwrap.dedent("""\
        %all-gather.5 = f32[16,8]{1,0:T(8,128)S(1)} all-gather(%g.1), channel_id=2, dimensions={0}, metadata={op_name="jit(program)/while/body/sharding_constraint"}
        %all-gather.10 = f32[16,59,8]{1,2,0:T(8,128)S(1)} all-gather(%g.2), channel_id=1, frontend_attributes={async_collective_name="all-gather-start"}
        %f = f32[59,8]{0,1:T(8,128)S(1)} fusion(%all-gather.10, %c), kind=kLoop, calls=%fused
        %ar = (f32[8]{0:T(128)}, f32[8]{0:T(128)}) all-reduce-start(%a, %b), to_apply=%sum
    """)
    census = obs_prof.parse_collectives(hlo)
    assert census["per_op"]["all-gather"] == {
        "count": 2, "bytes": 16 * 8 * 4 + 16 * 59 * 8 * 4}
    assert census["per_op"]["all-reduce"] == {"count": 1, "bytes": 64}


# -- ProgramProfile + contracts (pure) --------------------------------------


def _profile(**kw):
    return obs_prof.ProgramProfile(**kw)


def test_profile_census_accessors_and_json():
    p = _profile(collectives={"all-gather": {"count": 2, "bytes": 100}},
                 collective_bytes=100, alias_bytes=0, flops=1e6)
    assert p.collective_count("all-gather") == 2
    assert p.collective_count("all-reduce") == 0
    assert p.total_collectives == 2
    d = p.to_json()
    assert d["collectives"]["all-gather"]["count"] == 2
    assert d["errors"] == []
    assert "all-gather=2" in p.summary()


def test_collective_contract_check_and_enforce():
    p = _profile(collectives={"all-gather": {"count": 2, "bytes": 100}},
                 alias_bytes=0)
    ok = obs_prof.CollectiveContract(
        "ok", counts={"all-gather": 2, "all-reduce": 0}, alias_bytes=0)
    assert ok.check(p) == []
    ok.enforce(p)   # no raise

    rng = obs_prof.CollectiveContract(
        "rng", counts={"all-gather": (1, 16)})
    assert rng.check(p) == []
    bad_rng = obs_prof.CollectiveContract(
        "bad", counts={"all-gather": (3, 16)})
    assert any("outside [3, 16]" in m for m in bad_rng.check(p))

    bad_exact = obs_prof.CollectiveContract(
        "bad", counts={"all-reduce": 1})
    with pytest.raises(obs_prof.ContractViolation, match="all-reduce"):
        bad_exact.enforce(p)

    alias = obs_prof.CollectiveContract("alias", alias_bytes=1920)
    assert any("1920" in m for m in alias.check(p))
    # an unavailable alias analysis is itself a violation
    assert any("unavailable" in m
               for m in alias.check(_profile(alias_bytes=None)))


def test_contract_violated_by_incomplete_profile():
    """A census the profile never took must not read as "0 collectives":
    the replicated contract fails on a profile whose HLO was unreadable."""
    unread = _profile(alias_bytes=0, errors=("hlo: no text",))
    contract = obs_prof.default_contract()
    assert contract.check(_profile(alias_bytes=0)) == []
    assert contract.check(unread) == ["profile incomplete: hlo: no text"]
    with pytest.raises(obs_prof.ContractViolation, match="incomplete"):
        contract.enforce(unread)


def test_parse_custom_calls_synthetic_hlo():
    hlo = textwrap.dedent("""\
        %k1 = (s32[256,1]{1,0}) custom-call(f32[256,64]{1,0} %x), custom_call_target="tpu_custom_call", backend_config={}
        %k2 = (s32[256,1]{1,0}) custom-call(f32[256,64]{1,0} %y), custom_call_target="tpu_custom_call"
        %s = f32[4]{0} custom-call(f32[4]{0} %z), custom_call_target="Sharding"
    """)
    assert obs_prof.parse_custom_calls(hlo) == {"tpu_custom_call": 2,
                                                "Sharding": 1}
    assert obs_prof.parse_custom_calls("no kernels here") == {}


def test_default_contract_shapes():
    # no mesh: a replicated program may issue NO collectives, alias 0
    c = obs_prof.default_contract()
    assert c.counts == {op: 0 for op in obs_prof.COLLECTIVES}
    assert c.alias_bytes == 0
    assert "replicated" in c.name

    # multi-device mesh: gather-before-reduce (the mesh is only read
    # for .devices, so a 2x2 stand-in exercises the sharded branch)
    mesh = types.SimpleNamespace(devices=np.empty((2, 2), dtype=object))
    c = obs_prof.default_contract(mesh=mesh, mode="sync")
    assert c.counts["all-gather"] == obs_prof.DEFAULT_GATHER_RANGE
    assert c.counts["all-reduce"] == 0
    assert c.counts["reduce-scatter"] == 0
    assert "sync-sharded" in c.name

    # donation: the whole param tree must be aliased
    c = obs_prof.default_contract(mesh=mesh, donated=True,
                                  param_bytes=1920)
    assert c.alias_bytes == 1920 and c.name.endswith("-donated")
    # donated but size unknown: aliasing unconstrained rather than wrong
    assert obs_prof.default_contract(donated=True).alias_bytes is None


def test_param_tree_bytes():
    tree = {"w": jax.ShapeDtypeStruct((4, 59), jnp.float32),
            "b": np.zeros((3,), np.int32)}
    assert obs_prof.param_tree_bytes(tree) == 4 * 59 * 4 + 3 * 4
    # a placed array counts its on-device size (== logical on the CPU;
    # a TPU adds its tile padding)
    placed = jax.tree.map(jnp.asarray, tree["b"])
    assert obs_prof.param_tree_bytes(placed) == \
        placed.addressable_data(0).on_device_size_in_bytes() == 12


# -- live extraction (AOT lower/compile on the real backend) ----------------


def test_profile_jit_tiny_fn():
    jfn = jax.jit(lambda x: (x @ x.T).sum())
    prof = obs_prof.profile_jit(jfn, jnp.ones((8, 8), jnp.float32))
    # single-device: census must be empty, nothing aliased
    assert prof.total_collectives == 0
    assert prof.collective_bytes == 0
    assert prof.hlo_lines and prof.hlo_lines > 0
    assert prof.backend == jax.default_backend()
    assert not prof.donated
    if not prof.errors:      # backends may withhold individual analyses
        assert prof.flops is not None and prof.flops > 0
        assert prof.peak_live_bytes == (prof.argument_bytes
                                        + prof.output_bytes
                                        + prof.temp_bytes
                                        - prof.alias_bytes)


# -- session wiring: profiles attach, cache once, contracts gate ------------


def test_session_sync_profile_attaches_and_caches_once(svm):
    s = _session(svm, _cfg(svm, "sync", budget=600.0))
    rep = s.run_sync_ingraph(max_rounds=16, profile=True, contract=True)
    prof = rep.telemetry["profile"]
    assert prof["collectives"] == {}        # 1 device: no collectives
    assert prof["alias_bytes"] == 0         # nothing donated
    assert prof["donated"] is False
    assert s.compile_cache.stats()["profiled"] == 1
    # the second dispatch reuses the stored profile (no re-AOT)
    rep2 = s.run_sync_ingraph(max_rounds=16, profile=True)
    assert rep2.telemetry["profile"] == prof
    assert s.compile_cache.stats()["profiled"] == 1
    # profiling stays opt-in: an unprofiled run carries no profile key
    rep3 = _session(svm, _cfg(svm, "sync", budget=600.0)).run_sync_ingraph(
        max_rounds=16)
    assert "profile" not in (rep3.telemetry or {})


def test_session_async_profile_attaches(svm):
    s = _session(svm, _cfg(svm, "async", budget=600.0))
    rep = s.run_async_ingraph(max_events=32, profile=True, contract=True)
    prof = rep.telemetry["profile"]
    assert prof["collectives"] == {} and prof["alias_bytes"] == 0


def test_session_contract_violation_raises_before_results_leak(svm):
    s = _session(svm, _cfg(svm, "sync", budget=600.0))
    impossible = obs_prof.CollectiveContract(
        "impossible", counts={"all-gather": (5, 99)})
    with pytest.raises(obs_prof.ContractViolation, match="impossible"):
        s.run_sync_ingraph(max_rounds=16, contract=impossible)


def test_session_donated_profile_satisfies_alias_contract(svm):
    init = jax.tree.map(jnp.array, svm["init_params"])   # donatable copy
    s = _session(svm, _cfg(svm, "sync", budget=600.0), init=init)
    rep = s.run_sync_ingraph(max_rounds=16, donate=True, profile=True,
                             contract=True)
    prof = rep.telemetry["profile"]
    assert prof["donated"] is True
    assert prof["alias_bytes"] == obs_prof.param_tree_bytes(
        svm["init_params"])


# -- fleet wiring: cohort profiles land on tenant reports -------------------


def test_fleet_profile_attaches_to_tenant_reports(svm):
    server = FleetServer(n_slots=2, rounds_per_wave=4, profile=True)
    for s, b in enumerate((600.0, 900.0)):
        server.submit(TenantRun(cfg=_cfg(svm, "sync", budget=b, seed=s),
                                executor=svm["executor"],
                                tenant_id=f"t{s}",
                                metric_name=svm["metric"],
                                n_samples=svm["n_samples"],
                                init_params=svm["init_params"],
                                max_rounds=16))
    reports = server.drain()
    server.close()
    assert len(reports) == 2
    for rep in reports.values():
        prof = rep.telemetry["profile"]
        # the cohort step donates its stacked carry
        assert prof["donated"] is True
        assert prof["errors"] == []


# -- 2x2 sharded contract (subprocess: forced 4-device host) ----------------

_SHARDED_CONTRACT_SCRIPT = textwrap.dedent("""
    import dataclasses
    import jax, numpy as np
    assert jax.device_count() == 4, jax.devices()
    from repro.config import get_config
    from repro.data import make_wafer_dataset, partition_edges
    from repro.el import ELSession
    from repro.federated import ClassicExecutor
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.obs import prof as obs_prof

    train, test = make_wafer_dataset(n=512, seed=0)
    exp = get_config("svm-wafer")
    model = build_model(exp.model)
    ol = dataclasses.replace(
        exp.ol4el, mode="sync", policy="ol4el", n_edges=4, budget=600.0,
        heterogeneity=4.0, utility="eval_gain", seed=0)
    edges = partition_edges(train, 4, alpha=1.0, seed=0)
    ex = ClassicExecutor(model, edges, test, batch=32, lr=0.05)
    init = model.init(jax.random.key(0))
    param_bytes = obs_prof.param_tree_bytes(init)

    sess = (ELSession(ol, metric_name="accuracy", lr=0.05)
            .with_executor(ex, init_params=init,
                           n_samples=[len(e["y"]) for e in edges]))
    # contract=True enforces the sync-sharded-donated default contract
    # at dispatch time; a partial-sum reordering or dropped aliasing
    # makes this line raise ContractViolation
    rep = sess.run_sync_ingraph(max_rounds=24, mesh=make_debug_mesh(2, 2),
                                donate=True, profile=True, contract=True)
    prof = rep.telemetry["profile"]
    assert prof["collectives"].get("all-gather", {}).get("count", 0) >= 1, \\
        prof["collectives"]
    for op in ("all-reduce", "reduce-scatter", "all-to-all"):
        assert op not in prof["collectives"], prof["collectives"]
    assert prof["alias_bytes"] == param_bytes, \\
        (prof["alias_bytes"], param_bytes)
    assert prof["collective_bytes"] > 0
    print("CONTRACT-OK", prof["collectives"]["all-gather"]["count"],
          prof["alias_bytes"])
""")


@pytest.mark.slow
def test_sync_sharded_2x2_contract_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    r = subprocess.run([sys.executable, "-c", _SHARDED_CONTRACT_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CONTRACT-OK" in r.stdout
