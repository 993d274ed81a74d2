"""granite-4.0-h-micro edges in the compiled sync engine, at smoke size on
the CPU (2 layers: Mamba-2 then NoPE GQA, d 256, vocabulary 512, 64
tokens a row).

The model is compared on seeded weights with the plain reference the
benchmark checks it by (``bench/configs/granite-4.0-h-micro.py``): the
Granite multipliers and NoPE, the logits, the loss and one SGD step;
then ``run_sync_ingraph`` of five rounds with the reference's replay, on
one device and, in a child process, on four forced host devices, where
the sharded run must equal the unsharded one bit for bit and its program
must hold no all-gather of the ``[E, ...]`` edge stack.  The front doors
that do not run a language model refuse it.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

#: the smoke model as the benchmark's configuration names it
SMOKE = {"variant": "smoke", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "mamba_d_state": 16, "mamba_d_head": 32, "mamba_chunk_size": 32,
         "mamba_n_heads": 16, "layer_types": ["mamba", "attention"],
         "num_hidden_layers": 2, "vocab_size": 512,
         "published": {"num_hidden_layers": 2, "vocab_size": 512},
         "data": {"kind": "tokens", "seq_len": 64, "sequences": 8,
                  "held_out": 2, "zipf": 1.1, "seed": 0}}

#: float32 on the CPU, the program's chunked SSD (Pallas kernel,
#: interpreted) against the reference's quadratic form: the logits and
#: loss agree to float32 rounding of a sum over a few hundred terms
FWD_TOL = 2e-5
#: one SGD step's update, relative to the update's own norm: the
#: update is ~1e-3 of the weights, so the float32 rounding of the
#: weights (~6e-8 of them) is ~1e-4 of the update
STEP_TOL = 1e-3


def _bench():
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import load_module, load_named
    return load_module, load_named


def _setup():
    load_module, load_named = _bench()
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = dict(json.load(f), **SMOKE)
    ref = load_module(os.path.join(BENCH, "configs",
                                   "granite-4.0-h-micro.py"),
                      "granite_reference_under_test")
    lm = load_named("programs", "lm")
    edges, held = load_named("datasets", "tokens").make(cfg)
    M = load_named("checks", "device-f32-edges").Products(False)
    return cfg, ref, lm, edges, held, M


@pytest.fixture(scope="module")
def setup():
    return _setup()


def _model(lm, cfg, **replace):
    from repro.models import build_model
    mc = dataclasses.replace(lm.model_config(cfg), **replace)
    return lm.NamedLeaves(build_model(mc, use_ssd_kernel=True))


def test_logits_and_loss_match_the_reference(setup):
    cfg, ref, lm, edges, _, M = setup
    model = _model(lm, cfg)
    p = {k: jnp.asarray(v) for k, v in ref.init(cfg, 3).items()}
    toks = jnp.asarray(edges[1]["tokens"][:2])
    with jax.default_matmul_precision("highest"):
        got, _ = model.lm.forward(model.tree(p), toks[:, :-1])
        want = ref.logits(M, cfg, p, toks[:, :-1])
        np.testing.assert_allclose(got, want, rtol=FWD_TOL, atol=FWD_TOL)
        np.testing.assert_allclose(model.token_loss(p, toks),
                                   ref.loss(M, cfg, p, toks),
                                   rtol=FWD_TOL)


def test_multipliers_and_nope_are_the_models(setup):
    """Each Granite multiplier and NoPE changes the logits, and the
    program's value of each is the one the reference reads: without one
    of them the model no longer matches the reference."""
    cfg, ref, lm, edges, _, M = setup
    p = {k: jnp.asarray(v) for k, v in ref.init(cfg, 4).items()}
    toks = jnp.asarray(edges[0]["tokens"][:2, :-1])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(M, cfg, p, toks))
        for change in ({"embedding_multiplier": 1.0},
                       {"residual_multiplier": 1.0},
                       {"attention_multiplier": 0.0},
                       {"logits_scaling": 1.0}, {"rope": True}):
            model = _model(lm, cfg, **change)
            got, _ = model.lm.forward(model.tree(p), toks)
            assert np.max(np.abs(np.asarray(got) - want)) > 100 * FWD_TOL, \
                change


def test_one_sgd_step_matches_the_reference(setup):
    cfg, ref, lm, edges, _, M = setup
    model = _model(lm, cfg)
    init = ref.init(cfg, 5)
    p = {k: jnp.asarray(v) for k, v in init.items()}
    toks = jnp.asarray(edges[2]["tokens"][:2])
    with jax.default_matmul_precision("highest"):
        got, _ = model.local_step(p, {"tokens": toks}, cfg["lr"])
        want = ref.edge_step(M, cfg, p, toks)
    for k in want:
        d_want = np.asarray(want[k], np.float64) - init[k]
        d_got = np.asarray(got[k], np.float64) - init[k]
        assert np.linalg.norm(d_got - d_want) <= STEP_TOL * max(
            np.linalg.norm(d_want), 1e-6), k


def _executor(lm, cfg, edges, held):
    from repro.federated import TokenLMExecutor
    return TokenLMExecutor(_model(lm, cfg), [e["tokens"] for e in edges],
                           held["tokens"], batch=cfg["batch"], lr=cfg["lr"])


def _run_cfg(cfg, mode="sync", **kw):
    from repro.config import OL4ELConfig
    return OL4ELConfig(
        max_interval=cfg["max_interval"], mode=mode, cost_model="fixed",
        policy="ol4el", budget=cfg["budget"], comp_cost=cfg["comp_cost"],
        comm_cost=cfg["comm_cost"], heterogeneity=cfg["heterogeneity"],
        utility="eval_gain", n_edges=cfg["n_edges"], seed=11, **kw)


@pytest.mark.parametrize("door", ("async", "sweep", "fleet"))
def test_front_doors_that_run_no_language_model_refuse_it(setup, door):
    """Async, a sweep and the fleet refuse a token executor before any
    tracing, each with the support matrix."""
    from repro.el import ELSession
    from repro.el.fleet import FleetServer, TenantRun
    from repro.el.sweep import SweepSpec
    cfg, _, lm, edges, held, _ = setup
    ex = _executor(lm, cfg, edges, held)
    run_cfg = _run_cfg(cfg, mode="async" if door == "async" else "sync")
    s = ELSession(run_cfg, metric_name="neg_loss").with_executor(
        ex, init_params={})
    call = {"async": s.run_async_ingraph,
            "sweep": lambda: s.sweep(SweepSpec(seeds=(0, 1))),
            "fleet": lambda: FleetServer().submit(TenantRun(
                cfg=run_cfg, executor=ex, init_params={}))}[door]
    with pytest.raises(ValueError, match="only as a single sync run"):
        call()


def test_host_sync_loop_runs_a_token_executor(setup):
    """The host loop takes the same executor (``local_train``): five
    rounds, a finite held-out loss that the edges' steps lowered."""
    from repro.el import ELSession
    cfg, ref, lm, edges, held, _ = setup
    ex = _executor(lm, cfg, edges, held)
    init = {k: jnp.asarray(v) for k, v in ref.init(cfg, 2).items()}
    rep = ELSession(_run_cfg(cfg), metric_name="neg_loss").with_executor(
        ex, init_params=init).run_sync()
    assert rep.n_aggregations == 5
    assert np.isfinite(rep.final_metric)
    assert rep.final_metric > ex.evaluate(init)["neg_loss"]


def test_stacked_specs_carry_the_scanned_group_dim():
    """An ``[E, n_groups, ...]`` leaf of a scanned LM keeps its group
    dim unsharded and its own dims by the resolver; other leaves are
    unchanged."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import el_stacked_param_specs

    class Mesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 2), dtype=object)

    tree = {"embed": jax.ShapeDtypeStruct((8, 512, 256), jnp.float32),
            "groups": {"sub0": {"mix": {"in_proj": jax.ShapeDtypeStruct(
                (8, 3, 256, 1040), jnp.float32)}}},
            "w": jax.ShapeDtypeStruct((8, 59, 8), jnp.float32)}
    specs = el_stacked_param_specs(Mesh(), 8, tree)
    assert specs["groups"]["sub0"]["mix"]["in_proj"] == P(
        ("data",), None, None, "model")
    assert specs["embed"] == P(("data",), "model", None)
    assert specs["w"] == P(("data",), None, None)


CHILD = r"""
import json, sys
import numpy as np
sys.path[:0] = [sys.argv[1]]
import test_el_lm as t
cfg, ref, lm, edges, held, M = t._setup()
out = t.run_both(cfg, ref, lm, edges, held)
print(json.dumps(out))
"""


def run_both(cfg, ref, lm, edges, held):
    """One run mesh-less and one on a 4x1 mesh of the same executor and
    seed, each replayed by the benchmark's check; the sharded program's
    all-gathers; the two runs' final parameters compared bit for bit."""
    import jax

    from repro.el import ELSession
    from repro.el.ingraph import sync_knobs
    from repro.launch.mesh import make_debug_mesh
    _, load_named = _bench()
    from benchlib import check, drive
    ex = _executor(lm, cfg, edges, held)
    wl = load_named("checks", "device-f32-edges").workload(cfg, ref)
    init = ref.init(cfg, 9)
    runs = {}
    for name, mesh in (("one", None), ("four", make_debug_mesh(4, 1))):
        s = ELSession(_run_cfg(cfg), metric_name="neg_loss")
        s.with_executor(ex, init_params=init,
                        n_samples=[len(e["tokens"]) for e in edges])
        # the sharded run holds its default contract: the exchange's
        # collectives, no all-reduce, the donated params aliased
        rep = s.run_sync_ingraph(max_rounds=64, mesh=mesh,
                                 contract=mesh is not None)
        rec = {"n": rep.n_aggregations,
               "interval": np.array([r.interval for r in rep.records]),
               "metric": np.array([r.metric for r in rep.records]),
               "utility": np.array([r.utility for r in rep.records]),
               "consumed": np.array([r.total_consumed for r in rep.records]),
               "wall": np.array([r.wall_time for r in rep.records]),
               "edge": None, "final_params": rep.final_params,
               "final_metric": rep.final_metric}
        run = drive.run_spec(cfg, {}, "sync", {"seed": 11}, 64)
        nums = check.replay_numbers(dict(cfg, param_gap_of="update"), wl,
                                    {"run": run, "record": rec,
                                     "init": init})
        runs[name] = {"numbers": nums, "n": rec["n"],
                      "final": np.concatenate([np.ravel(v) for v in
                                               rep.final_params.values()])}
        if mesh is not None:
            hlo = s._fastpath.lower(init, jax.random.key(0),
                                    sync_knobs(s.cfg)).compile().as_text()
    # an all-gather's result that is an edge stack of a leaf, [E, *leaf],
    # and not itself the shape of a leaf (conv_w [4, C] is conv_b [C]
    # stacked four times over)
    leaf_shapes = {tuple(np.shape(v)) for v in init.values()}
    gathers = [line for line in hlo.splitlines() if "all-gather(" in line]
    stacked = [g for g in gathers for shp in leaf_shapes
               if (cfg["n_edges"],) + shp not in leaf_shapes
               and "[" + ",".join(map(str, (cfg["n_edges"],) + shp)) + "]"
               in g.split("all-gather(")[0]]
    return {"numbers": {k: v["numbers"] for k, v in runs.items()},
            "rounds": {k: v["n"] for k, v in runs.items()},
            "identical": bool(np.array_equal(runs["one"]["final"],
                                             runs["four"]["final"])),
            "all_gathers": len(gathers), "stacked_gathers": len(stacked),
            "all_to_all": hlo.count("all-to-all(")}


def test_sync_runs_match_the_reference_on_one_and_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", CHILD,
                        os.path.dirname(os.path.abspath(__file__))],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    with open(os.path.join(BENCH, "cells",
                           "granite-4.0-h-micro.run-sync.json")) as f:
        limits = json.load(f)["limits"]
    for name in ("one", "four"):
        nums = got["numbers"][name]
        assert got["rounds"][name] >= 3
        # the replay follows the run's arms and counts exactly
        assert nums["select_gap"] == 0.0 and nums["ledger_gap"] == 0.0
        assert all(nums[k] <= limits[k] for k in limits), nums
    assert got["identical"], got
    assert got["all_to_all"] > 0 and got["stacked_gathers"] == 0, got
