"""The correctness check on the CPU at small size: a sound run of every
cell passes the committed limits, and the control — the plain reference
in the program's place with one-pass bfloat16 products — fails them."""

import json
import os

import numpy as np
import pytest

from benchtest import (CELLS, checkout, harness,  # noqa: F401
                       keep_matmul_precision, run_small, small)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    from benchlib import check, control
    _, _, cfg, ref, traffic, limits = harness().load_cell(cell,
                                                          checkout(cell))
    ov = small(cell)
    cfg.update(ov["config"])
    if traffic["kind"] == "sweep":
        traffic.update(grid=ov["traffic"]["grid"], seeds_per_call=2)
    nums = control.readings(cfg, ref, traffic, seed=5, n=3)
    assert not check.verdict(nums, limits["limits"]), nums


def test_every_cell_has_limits_for_every_number():
    from benchlib.check import NUMBERS
    for cell in CELLS:
        with open(os.path.join(checkout(cell), "bench", "cells",
                               cell + ".json")) as f:
            lim = json.load(f)
        assert set(lim["limits"]) == set(NUMBERS)
        assert int(lim["sample"]) >= 1


def _kmeans_replay_case(width, budget=800.0):
    """A small kmeans-traffic run of the float64 reference, the first
    E-step point it meets within ``width`` (a tie by that width) and the
    workload that replays it."""
    from benchlib import data, elref
    from benchlib.control import run_specs
    from benchlib.prec import F64
    cell = "kmeans-traffic.sweep-sync"
    _, _, cfg, ref, traffic, limits = harness().load_cell(cell,
                                                          checkout(cell))
    cfg.update(small(cell)["config"])
    traffic.update(grid={"heterogeneity": [3.0], "budget": [budget]},
                   seeds_per_call=1)
    run = run_specs(cfg, traffic, seed=11, n=1)[0]
    init = ref.init(cfg, 5)
    edges, test = data.make(cfg)
    wl = elref.Workload(cfg, ref, edges, test, F64)
    wl.ties = elref.Ties(width)
    elref.simulate(wl, dict(run, init=init))
    first = wl.ties.met[0]
    wl.ties = None
    return cfg, ref, wl, run, init, first, limits["limits"]


def _program_run(wl, run, init, flips):
    """The record of a run whose E-step assigns ``flips`` the other way,
    its parameters in float32 as a program's are."""
    from benchlib import elref
    wl.ties = elref.Ties(1.0, flips)
    try:
        rec = elref.simulate(wl, dict(run, init=init))["record"]
    finally:
        wl.ties = None
    rec["final_params"] = {k: np.asarray(v, np.float32)
                           for k, v in rec["final_params"].items()}
    return {"run": run, "record": rec, "init": init}


def test_a_tie_resolved_the_other_way_is_followed():
    """A run that assigns a tied point to its second-nearest centroid is
    compared with the replay that does the same; without the tie width
    the same run reads far off."""
    from benchlib import check
    cfg, _, wl, run, init, first, limits = _kmeans_replay_case(0.05)
    row = _program_run(wl, run, init, {first})
    wl.tie_width = 0.05
    got = check.replay_numbers(cfg, wl, row)
    assert got["ties_followed"] >= 1 and check.verdict(got, limits), got
    assert got["param_gap"] < 1e-6
    wl.tie_width = None
    plain = check.replay_numbers(cfg, wl, row)
    assert plain["ties_followed"] == 0 and plain["param_gap"] > 1e-4
    assert not check.verdict(plain, limits), plain


def test_a_point_off_any_tie_assigned_the_other_way_is_not_correct():
    """A wrong assignment where the distances are far apart is a fault:
    no branch follows it, at the reference's own tie width."""
    from benchlib import check
    cfg, ref, wl, run, init, first, limits = _kmeans_replay_case(0.05)
    assert wl.tie_width == ref.TIE_WIDTH
    far = (0, 0) if first != (0, 0) else (0, 1)
    row = _program_run(wl, run, init, {far})
    got = check.replay_numbers(cfg, wl, row)
    assert got["ties_followed"] == 0 and not check.verdict(got, limits), got


def test_following_no_tie_replays_as_the_plain_argmin():
    """With no tie given the other way, the replay that logs ties is the
    plain replay bit for bit."""
    from benchlib import elref
    cfg, _, wl, run, init, _, _ = _kmeans_replay_case(0.05)
    row = _program_run(wl, run, init, ())
    plain = elref.simulate(wl, dict(run, init=init), row["record"])
    logged, met = elref.replay(wl, dict(run, init=init), row["record"])
    assert plain["select_gap"] == logged["select_gap"]
    for k in ("interval", "metric", "utility", "consumed", "wall"):
        assert np.array_equal(plain["record"][k], logged["record"][k],
                              equal_nan=True)
    assert np.array_equal(plain["record"]["final_params"]["centers"],
                          logged["record"]["final_params"]["centers"])


def test_a_tie_the_run_resolved_as_the_reference_is_not_followed():
    """Where the run assigned every tie as the reference does, the plain
    branch is compared, on all five numbers, even where an early tie
    followed the other way ends near the same parameters (a run of ~37
    rounds, whose final centroids forget a step's flip)."""
    from benchlib import check
    cfg, _, wl, run, init, _, _ = _kmeans_replay_case(0.05, budget=5005.0)
    row = _program_run(wl, run, init, ())
    wl.tie_width = 0.05
    got = check.replay_numbers(cfg, wl, row)
    wl.tie_width = None
    assert got == check.replay_numbers(cfg, wl, row)
    assert got["ties_followed"] == 0
