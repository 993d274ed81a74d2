"""The correctness check on the CPU at small size: a sound run of every
cell passes the committed limits, and the control — the plain reference
in the program's place with one-pass bfloat16 products — fails them."""

import json
import os

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
