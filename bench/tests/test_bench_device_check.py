"""The ``device-f32`` check on the CPU at small size, with svm-wafer's
model as a ``jax.numpy`` reference (``tests/data/svm-wafer-jnp.py``): a
sound run passes the cell's committed limits; the planted faults of
``test_bench_faults`` and the control — the reference in the program's
place with one-pass bfloat16 products — fail them."""

import numpy as np
import pytest

from benchtest import (harness, keep_matmul_precision,  # noqa: F401
                       run_small, small, variant)
from test_bench_faults import _plant

CELLS = ("svm-wafer.run-sync", "svm-wafer.run-async")
DEVICE = {"check": "device-f32", "param_gap_of": "update"}


def _jnp(cell):
    """The cell of a configuration that states the device check, with
    svm-wafer's model as a jnp reference: ``(root, name)``."""
    name = "svm-wafer-jnp." + cell.split(".", 1)[1]
    return variant(cell, name, config="svm-wafer-jnp", config_entries=DEVICE,
                   reference="svm-wafer-jnp.py"), name


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_on_the_device_check(cell):
    root, name = _jnp(cell)
    res = run_small(name, root=root)
    assert res["correct"], res["checks"]
    assert res["checks"]["param_gap"]["value"] < 1e-5


@pytest.mark.parametrize("fault", ("frozen", "half", "altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct_on_the_device_check(monkeypatch, cell, fault):
    _plant(monkeypatch, fault)
    root, name = _jnp(cell)
    res = run_small(name, root=root)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_device_control_is_not_correct(cell):
    from benchlib import check, control
    root, name = _jnp(cell)
    _, _, cfg, ref, traffic, limits = harness().load_cell(name, root)
    assert cfg["check"] == "device-f32" and hasattr(ref, "edge_step")
    cfg.update(small(cell)["config"])
    nums = control.readings(cfg, ref, traffic, seed=5, n=3)
    assert not check.verdict(nums, limits["limits"]), nums
    # the control departs by rounding, not by a fault: every decision
    # and count is the reference's own
    assert nums["ledger_gap"] == 0.0 and nums["param_gap"] > 6e-5


def test_param_gap_of_an_update():
    from benchlib.check import param_gap
    init = {"w": np.full(4, 10.0), "b": np.zeros(2)}
    ref = {"w": init["w"] + [1.0, 0.0, 0.0, 0.0], "b": np.array([3.0, 4.0])}
    prog = {"w": ref["w"] + [0.0, 0.5, 0.0, 0.0], "b": ref["b"]}
    # against the weights the gap is 0.5 / |w| = 0.5 / 20.02...; against
    # the update it is 0.5 / max(|dw| = 1, median(1, 5) = 3)
    assert param_gap(prog, ref) == pytest.approx(
        0.5 / np.linalg.norm(ref["w"]))
    assert param_gap(prog, ref, init) == pytest.approx(0.5 / 3.0)
