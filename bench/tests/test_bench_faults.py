"""Each fault a cell can have, planted under a full run of the harness
(its look for a chip skipped): ``correct`` must come out false.

  frozen   a local step that returns its state unchanged;
  half     half of each minibatch left out, the mean taken over the rest;
  altered  an answer altered where it is produced: the first recorded
           interval of a run (or sweep cell) changed.

The exchange between chips does not exist in these one-chip cells."""

import numpy as np
import pytest

from benchtest import CELLS, keep_matmul_precision, run_small  # noqa: F401


def _plant(monkeypatch, fault):
    from repro.models.classic import KMeans, LinearSVM
    if fault in ("frozen", "half"):
        for cls in (LinearSVM, KMeans):
            orig = cls.local_step

            def step(self, p, b, lr, orig=orig):
                if fault == "frozen":
                    return p, {}
                half = {k: v[: v.shape[0] // 2] for k, v in b.items()}
                return orig(self, p, half, lr)

            monkeypatch.setattr(cls, "local_step", step)
        return
    import repro.el.fleet.cohort as cohort
    import repro.el.session as session
    import repro.el.sweep.engine as engine

    def bump(x):
        x = np.array(x, copy=True)
        x[..., 0] = x[..., 0] % 10 + 1
        return x

    def records(out, lo, hi, orig=session.records_from_out):
        out = dict(out)
        if lo == 0 and hi > 0:
            out["interval"] = bump(out["interval"])
        return orig(out, lo, hi)

    def sweep(program, params, cfgs, orig=engine.run_sweep_program):
        p, out = orig(program, params, cfgs)
        out["interval"] = bump(out["interval"])
        return p, out

    monkeypatch.setattr(session, "records_from_out", records)
    monkeypatch.setattr(cohort, "records_from_out", records)
    monkeypatch.setattr(engine, "run_sweep_program", sweep)


@pytest.mark.parametrize("fault", ("frozen", "half", "altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(monkeypatch, cell, fault):
    _plant(monkeypatch, fault)
    res = run_small(cell)
    assert not res["correct"], res["checks"]
