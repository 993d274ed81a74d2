"""A cell on four chips, on four forced host devices in a child process:
svm-wafer's run-sync at small size runs sharded over a mesh of the four
(16 edges' stand-in: 4 edges, one a chip), its records are bit for bit
those of the same cell on one chip for the same seed (the engine's
sharded contract), and the memory reading covers every chip.  The
four-chip cell is added to a copy of the benchmark by files alone."""

import json
import os
import subprocess
import sys

from benchtest import BENCH, ROOT

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1]]
import numpy as np
import benchtest
from benchlib import program
import repro.el.session as session

records, meshes, chips = {1: [], 4: []}, {1: set(), 4: set()}, [1]
record = program.record_from_report
program.record_from_report = lambda rep: (
    records[chips[0]].append(record(rep)) or records[chips[0]][-1])
run = session.ELSession.run_sync_ingraph

def spy(self, *a, mesh=None, **k):
    meshes[chips[0]].add(None if mesh is None else str(dict(mesh.shape)))
    return run(self, *a, mesh=mesh, **k)

session.ELSession.run_sync_ingraph = spy
four = benchtest.variant("svm-wafer.run-sync", "svm-wafer.four-chips.run-sync",
                        chips=4)
out = {}
for n, cell, root in ((1, "svm-wafer.run-sync", None),
                      (4, "svm-wafer.four-chips.run-sync", four)):
    chips[0] = n
    res = benchtest.run_small(cell, seed=2**31 + 91, seconds=1.0, root=root)
    out[n] = {"correct": res["correct"], "device": res["device"],
              "meshes": sorted(map(str, meshes[n]))}
k = min(len(records[1]), len(records[4]))
same = k > 0
for a, b in zip(records[1][:k], records[4][:k]):
    for key in ("n", "interval", "metric", "utility", "consumed", "wall",
                "final_metric"):
        same &= bool(np.array_equal(a[key], b[key]))
    for leaf in a["final_params"]:
        same &= bool(np.array_equal(a["final_params"][leaf],
                                    b["final_params"][leaf]))
print(json.dumps({"runs": out, "compared": k, "identical": same}))
"""


def test_four_chip_cell_runs_sharded_and_matches_one_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", CHILD, os.path.join(BENCH, "tests")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    one, four = got["runs"]["1"], got["runs"]["4"]
    assert one["correct"] and four["correct"]
    assert one["meshes"] == ["None"]
    assert four["meshes"] == [str({"data": 4, "model": 1})]
    assert four["device"]["count"] == 4
    assert len(four["device"]["memory_peak_bytes_per_chip"]) == 4
    assert len(one["device"]["memory_peak_bytes_per_chip"]) == 1
    assert got["compared"] > 0 and got["identical"], got
