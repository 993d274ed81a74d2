"""The benchmark's data: synthetic stand-ins for the paper's two data sets
and their non-IID split over edges, made from a configuration's
``data_seed``.

Each data kind's recipe is ``bench/datasets/<kind>.py`` (wafer: the
SVM's Gaussian class clusters; traffic: K-means's Gaussian mixture),
which returns its rows in the dtypes the model takes them (float32
features, int32 labels; token rows would be int32); the
held-out split and the non-IID split over edges (a Dirichlet(alpha) draw
of class proportions per edge) are here.  All are copied from the
program's ``repro.data.classic_data``, so that the data the reference
trains on is the benchmark's own and no change to the program can move
it.

The data set is fixed per configuration (its shapes size the compiled
programs, and a program keeps its data as constants): ``--seed`` varies
the runs, not the rows.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchlib import load_named

Split = Dict[str, np.ndarray]


def _split(rng, x, y, test_frac) -> Tuple[Split, Split]:
    n_test = int(len(y) * test_frac)
    idx = rng.permutation(len(y))
    tr, te = idx[n_test:], idx[:n_test]
    return {"x": x[tr], "y": y[tr]}, {"x": x[te], "y": y[te]}


def dirichlet_edges(data: Split, n_edges: int, alpha: float, seed: int
                    ) -> List[Split]:
    """Dirichlet non-IID split of ``(x, y)`` over ``n_edges`` edges."""
    rng = np.random.default_rng(seed + 2)
    y = data["y"]
    edge_idx: List[List[int]] = [[] for _ in range(n_edges)]
    for cls in range(int(y.max()) + 1):
        cls_idx = np.where(y == cls)[0]
        rng.shuffle(cls_idx)
        props = rng.dirichlet([alpha] * n_edges)
        cuts = (np.cumsum(props) * len(cls_idx)).astype(int)[:-1]
        for e, part in enumerate(np.split(cls_idx, cuts)):
            edge_idx[e].extend(part.tolist())
    out = []
    for e in range(n_edges):
        idx = np.asarray(edge_idx[e], dtype=np.int64)
        rng.shuffle(idx)
        if len(idx) == 0:                         # never leave an edge empty
            idx = rng.integers(0, len(y), size=8)
        out.append({k: v[idx] for k, v in data.items()})
    return out


def make(cfg: dict) -> Tuple[List[Split], Split]:
    """``(edge_splits, eval_set)`` for a configuration file's ``data``:
    the recipe ``bench/datasets/<kind>.py`` makes the rows, found by
    name."""
    d = cfg["data"]
    mod = load_named("datasets", d["kind"])
    rng, x, y = mod.make(d["samples"], cfg["features"], cfg["classes"],
                         d["seed"])
    train, test = _split(rng, x, y, d["test_frac"])
    return dirichlet_edges(train, cfg["n_edges"], d["dirichlet_alpha"],
                           d["seed"]), test
