"""Token rows for a language model's edges: non-IID int32 sequences made
from the configuration's data seed.

Every token is a Zipf(``zipf``) draw of a rank over the cell's vocabulary
slice, mapped to an id by a permutation of the slice: each edge has its own
permutation, so the edges agree on how skewed their streams are and
disagree on which ids are frequent (the non-IID split).  An edge holds
``sequences`` rows of ``seq_len + 1`` tokens (a step reads the first
``seq_len`` and predicts the last ``seq_len``); the ``held_out`` rows of
the cloud's metric draw each token under a random edge's permutation.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def make(cfg: dict) -> Tuple[List[Dict[str, np.ndarray]],
                             Dict[str, np.ndarray]]:
    """``(edge_splits, eval_set)``: ``[{"tokens": [N, S+1]}] * n_edges``
    and ``{"tokens": [held_out, S+1]}``, int32."""
    d = cfg["data"]
    v, e = cfg["vocab_size"], cfg["n_edges"]
    width = d["seq_len"] + 1
    rng = np.random.default_rng(d["seed"])
    p = np.arange(1, v + 1, dtype=np.float64) ** -float(d["zipf"])
    cdf = np.cumsum(p / p.sum())
    perms = np.stack([rng.permutation(v) for _ in range(e)])

    def ranks(n):
        return np.minimum(np.searchsorted(cdf, rng.random(n)), v - 1)

    edges = [{"tokens": perms[i][ranks(d["sequences"] * width)].reshape(
        d["sequences"], width).astype(np.int32)} for i in range(e)]
    n = d["held_out"] * width
    held = perms[rng.integers(0, e, size=n), ranks(n)]
    return edges, {"tokens": held.reshape(d["held_out"], width)
                   .astype(np.int32)}
