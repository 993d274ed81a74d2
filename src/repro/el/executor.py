"""The typed edge data-plane interface the EL runtime drives.

``EdgeExecutor`` makes the previously implicit ``local_train/evaluate``
duck interface an explicit, runtime-checkable Protocol.  The two concrete
executors (``repro.federated.executors.ClassicExecutor`` for SVM/K-means
and ``LMExecutor`` for language models) satisfy it structurally — no
inheritance needed; third-party executors only have to match the shapes.

``InGraphExecutor`` is the narrower contract the compiled sync fast path
needs (raw per-edge arrays + a jittable model) — ``ClassicExecutor``
satisfies it, and ``TokenLMExecutor`` (a language model on per-edge
token rows) for the single sync run.
"""

from __future__ import annotations

from typing import (Any, Dict, List, Protocol, Tuple, runtime_checkable)

import numpy as np

Params = Any


@runtime_checkable
class EdgeExecutor(Protocol):
    """One edge server's training/eval surface.

    ``local_train`` runs ``n_iters`` local iterations for ``edge`` starting
    from ``params`` and returns the updated params plus an info dict;
    ``evaluate`` computes cloud-side metrics (the utility estimator and the
    report both read them).
    """

    def local_train(self, params: Params, edge: int, n_iters: int,
                    seed: int) -> Tuple[Params, Dict]:
        ...

    def evaluate(self, params: Params) -> Dict[str, float]:
        ...


@runtime_checkable
class InitCapable(Protocol):
    """Executors that can produce their own initial parameters."""

    def init_params(self, seed: int) -> Params:
        ...


@runtime_checkable
class InGraphExecutor(Protocol):
    """What ``ELSession.run_sync_ingraph`` additionally needs: the jittable
    model plus raw per-edge datasets so the whole budgeted loop can be
    staged into one XLA program.  An executor may also offer
    ``evaluate_many(stacked_params) -> {name: [n_cells]}`` (or None):
    a sweep without an in-graph metric then scores all its cells in one
    call instead of one ``evaluate`` per cell."""

    model: Any
    edge_data: List[Dict[str, np.ndarray]]
    eval_set: Dict[str, Any]
    batch: int
    lr: float

    def local_train(self, params: Params, edge: int, n_iters: int,
                    seed: int) -> Tuple[Params, Dict]:
        ...

    def evaluate(self, params: Params) -> Dict[str, float]:
        ...


def validate_executor(ex: Any) -> None:
    """Fail fast (with a useful message) on malformed executors."""
    missing = [m for m in ("local_train", "evaluate")
               if not callable(getattr(ex, m, None))]
    if missing:
        raise TypeError(
            f"{type(ex).__name__} does not satisfy EdgeExecutor: "
            f"missing callable(s) {missing}; see repro.el.executor")
