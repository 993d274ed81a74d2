"""Executors: the ML data-plane the EL runtime drives.

Both satisfy the typed ``repro.el.EdgeExecutor`` Protocol (structurally —
``local_train`` / ``evaluate`` / ``init_params``).

``ClassicExecutor`` — SVM / K-means local training on per-edge (non-IID)
datasets, jitted per interval length via lax.scan over stacked minibatches.
It also satisfies ``repro.el.InGraphExecutor`` (raw per-edge arrays + a
jittable model), which is what lets ``ELSession.run_sync_ingraph`` stage
a whole run into one XLA program.

``LMExecutor`` — small language models through the same interface (params
only; per-edge optimizer moments are ephemeral within a local block, the
standard local-SGD simplification).

``TokenLMExecutor`` — a language model on per-edge token rows: what the
compiled sync engine runs for an LM edge (``repro.el.InGraphExecutor``,
plain-SGD ``model.local_step``, held-out negative loss).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, TrainConfig
from repro.data.classic_data import minibatches
from repro.data.pipeline import SyntheticLMData
from repro.train.optimizer import init_opt_state
from repro.train.state import TrainState, make_train_step

Params = Any


class ClassicExecutor:
    """SVM / K-means on per-edge datasets."""

    def __init__(self, model, edge_data: List[Dict[str, np.ndarray]],
                 eval_set: Dict[str, np.ndarray], batch: int = 64,
                 lr: float = 0.05):
        self.model = model
        self.edge_data = edge_data
        self.eval_set = {k: jnp.asarray(v) for k, v in eval_set.items()}
        self.batch = batch
        self.lr = lr

        def scan_steps(params: Params, xs: jax.Array, ys: jax.Array
                       ) -> Params:
            def body(p, xy):
                x, y = xy
                p, _ = self.model.local_step(p, {"x": x, "y": y}, self.lr)
                return p, None
            params, _ = jax.lax.scan(body, params, (xs, ys))
            return params

        self._scan_steps = jax.jit(scan_steps)

    def init_params(self, seed: int = 0) -> Params:
        return self.model.init(jax.random.key(seed))

    def sample_batches(self, edge: int, n_iters: int, seed: int
                       ) -> Tuple[jax.Array, jax.Array]:
        data = self.edge_data[edge]
        rng = np.random.default_rng(seed)
        idx = rng.integers(0, len(data["y"]), size=(n_iters, self.batch))
        return jnp.asarray(data["x"][idx]), jnp.asarray(data["y"][idx])

    def local_train(self, params: Params, edge: int, n_iters: int,
                    seed: int) -> Tuple[Params, Dict]:
        xs, ys = self.sample_batches(edge, n_iters, seed)
        return self._scan_steps(params, xs, ys), {}

    def evaluate(self, params: Params) -> Dict[str, float]:
        return self.model.evaluate(params, self.eval_set)

    def evaluate_many(self, stacked_params: Params
                      ) -> Optional[Dict[str, np.ndarray]]:
        """``evaluate`` for every cell of a stacked ``[n_cells, ...]``
        params tree at once, as ``[n_cells]`` arrays; None where the
        model has no batched form (the caller then scores cell by
        cell)."""
        many = getattr(self.model, "evaluate_many", None)
        return None if many is None else many(stacked_params,
                                              self.eval_set)


class LMExecutor:
    """Small LMs under the same EL interface (loss-based metric)."""

    def __init__(self, model, model_cfg: ModelConfig, train_cfg: TrainConfig,
                 batch: int = 4, seq_len: int = 64, seed: int = 0):
        self.model = model
        self.train_cfg = train_cfg
        self.data = SyntheticLMData.for_model(model_cfg, batch, seq_len,
                                              seed=seed)
        train_step = make_train_step(model, train_cfg)

        def scan_steps(state: TrainState, edge: jax.Array, start: jax.Array,
                       n_iters: jax.Array, h_max: int) -> TrainState:
            def body(s, i):
                b = self.data.batch(edge, start + i)
                s2, _ = train_step(s, b)
                s = jax.tree.map(
                    lambda a, bb: jnp.where(i < n_iters, bb, a), s, s2)
                return s, None
            state, _ = jax.lax.scan(body, state, jnp.arange(h_max))
            return state

        self._scan = {}
        self._scan_fn = scan_steps
        self._step_counter = np.zeros(64, np.int64)
        self._eval_batch = self.data.batch(999, 0)

        def eval_loss(params):
            _, m = model.loss(params, self._eval_batch)
            return m["ce_loss"]

        self._eval = jax.jit(eval_loss)

    def init_params(self, seed: int = 0) -> Params:
        return self.model.init(jax.random.key(seed))

    def local_train(self, params: Params, edge: int, n_iters: int,
                    seed: int) -> Tuple[Params, Dict]:
        h_max = int(n_iters)
        if h_max not in self._scan:
            self._scan[h_max] = jax.jit(
                partial(self._scan_fn, h_max=h_max))
        state = TrainState(params, init_opt_state(self.train_cfg, params))
        start = int(self._step_counter[edge])
        self._step_counter[edge] += h_max
        state = self._scan[h_max](state, jnp.asarray(edge),
                                  jnp.asarray(start), jnp.asarray(n_iters))
        return state.params, {}

    def evaluate(self, params: Params) -> Dict[str, float]:
        loss = float(self._eval(params))
        return {"loss": loss, "neg_loss": -loss}


class TokenLMExecutor:
    """A language model whose edges hold int32 token rows ``[N_e, S+1]``
    (``edge_data[e]["tokens"]``), for ``ELSession.run_sync_ingraph``.

    A local step is one plain SGD step on the next-token loss of
    ``batch`` rows of the edge (``model.local_step``; no optimizer
    state); the metric is the held-out negative loss of ``eval_tokens``
    (``neg_loss``, and ``loss``).  ``local_train`` runs the same steps
    for the host loops, on rows drawn by numpy."""

    def __init__(self, model, edge_tokens: List[np.ndarray],
                 eval_tokens: np.ndarray, batch: int = 2, lr: float = 0.1):
        self.model = model
        self.edge_data = [{"tokens": np.asarray(t, np.int32)}
                          for t in edge_tokens]
        self.eval_set = {"tokens": jnp.asarray(eval_tokens, jnp.int32)}
        self.batch = batch
        self.lr = lr
        self._step = jax.jit(lambda p, toks: model.local_step(
            p, {"tokens": toks}, lr)[0])
        self._loss = jax.jit(model.token_loss)

    def init_params(self, seed: int = 0) -> Params:
        return self.model.init(jax.random.key(seed))

    def local_train(self, params: Params, edge: int, n_iters: int,
                    seed: int) -> Tuple[Params, Dict]:
        toks = self.edge_data[edge]["tokens"]
        rng = np.random.default_rng(seed)
        for _ in range(int(n_iters)):
            params = self._step(params, toks[rng.integers(
                0, len(toks), size=self.batch)])
        return params, {}

    def evaluate(self, params: Params) -> Dict[str, float]:
        # params replicated over a mesh are read on their first device:
        # the model's kernels cannot be partitioned over the mesh
        params = jax.tree.map(
            lambda x: (x.addressable_shards[0].data
                       if isinstance(x, jax.Array) and len(x.devices()) > 1
                       and x.sharding.is_fully_replicated else x), params)
        loss = float(self._loss(params, self.eval_set["tokens"]))
        return {"loss": loss, "neg_loss": -loss}
