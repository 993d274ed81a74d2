"""The paper's own workloads as JAX models: linear SVM and K-means.

Both expose the same functional surface the EL runtime drives:
  ``init(rng) -> params``
  ``local_step(params, batch, lr) -> (params, metrics)``  (one local iteration)
  ``evaluate(params, eval_set) -> metrics``               (cloud-side utility)

SVM  — multiclass one-vs-rest squared-hinge linear SVM (paper: 59-dim wafer
       features, 8 classes; metric = prediction accuracy).
K-means — minibatch Lloyd steps (paper: traffic images, K=3; metric = F1
       of cluster assignments vs. ground truth after greedy cluster->class
       matching; utility = negative center shift between slots — the
       paper's own example of a model-specific utility).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Linear multiclass SVM (one-vs-rest, squared hinge)
# ---------------------------------------------------------------------------


class LinearSVM:
    def __init__(self, cfg: ModelConfig, reg: float = 1e-4):
        self.cfg = cfg
        self.d = cfg.d_model
        self.n_classes = cfg.vocab_size
        self.reg = reg

    def init(self, rng: jax.Array) -> Params:
        return {
            "w": jnp.zeros((self.d, self.n_classes), jnp.float32),
            "b": jnp.zeros((self.n_classes,), jnp.float32),
        }

    def scores(self, params: Params, x: jax.Array) -> jax.Array:
        return x @ params["w"] + params["b"]

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        x, y = batch["x"], batch["y"]
        s = self.scores(params, x)                       # [B, C]
        y_pm = 2.0 * jax.nn.one_hot(y, self.n_classes) - 1.0
        margin = jnp.maximum(0.0, 1.0 - y_pm * s)
        hinge = jnp.mean(jnp.sum(margin ** 2, axis=-1))
        l2 = self.reg * jnp.sum(params["w"] ** 2)
        loss = hinge + l2
        acc = jnp.mean((jnp.argmax(s, -1) == y).astype(jnp.float32))
        return loss, {"loss": loss, "accuracy": acc}

    def local_step(self, params: Params, batch: Dict[str, jax.Array],
                   lr: float) -> Tuple[Params, Dict[str, jax.Array]]:
        (loss, metrics), grads = jax.value_and_grad(
            self.loss, has_aux=True)(params, batch)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, metrics

    def evaluate(self, params: Params, eval_set: Dict[str, jax.Array]
                 ) -> Dict[str, float]:
        s = self.scores(params, eval_set["x"])
        acc = jnp.mean((jnp.argmax(s, -1) == eval_set["y"])
                       .astype(jnp.float32))
        return {"accuracy": float(acc)}


# ---------------------------------------------------------------------------
# K-means (minibatch Lloyd)
# ---------------------------------------------------------------------------


class KMeans:
    """Minibatch-Lloyd K-means.

    ``impl`` selects the E-step engine, following the ``models/layers``
    convention: ``"jnp"`` (the pure-XLA distance expansion) or
    ``"pallas"`` — the ``repro.kernels.kmeans_assign`` Pallas kernel
    (native on TPU, interpret mode on the CPU; oracle-tested against the
    jnp path in tests/test_kernels.py).  The kernel is vmap-safe, so the
    compiled EL programs' per-edge local blocks route through it too.
    ``use_kernel=True`` is the deprecated spelling of ``impl="pallas"``.
    """

    def __init__(self, cfg: ModelConfig, blend: float = 0.5,
                 use_kernel: bool = False, impl: str = "jnp"):
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"KMeans impl={impl!r}; expected 'jnp' or "
                             "'pallas'")
        self.cfg = cfg
        self.d = cfg.d_model
        self.k = cfg.vocab_size
        self.blend = blend           # minibatch-Lloyd blending rate
        self.impl = "pallas" if use_kernel else impl
        # every set of a stacked [n, K, D] centers tree scored in one
        # compiled call (``evaluate_many``); traced once per shape
        self._score_many = jax.jit(jax.vmap(
            lambda params, x: (self.assign(params, x),
                               self.inertia(params, x)),
            in_axes=(0, None)))

    @property
    def use_kernel(self) -> bool:   # pre-impl= spelling, kept for callers
        return self.impl == "pallas"

    def init(self, rng: jax.Array) -> Params:
        return {"centers": jax.random.normal(rng, (self.k, self.d),
                                             jnp.float32)}

    def assign(self, params: Params, x: jax.Array) -> jax.Array:
        if self.impl == "pallas":
            from repro.kernels.kmeans_assign import ops as ka_ops
            return ka_ops.assign(x, params["centers"])
        d2 = (jnp.sum(x ** 2, -1, keepdims=True)
              - 2.0 * x @ params["centers"].T
              + jnp.sum(params["centers"] ** 2, -1)[None, :])
        return jnp.argmin(d2, axis=-1)

    def inertia(self, params: Params, x: jax.Array) -> jax.Array:
        d2 = (jnp.sum(x ** 2, -1, keepdims=True)
              - 2.0 * x @ params["centers"].T
              + jnp.sum(params["centers"] ** 2, -1)[None, :])
        return jnp.mean(jnp.min(d2, axis=-1))

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        loss = self.inertia(params, batch["x"])
        return loss, {"loss": loss}

    def local_step(self, params: Params, batch: Dict[str, jax.Array],
                   lr: float = 1.0) -> Tuple[Params, Dict[str, jax.Array]]:
        """One minibatch Lloyd step (blend new centroids into old)."""
        x = batch["x"]
        a = self.assign(params, x)                       # [B]
        onehot = jax.nn.one_hot(a, self.k, dtype=jnp.float32)   # [B, K]
        counts = onehot.sum(0)                            # [K]
        sums = onehot.T @ x                               # [K, d]
        new = sums / jnp.maximum(counts[:, None], 1.0)
        has = (counts > 0)[:, None]
        rate = self.blend * jnp.asarray(lr, jnp.float32)
        centers = jnp.where(
            has, (1.0 - rate) * params["centers"] + rate * new,
            params["centers"])
        inert = self.inertia({"centers": centers}, x)
        return {"centers": centers}, {"loss": inert}

    def evaluate(self, params: Params, eval_set: Dict[str, jax.Array]
                 ) -> Dict[str, float]:
        """Macro F1 after greedy cluster->class matching (paper metric)."""
        x = np.asarray(eval_set["x"])
        y = np.asarray(eval_set["y"])
        a = np.asarray(self.assign(params, jnp.asarray(x)))
        f1 = cluster_f1(a, y, self.k)
        inert = float(self.inertia(params, jnp.asarray(x)))
        return {"f1": f1, "inertia": inert}

    def evaluate_many(self, stacked: Params, eval_set: Dict[str, jax.Array]
                      ) -> Dict[str, np.ndarray]:
        """``evaluate`` for every set of centers in a stacked ``[n, K, D]``
        tree, as ``[n]`` arrays.  One compiled call assigns the eval
        points to each set (``assign``: the same E-step engine, so the
        same assignments) and takes its inertia; one host copy brings
        back the ``[n, N]`` assignments, the inertias and the labels;
        the F1 matching runs on the host per set.  The centers are put
        where the eval points live, so the call is one device's program
        even when they arrive sharded over a mesh (a Pallas kernel
        cannot be partitioned)."""
        x = jnp.asarray(eval_set["x"])
        stacked = jax.device_put(stacked, x.sharding)
        a, inert, y = jax.device_get(
            (*self._score_many(stacked, x), eval_set["y"]))
        return {"f1": np.array([cluster_f1(ai, y, self.k) for ai in a]),
                "inertia": np.asarray(inert, np.float64)}


def cluster_f1(assignments: np.ndarray, labels: np.ndarray, k: int) -> float:
    """Greedy majority cluster->class mapping, then macro F1."""
    n_classes = int(labels.max()) + 1
    mapping = np.zeros(k, np.int64)
    for c in range(k):
        members = labels[assignments == c]
        mapping[c] = np.bincount(members, minlength=n_classes).argmax() \
            if members.size else 0
    pred = mapping[assignments]
    f1s = []
    for cls in range(n_classes):
        tp = np.sum((pred == cls) & (labels == cls))
        fp = np.sum((pred == cls) & (labels != cls))
        fn = np.sum((pred != cls) & (labels == cls))
        prec = tp / max(tp + fp, 1)
        rec = tp / max(tp + fn, 1)
        f1s.append(0.0 if prec + rec == 0 else 2 * prec * rec / (prec + rec))
    return float(np.mean(f1s))
