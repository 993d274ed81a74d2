"""``device-f32``: the check of a model that no host replay can follow.

The control plane is ``benchlib.elref``'s: the same selection weights,
costs, ledger and ``jax.random`` stream derivations, in float64 on the
host.  The model arithmetic is the configuration's reference as
``jax.numpy`` functions, run on JAX's default device (the first chip,
whatever the cell's number of chips) in float32 with every matrix
product at ``highest`` precision, whatever the configuration's own
precision.  It runs one edge and one local step at a time and
accumulates the aggregation edge by edge, leaf by leaf; a step after an
edge's first consumes the edge's copy, and each sum after the first
consumes the running sum (buffer donation).  So at its peak it holds
about four parameter-sized buffers — the global parameters, the edge's
copy, what the step makes of it (its gradient inside), the running sum —
besides one step's activations: a reference fits where four copies of
its parameters and the activations fit on one chip.  Its control is the
same reference with every product in one bfloat16 pass with float32
accumulation, as JAX's ``default`` precision computes on a TPU.

A configuration that uses it sets ``"check": "device-f32"``, and may set
``"param_gap_of": "update"`` (``benchlib.check.replay_numbers``).  Its
reference module (``configs/<name>.py``) provides:

    init(cfg, seed) -> {name: array}
        the run's initial parameters (the program starts from them too);
    edge_step(M, cfg, p, x, y) -> {name: array}
        one local step of one edge: ``p``'s leaves have no edge axis,
        ``x`` and ``y`` are one minibatch of the edge's rows;
    eval_metric(M, cfg, p, eval_set) -> scalar
        the in-run metric over the held-out rows (``{"x", "y"}``);
    weighted_sum(acc, p, w) -> {name: array}
        ``acc + w p`` leaf by leaf (``acc`` ``None``: ``w p``), of which
        the aggregation's n_e-weighted mean and the async merge are made;
    step_flops(cfg), n_params(cfg), eval_flops(cfg)
        the counts ``benchlib.counts`` reads.

``M`` carries every matrix product, so that the check sets their
precision: ``M.mm(a, b)`` (as ``jnp.matmul``) and ``M.einsum(spec,
*operands)``.  The functions are traced under ``jax.jit``: ``jax.numpy``
only, with ``cfg``'s numbers as constants.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from benchlib import data
from benchlib.prec import F64

Params = Dict[str, object]


class Products:
    """The reference's matrix products: float32 at ``highest``, or for
    the control one bfloat16 pass with float32 accumulation."""

    def __init__(self, control: bool):
        self.control = control

    def _args(self, ops):
        import jax
        import jax.numpy as jnp
        if self.control:
            return ([o.astype(jnp.bfloat16) for o in ops],
                    {"preferred_element_type": jnp.float32})
        return ops, {"precision": jax.lax.Precision.HIGHEST}

    def mm(self, a, b):
        import jax.numpy as jnp
        ops, kw = self._args((a, b))
        return jnp.matmul(*ops, **kw)

    def einsum(self, spec: str, *operands):
        import jax.numpy as jnp
        ops, kw = self._args(operands)
        return jnp.einsum(spec, *ops, **kw)


class DeviceWorkload:
    """``elref.Workload``'s interface with the model's arithmetic on the
    device.  The edges' rows stay on the host; each step's minibatch is
    gathered there (row = floor(u * n_e) in float32, as the run's sampler
    specifies) and sent with the step."""

    def __init__(self, cfg: dict, ref, control: bool = False):
        import jax

        self.cfg, self.P = cfg, F64
        self.precision = "default" if control else "highest"
        edges, test = data.make(cfg)
        self.edges = edges
        self.n = np.array([len(e["y"]) for e in edges], np.int64)
        self.w_agg = self.n / self.n.sum()
        self.eval = jax.device_put(test)
        M = Products(control)
        step = lambda p, x, y: ref.edge_step(M, cfg, p, x, y)  # noqa: E731
        # the first step of an edge keeps the parameters it starts from
        # (the global copy); a later step consumes the edge's own copy,
        # and a later sum the running sum
        self._step = jax.jit(step)
        self._step_own = jax.jit(step, donate_argnums=0)
        self._metric = jax.jit(
            lambda p, ev: ref.eval_metric(M, cfg, p, ev))
        self._wsum = jax.jit(ref.weighted_sum)
        self._wsum_into = jax.jit(ref.weighted_sum, donate_argnums=0)
        self._sq = jax.jit(lambda a, b: sum(
            ((a[k] - b[k]) ** 2).sum() for k in a))

    def _call(self, f, *args):
        import jax
        with jax.default_matmul_precision(self.precision):
            return f(*args)

    def _steps(self, p: Params, e: int, interval: int, u: np.ndarray
               ) -> Params:
        """``interval`` local steps of edge ``e`` from ``p`` (``u``
        ``[k, B]``)."""
        n = np.float32(self.n[e])
        for s in range(interval):
            idx = (u[s].astype(np.float32) * n).astype(np.int64)
            p = self._call(self._step_own if s else self._step, p,
                           self.edges[e]["x"][idx], self.edges[e]["y"][idx])
        return p

    def _sum(self, acc, p: Params, w: float) -> Params:
        """``acc + w p``, consuming ``acc`` (``None``: ``w p``)."""
        return self._call(self._wsum if acc is None else self._wsum_into,
                          acc, p, np.float32(w))

    def params(self, init: Params) -> Params:
        import jax
        return {k: jax.device_put(np.asarray(v)) for k, v in init.items()}

    def round(self, p: Params, interval: int, u: np.ndarray) -> Params:
        acc = None
        for e in range(self.cfg["n_edges"]):
            acc = self._sum(acc, self._steps(p, e, interval, u[e]),
                            self.w_agg[e])
        return acc

    def event(self, p: Params, e: int, interval: int, u: np.ndarray
              ) -> Params:
        return self._steps(p, e, interval, u)

    def mix(self, g: Params, p: Params, alpha: float) -> Params:
        return self._sum(self._sum(None, g, 1.0 - alpha), p, alpha)

    def host(self, p: Params) -> Params:
        return {k: np.asarray(v) for k, v in p.items()}

    def metric(self, p: Params) -> float:
        return float(self._call(self._metric, p, self.eval))

    def utility(self, new: Params, old: Params, new_metric, prev_metric):
        if self.cfg["utility"] == "eval_gain":
            return new_metric - prev_metric
        return 1.0 / (1.0 + math.sqrt(float(self._call(self._sq, new,
                                                        old))))


def workload(cfg: dict, ref, control: bool = False) -> DeviceWorkload:
    return DeviceWorkload(cfg, ref, control)
