"""``device-f32-edges``: the check of a model too large for ``device-f32``
to hold four copies of on one chip — a language model's period, whose
float32 copy is gigabytes.

Its interface is ``device-f32``'s (that file's docstring; ``edge_step``
takes one edge's token rows ``[B, S+1]`` in place of ``x, y``): the
control plane is ``benchlib.elref``'s, in float64 on the host; the model
arithmetic is the configuration's reference (``edge_step``,
``eval_metric``) in float32 with every product at ``highest``, and its
control has every product's operands rounded to bfloat16 first
(``Products``).  What differs is where it runs:

  * every edge steps at once, edge ``e`` on chip ``e`` (edges split
    evenly over as many chips as divide their number): one program over
    a mesh of those chips (``shard_map`` over the edges' stacked
    copies), so the step compiles once for all of them — a step jitted
    for one device compiles again for every other, and a period's step
    takes over a minute to compile.  Each step consumes the edges'
    copies (buffer donation), so a chip holds its edge's copy, the
    step's gradient and output, and one step's activations;
  * the aggregation's running sum is kept on the host in float64, leaf by
    leaf (``w_e`` times each edge's float32 result, edge 0 first), and
    the global parameters stay there between rounds; each set of them is
    made float32 and sent to every edge's chip once, and the metric of a
    round's result reads the first chip's copy before the next round
    steps from it.

The step and the metric compile at once, in two threads, and the host
work (the float32 copies, their transfers, the sum) runs on
``HOST_THREADS`` threads, a leaf each.  Token rows
come from the configuration's ``datasets/<kind>.py`` (``make(cfg)``:
edges' ``{"tokens": [N, S+1]}`` and the held-out rows); a step's rows are
drawn as the run's sampler draws them (row = floor(u * n_e) in float32).
Sync runs only: a language model compiles only as a sync run.  Use
``"param_gap_of": "update"``: the update of a few SGD steps is small
beside the initial weights.
"""

from __future__ import annotations

import concurrent.futures
import math
import os
from typing import Dict

import numpy as np

from benchlib import load_named
from benchlib.prec import F64

Params = Dict[str, object]

#: host threads of the float32 copies and the float64 running sum (numpy
#: releases the GIL)
HOST_THREADS = min(16, os.cpu_count() or 1)

#: elements of a leaf summed at a time: the float64 partial sum stays in
#: the core's cache while every edge's part is added to it
SUM_CHUNK = 1 << 16


class Products:
    """The reference's matrix products, float32 at ``highest``; for the
    control each operand is first rounded to bfloat16, which computes
    one bfloat16 pass with float32 accumulation (a product of two
    bfloat16 values is exact in float32) on any backend, batched
    products included."""

    def __init__(self, control: bool):
        self.control = control

    def _ops(self, ops):
        import jax.numpy as jnp
        if self.control:
            return [o.astype(jnp.bfloat16).astype(jnp.float32) for o in ops]
        return ops

    def mm(self, a, b):
        import jax
        import jax.numpy as jnp
        return jnp.matmul(*self._ops((a, b)),
                          precision=jax.lax.Precision.HIGHEST)

    def einsum(self, spec: str, *operands):
        import jax
        import jax.numpy as jnp
        return jnp.einsum(spec, *self._ops(operands),
                          precision=jax.lax.Precision.HIGHEST)


class EdgesWorkload:
    """``elref.Workload``'s interface, one edge a chip, the sum on the
    host."""

    def __init__(self, cfg: dict, ref, control: bool = False):
        import jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        self.cfg, self.P = cfg, F64
        self.precision = "default" if control else "highest"
        edges, held = load_named("datasets", cfg["data"]["kind"]).make(cfg)
        self.toks = [e["tokens"] for e in edges]
        self.n = np.array([len(t) for t in self.toks], np.int64)
        self.w_agg = self.n / self.n.sum()
        n_edges = cfg["n_edges"]
        devs = jax.devices()
        chips = max(c for c in range(1, min(n_edges, len(devs)) + 1)
                    if n_edges % c == 0)
        self.edges = NamedSharding(Mesh(np.array(devs[:chips]), ("edge",)),
                                   P("edge"))
        first = jax.sharding.SingleDeviceSharding(devs[0])
        self.eval = jax.device_put(held["tokens"], first)
        M = Products(control)

        def local(p, t):
            """The steps of the edges on one chip, one after another."""
            new = [ref.edge_step(M, cfg, {k: v[i] for k, v in p.items()},
                                 t[i]) for i in range(t.shape[0])]
            return {k: jax.numpy.stack([q[k] for q in new]) for k in p}

        step = jax.jit(jax.shard_map(local, mesh=self.edges.mesh,
                                     in_specs=(P("edge"), P("edge")),
                                     out_specs=P("edge")),
                       donate_argnums=0)
        # the metric of the first chip's copies (their first edge's)
        metric = jax.jit(lambda p, t: ref.eval_metric(
            M, cfg, {k: v[0] for k, v in p.items()}, t))

        def spec(shape, dtype, sharding):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        shapes = ref.shapes(cfg)
        rows = (n_edges, cfg["batch"], cfg["data"]["seq_len"] + 1)
        self._pool = concurrent.futures.ThreadPoolExecutor(HOST_THREADS)
        self._step, self._metric = self._pool.map(self._compile, (
            (step, {k: spec((n_edges,) + s, np.float32, self.edges)
                    for k, s in shapes.items()},
             spec(rows, np.int32, self.edges)),
            (metric, {k: spec((n_edges // chips,) + s, np.float32, first)
                      for k, s in shapes.items()},
             spec(self.eval.shape, np.int32, first))))
        self._placed = (None, None)

    def _compile(self, job):
        import jax
        fn, *args = job
        with jax.default_matmul_precision(self.precision):
            return fn.lower(*args).compile()

    def _place(self, p: Params) -> Params:
        """The host params ``p`` in float32, one copy an edge on the
        edge's chip (``[E, ...]`` leaves sharded over the edges), made
        once per parameter set: the metric of a round's result reads the
        first chip's copies, and the next round steps from them."""
        import jax
        if self._placed[0] is not p:
            self._placed = (None, None)
            n_edges = self.cfg["n_edges"]
            chips = list(self.edges.mesh.devices.flat)
            per = n_edges // len(chips)

            def leaf(k):
                x = np.asarray(p[k], np.float32)
                here = np.ascontiguousarray(
                    np.broadcast_to(x, (per,) + x.shape))
                return k, jax.make_array_from_single_device_arrays(
                    (n_edges,) + x.shape, self.edges,
                    [jax.device_put(here, d) for d in chips])

            self._placed = (p, dict(self._pool.map(leaf, list(p))))
        return self._placed[1]

    def _sum(self, q: Params) -> Params:
        """``sum_e w_e q[e]`` in float64, edge 0 first, leaf by leaf on
        the host threads, a cache-sized chunk at a time."""
        w = [float(x) for x in self.w_agg]
        parts = {k: [s.data for s in sorted(
            v.addressable_shards, key=lambda s: s.index[0].start or 0)]
            for k, v in q.items()}
        for part in parts.values():
            for x in part:
                x.copy_to_host_async()

        def leaf(k):
            xs = [x.reshape(-1) for part in parts[k]
                  for x in np.asarray(part)]
            out = np.empty(xs[0].shape, np.float64)
            for a in range(0, out.size, SUM_CHUNK):
                acc = np.multiply(xs[0][a:a + SUM_CHUNK], w[0],
                                  dtype=np.float64)
                for e in range(1, len(xs)):
                    acc += np.multiply(xs[e][a:a + SUM_CHUNK], w[e],
                                       dtype=np.float64)
                out[a:a + SUM_CHUNK] = acc
            return k, out.reshape(q[k].shape[1:])

        return dict(self._pool.map(leaf, list(q)))

    def params(self, init: Params) -> Params:
        return dict(self._pool.map(
            lambda k: (k, np.asarray(init[k], np.float64)), list(init)))

    def round(self, p: Params, interval: int, u: np.ndarray) -> Params:
        """``interval`` steps of every edge from the host params ``p``
        (``u`` ``[E, k, B]``), then the weighted sum."""
        import jax
        q = self._place(p)
        self._placed = (None, None)
        for s in range(interval):
            rows = np.stack([
                t[(u[e, s].astype(np.float32)
                   * np.float32(len(t))).astype(np.int64)]
                for e, t in enumerate(self.toks)])
            q = self._step(q, jax.device_put(rows, self.edges))
        return self._sum(q)

    def event(self, p, e, interval, u):
        raise NotImplementedError("device-f32-edges replays sync runs")

    def mix(self, g, p, alpha):
        raise NotImplementedError("device-f32-edges replays sync runs")

    def host(self, p: Params) -> Params:
        return p

    def metric(self, p: Params) -> float:
        first = self.edges.mesh.devices.flat[0]
        return float(self._metric(
            {k: next(s.data for s in v.addressable_shards
                     if s.device == first)
             for k, v in self._place(p).items()}, self.eval))

    def utility(self, new: Params, old: Params, new_metric, prev_metric):
        if self.cfg["utility"] == "eval_gain":
            return new_metric - prev_metric
        sq = sum(float(np.sum((new[k] - old[k]) ** 2)) for k in new)
        return 1.0 / (1.0 + math.sqrt(sq))


def workload(cfg: dict, ref, control: bool = False) -> EdgesWorkload:
    return EdgesWorkload(cfg, ref, control)
