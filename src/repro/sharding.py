"""Per-architecture sharding-rule resolver.

Maps every parameter / activation / cache tensor to a PartitionSpec for the
production meshes.  Rules are *name + shape* based and divisibility-checked
against the actual mesh axis sizes, because the assigned architectures have
head counts (40, 56, 36, 24...) that do not all divide the 16-way model
axis: the resolver prefers sharding heads, falls back to head_dim, then to
replication — recorded per-arch by the dry-run.

Conventions:
  * ``model`` axis: tensor-parallel dim (heads / d_ff / experts / d_inner).
  * ``data`` (+ ``pod``) axes: the batch — and, for the batch=1 long-context
    shape, the KV-cache *sequence* dim instead (flash-decoding style).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig


#: Mesh axes the per-edge (batch/fleet) dims spread over.
_EDGE_AXIS_NAMES = ("pod", "data")


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def edge_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in _EDGE_AXIS_NAMES if a in mesh.axis_names)


def _leaf_path_keys(path) -> list:
    return [getattr(k, "key", getattr(k, "idx", None)) for k in path]


def _leaf_param_name(keys) -> str:
    """The rule-lookup name of a param-tree leaf: the last string key on
    its path, ignoring ``sub*`` wrapper levels — the one resolver every
    spec builder in this module shares."""
    return next((k for k in reversed(keys) if isinstance(k, str)
                 and not k.startswith("sub")), "")


def _div(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def _param_spec(name: str, shape: Tuple[int, ...], ms: int) -> P:
    """PartitionSpec for one parameter leaf (no stacking dim)."""
    nd = len(shape)

    def pick(*cands: Tuple[int, str]) -> P:
        """First candidate dim divisible by the model-axis size wins."""
        spec: list = [None] * nd
        for dim, axis in cands:
            if _div(shape[dim], ms):
                spec[dim] = axis
                return P(*spec)
        return P(*spec)

    if name == "embed":
        if nd == 3:                       # [CB, V, d]
            return pick((1, "model"), (2, "model"))
        return pick((0, "model"), (1, "model"))          # [V, d]
    if name == "lm_head":
        if nd == 3:                       # [CB, d, V]
            return pick((2, "model"), (1, "model"))
        return pick((1, "model"), (0, "model"))          # [d, V]
    if name in ("wq", "wk", "wv"):        # [d, H, hd]
        return pick((1, "model"), (2, "model"))
    if name == "wo" and nd == 3:          # [H, hd, d]
        return pick((0, "model"), (1, "model"))
    if name == "wo" and nd == 2:          # mlp down [f, d]
        return pick((0, "model"))
    if name in ("bq", "bk", "bv"):        # [H, hd]
        return pick((0, "model"), (1, "model"))
    if name in ("wi_gate", "wi_up", "ws_gate", "ws_up"):  # [d, f]
        return pick((1, "model"))
    if name == "ws_down":                 # [f, d]
        return pick((0, "model"))
    if name == "router":                  # [d, E]
        return pick((1, "model"))
    if name in ("we_gate", "we_up"):      # [E, d, f]
        return pick((0, "model"), (2, "model"))
    if name == "we_down":                 # [E, f, d]
        return pick((0, "model"), (1, "model"))
    if name == "in_proj":                 # [d, 2di+2n+nh]
        return pick((1, "model"))
    if name == "conv_w":                  # [K, C]
        return pick((1, "model"))
    if name in ("conv_b", "gate_norm", "A_log", "D", "dt_bias"):
        return pick((0, "model"))
    if name == "out_proj":                # [di, d]
        return pick((0, "model"))
    # norms, scalars, classic-model params: replicate
    return P(*([None] * nd))


def param_specs(cfg: ModelConfig, mesh: Mesh, params_shape: Any,
                fsdp: bool = False) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (eval_shape output).

    ``fsdp=True`` additionally shards each parameter's largest still-
    unsharded dim over the edge (pod+data) axes when divisible — the
    ZeRO-3/FSDP layout used by the baseline ``train_step`` so that e.g.
    jamba-398B optimizer state spreads over all chips, not just the
    ``model`` axis.
    """
    ms = _axis_size(mesh, "model")
    ea = edge_axes(mesh)
    n_edge = _prod(_axis_size(mesh, a) for a in ea)

    def add_fsdp(spec: P, shape: Tuple[int, ...]) -> P:
        if not fsdp or len(shape) < 2 or n_edge <= 1:
            return spec
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        out = list(spec) + [None] * (len(shape) - len(spec))
        for i in dims:
            if out[i] is None and _div(shape[i], n_edge):
                out[i] = ea
                return P(*out)
        return spec

    def leaf_spec(path, leaf) -> P:
        keys = _leaf_path_keys(path)
        name = _leaf_param_name(keys)
        # scanned models stack group params on a leading n_groups dim;
        # unrolled models keep a list of per-group dicts (no extra dim)
        stacked = ("groups" in keys) and cfg.scan_layers
        shape = leaf.shape
        if stacked:
            base = add_fsdp(_param_spec(name, shape[1:], ms), shape[1:])
            return P(None, *base)
        return add_fsdp(_param_spec(name, shape, ms), shape)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def batch_spec(mesh: Mesh) -> P:
    """Token batches: batch dim over the edge (pod+data) axes."""
    return P(edge_axes(mesh))


def batch_sharding(cfg: ModelConfig, mesh: Mesh, batch_shape: Any,
                   shard_batch: bool = True) -> Any:
    """Shardings for a train/prefill input batch pytree."""
    ea = edge_axes(mesh)

    def leaf(path, x) -> P:
        keys = [getattr(k, "key", None) for k in path]
        nd = len(x.shape)
        if not shard_batch or x.shape[0] % max(
                1, _prod(_axis_size(mesh, a) for a in ea)):
            return P(*([None] * nd))
        if "prefix_emb" in keys:
            return P(ea, None, None)
        return P(ea, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(leaf, batch_shape)


def _prod(it) -> int:
    out = 1
    for v in it:
        out *= v
    return out


def cache_specs(cfg: ModelConfig, mesh: Mesh, cache_shape: Any,
                batch: int) -> Any:
    """PartitionSpecs for the decode cache.

    batch >= n_edge_devices -> shard batch over edge axes; batch == 1
    (long-context) -> shard the KV *sequence* dim over the edge axes
    instead, giving flash-decoding-style partial-softmax collectives.
    """
    ms = _axis_size(mesh, "model")
    ea = edge_axes(mesh)
    n_edge = _prod(_axis_size(mesh, a) for a in ea)
    shard_batch = _div(batch, n_edge)

    def leaf_spec(path, leaf) -> P:
        keys = _leaf_path_keys(path)
        name = _leaf_param_name(keys)
        stacked = ("groups" in keys) and cfg.scan_layers
        shape = leaf.shape[1:] if stacked else leaf.shape
        nd = len(shape)
        spec: list = [None] * nd
        if name in ("k", "v"):            # [B, S, KV, hd]
            if shard_batch:
                spec[0] = ea
            elif _div(shape[1], n_edge):
                spec[1] = ea              # seq-sharded KV (batch=1)
            if _div(shape[2], ms):
                spec[2] = "model"
            elif _div(shape[3], ms):
                spec[3] = "model"
        elif name == "conv":              # [B, K-1, C]
            if shard_batch:
                spec[0] = ea
            if _div(shape[2], ms):
                spec[2] = "model"
        elif name == "ssm":               # [B, H, P, N]
            if shard_batch:
                spec[0] = ea
            if _div(shape[1], ms):
                spec[1] = "model"
            elif _div(shape[2], ms):
                spec[2] = "model"
        # "index": replicated scalar
        if stacked:
            return P(None, *spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_shape)


def to_shardings(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# EL data-plane placement (shared by the single-run compiled programs in
# repro.el.ingraph / repro.el.events and the sweep engine repro.el.sweep)
# ---------------------------------------------------------------------------

#: Control-plane knobs with a trailing per-edge dim ``[..., E]`` — the
#: sweep engine stacks these as ``[n_cells, E]``; a single run passes
#: them as ``[E]`` (replicated: they are bytes, and the single-run
#: control plane — bandit stats, budgets, finish times — replicates).
EL_EDGE_KNOBS = ("comp", "comm", "min_edge_cost")
#: Scalar control-plane knobs (``[n_cells]`` in a sweep, 0-d in a run).
#: ``event_cap`` is the async engine's traced int32 event budget;
#: ``scn_drift`` / ``policy_id`` are the scenario engine's drift rate
#: and policy-switch selector (``repro.el.scenarios``).
EL_SCALAR_KNOBS = ("ucb_c", "budget", "cost_noise", "async_alpha",
                   "event_cap", "scn_drift", "policy_id")
#: Scenario schedule knobs ``[period, E]`` (``[n_cells, period, E]`` in
#: a sweep) — control plane like every other knob: replicated in a
#: single run, cell-sharded only along the sweep axis.
EL_SCHEDULE_KNOBS = ("scn_active", "scn_mult")


def el_edge_dim_axes(axis_names: Sequence[str],
                     axis_sizes: Dict[str, int],
                     n_edges: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes the ``[n_edges, ...]`` data-plane dim shards over.

    Pure placement policy (no devices), resolver-style like
    ``param_specs``: the edge dim goes over the (``pod``, ``data``) axes
    when it tiles them, and *replicates* otherwise (a 3-edge fleet on a
    2-wide data axis cannot split evenly — the run still works, just
    without edge parallelism).  Returns the axis tuple or ``None``.
    """
    ea = tuple(a for a in _EDGE_AXIS_NAMES if a in axis_names)
    n_shards = _prod(axis_sizes.get(a, 1) for a in ea)
    if ea and n_shards > 1 and n_edges % n_shards == 0:
        return ea
    return None


def el_run_partition_specs(axis_names: Sequence[str],
                           axis_sizes: Dict[str, int],
                           n_edges: int,
                           knob_names: Sequence[str]
                           ) -> Tuple[P, Dict[str, P]]:
    """PartitionSpecs for one EL run's (edge data, knobs).

    The per-edge datasets ``xs [E, N, d]`` / ``ys [E, N]`` shard their
    edge dim over (``pod``, ``data``) via :func:`el_edge_dim_axes`; the
    control-plane knobs all replicate — bandit statistics, budgets and
    finish times are the replicated control plane, only the data plane
    (per-edge params/data) spreads over the mesh.  Pure (no devices) so
    the placement policy is unit-testable, mirroring
    ``repro.el.sweep.sweep_partition_specs``.
    """
    ea = el_edge_dim_axes(axis_names, axis_sizes, n_edges)
    edge_spec = P(ea) if ea else P(None)
    knob_specs = {name: P() for name in knob_names}
    return edge_spec, knob_specs


def el_stacked_param_specs(mesh: Mesh, n_edges: int,
                           stacked_params: Any) -> Any:
    """PartitionSpecs for an ``[n_edges, ...]``-stacked param tree.

    The ``el_state_specs`` layout (``repro.federated.local_sgd``) for
    the in-graph programs: leading edge dim over (``pod``, ``data``)
    when it tiles, each parameter's own dims by the per-arch name+shape
    resolver (large model tensors over ``model``, classic/unknown names
    replicate).  ``stacked_params`` may hold tracers — only ``.shape``
    is read, so this works at trace time inside the compiled programs.

    A scanned LM's group leaves (under ``groups``, a dict: ``[E,
    n_groups, ...]``) keep the stacked ``n_groups`` dim unsharded, as
    ``param_specs``' ``groups`` rule does.  The compiled sync engine
    runs a language model's edges without this stack
    (``repro.el.ingraph.make_token_round``); these specs are the
    classic gather path's, which any tree can take.
    """
    ms = _axis_size(mesh, "model")
    ea = el_edge_dim_axes(mesh.axis_names, dict(
        zip(mesh.axis_names, mesh.devices.shape)), n_edges)

    def leaf_spec(path, leaf) -> P:
        keys = _leaf_path_keys(path)
        name = _leaf_param_name(keys)
        # a scanned model keeps its groups in a dict of stacked leaves,
        # an unrolled one in a list (no extra dim)
        stacked = ("groups" in keys
                   and isinstance(keys[keys.index("groups") + 1], str))
        base = _param_spec(name, leaf.shape[1 + stacked:], ms)
        return P(ea, *([None] * stacked), *base)

    return jax.tree_util.tree_map_with_path(leaf_spec, stacked_params)


def el_cohort_slot_axes(axis_names: Sequence[str],
                        axis_sizes: Dict[str, int],
                        n_slots: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes a fleet cohort's ``[n_slots, ...]`` tenant-slot dim
    shards over: the (``pod``, ``data``) axes when the slot count tiles
    them, replication otherwise — the same tiles-or-replicates policy as
    the single-run edge dim (:func:`el_edge_dim_axes`), because a
    cohort's slot dim *is* its batch dim.  Pure (no devices)."""
    return el_edge_dim_axes(axis_names, axis_sizes, n_slots)


def el_cohort_state_specs(mesh: Mesh, n_slots: int, state: Any) -> Any:
    """PartitionSpecs for a cohort's slot-stacked carry/knob pytree:
    every leaf with a leading ``[n_slots]`` dim shards that dim over the
    cohort slot axes (inner dims replicated — classic-model tensors are
    tiny; the per-slot math is the unsharded cell's, which is what keeps
    fleet runs bit-identical to single runs), anything else replicates.
    ``state`` may hold tracers — only ``.shape`` is read."""
    ea = el_cohort_slot_axes(mesh.axis_names, dict(
        zip(mesh.axis_names, mesh.devices.shape)), n_slots)

    def leaf_spec(leaf) -> P:
        nd = len(leaf.shape)
        if ea and nd >= 1 and leaf.shape[0] == n_slots:
            return P(ea, *([None] * (nd - 1)))
        return P(*([None] * nd))

    return jax.tree.map(leaf_spec, state)


def el_run_in_shardings(mesh: Mesh, model_cfg: Optional[ModelConfig],
                        params_shape: Any,
                        knob_names: Sequence[str]) -> Tuple[Any, ...]:
    """NamedShardings for the compiled EL programs' call signature
    ``(init_params, rng, knobs)``: params by the per-arch resolver
    (classic models replicate — their tensors are tiny), the rng key and
    every knob replicated (the control plane)."""
    if model_cfg is not None:
        p_sh = to_shardings(mesh, param_specs(model_cfg, mesh,
                                              params_shape))
    else:
        p_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                            params_shape)
    rep = NamedSharding(mesh, P())
    return p_sh, rep, {k: rep for k in knob_names}
