"""The fully in-graph sync fast path: one XLA program per EL run.

The host-driven runtime round-trips cloud↔device once per round: a numpy
bandit picks the interval, a jitted scan runs the local iterations, numpy
charges the budgets.  This module stages the *entire* budgeted sync loop —

    in-graph bandit select  (``jax_selection_weights`` + categorical)
      → ``lax.scan`` local iterations, vmapped over edges
      → weighted parameter aggregation
      → in-graph utility (eval-gain or param-delta)
      → ``jax_bandit_update`` + budget charge

— into a single ``lax.while_loop``, so an entire run (hundreds of rounds)
is ONE compiled program with zero host synchronization.  This is what the
previously-dormant ``jax_bandit_*`` functions exist for.

The control-plane knobs (exploration constant, per-edge budget, cost
arrays) are *inputs* of the compiled program, not trace-time constants —
``make_sync_program`` returns ``program(init_params, rng, knobs)`` and
``sync_knobs(cfg)`` derives the knob arrays on the host.  That is what
lets ``repro.el.sweep`` vmap the very same program over a flattened
``[n_cells]`` ablation grid (ucb_c × budget × heterogeneity × seed) and
run a whole sweep as one XLA program.

Supported configuration matrix (see ``check_ingraph_support``) — shared
with the async event-horizon program in ``repro.el.events``:

  ==============  =======================================================
  dimension        supported in-graph
  ==============  =======================================================
  mode             ``sync`` (this module) and ``async`` (the
                   ``repro.el.events`` event-horizon program)
  policy           ``ol4el`` (the compiled 3-step KUBE bandit; one
                   shared bandit in sync, one bandit per edge in async —
                   the policy registry records this as
                   ``Policy.ingraph_modes``); with a ``ScenarioSpec``
                   the sync program routes selection through a traced
                   policy switch that adds the task-allocation
                   baselines (``repro.el.scenarios.baselines``)
  cost_model       ``fixed`` and ``variable`` (the noise scale is the
                   traced ``cost_noise`` knob: i.i.d. multipliers drawn
                   via ``jax.random``, clipped at the host path's 0.1
                   floor; ``cost_noise=0`` multiplies by exactly 1.0, so
                   the fixed program is the noise-0 program bit-for-bit);
                   heavy-tailed / trace-replayed models are
                   ``ScenarioSpec`` cost kinds, layered on top
  scenario         ``None`` — today's programs bit-for-bit — or a
                   ``repro.el.scenarios.ScenarioSpec`` (churn activity
                   masks, straggler cost schedules, data drift as traced
                   knobs; async requires K=1 event waves)
  utility          ``eval_gain`` (needs a jittable metric) and
                   ``param_delta``
  executor         ``InGraphExecutor`` shape — raw per-edge arrays + a
                   jittable ``model.local_step`` (``ClassicExecutor``);
                   a language model on per-edge token rows
                   (``TokenLMExecutor``) as a single sync run
                   (``run_sync_ingraph``) without a scenario: its round
                   runs each edge's steps unmasked and aggregates
                   without replicating the ``[E, params]`` stack
                   (``make_token_round``); async, sweeps and the fleet
                   refuse it
  ==============  =======================================================

Everything else stays on the host paths (``ELSession.run_sync`` /
``run_async``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import OL4ELConfig
from repro.core.bandit import (jax_bandit_init, jax_bandit_update,
                               jax_selection_weights)
from repro.core.coordinator import edge_speed_factors

Params = Any

#: Names (and shapes) of the per-run control-plane inputs of the compiled
#: program: scalars ``ucb_c`` / ``budget`` / ``cost_noise``, per-edge
#: ``comp`` / ``comm`` / ``min_edge_cost`` ``[E]``, and the binding-edge
#: arm costs ``costs_k`` ``[K]``.  The sweep engine stacks each along a
#: leading ``[n_cells]`` axis and vmaps.
KNOB_NAMES = ("ucb_c", "budget", "comp", "comm", "costs_k", "min_edge_cost",
              "cost_noise")

_INGRAPH_UTILITIES = ("eval_gain", "param_delta")
_INGRAPH_COST_MODELS = ("fixed", "variable")

#: Attributes an executor must expose to be in-graph capable
#: (the ``InGraphExecutor`` Protocol, satisfied by ``ClassicExecutor``).
INGRAPH_EXECUTOR_ATTRS = ("model", "edge_data", "eval_set", "batch", "lr")


def _combo(cfg: OL4ELConfig, executor: Any) -> str:
    ex_name = type(executor).__name__ if executor is not None else "<unset>"
    scn = "None" if cfg.scenario is None else type(cfg.scenario).__name__
    return (f"(policy={cfg.policy!r}, cost_model={cfg.cost_model!r}, "
            f"scenario={scn}, executor={ex_name})")


def support_matrix() -> str:
    """The scenario/cost-model support matrix, rendered for error
    messages — so an unsupported combination is rejected at the front
    door with the full menu, instead of failing late inside tracing."""
    from repro.el.scenarios.baselines import INGRAPH_POLICY_ORDER
    return (
        "supported in-graph matrix:\n"
        "  mode        'sync' (repro.el.ingraph) | 'async' "
        "(repro.el.events)\n"
        "  policy      scenario=None: 'ol4el' only; with a ScenarioSpec "
        f"the sync policy switch adds {INGRAPH_POLICY_ORDER[1:]} (other "
        "registry policies run host-side only; async is always the "
        "per-edge 'ol4el' bandit)\n"
        f"  cost_model  cfg.cost_model in {_INGRAPH_COST_MODELS}; "
        "heavy-tailed / replayed models ('pareto' | 'lognormal' | "
        "'trace:<path>') are ScenarioSpec COST KINDS — set "
        "cfg.scenario=ScenarioSpec(cost=CostSpec(kind=...)) (the "
        "--cost-model launch flag builds this for you)\n"
        "  scenario    None (today's programs bit-for-bit) | ScenarioSpec "
        "(churn/straggler/drift schedules; async requires K=1 event "
        "waves)\n"
        f"  utility     {_INGRAPH_UTILITIES}\n"
        "  executor    InGraphExecutor shape (raw per-edge arrays + a "
        "jittable model.local_step, e.g. ClassicExecutor); a language "
        "model on token rows (TokenLMExecutor) only as a single sync run "
        "(run_sync_ingraph) with scenario=None — async, sweep and fleet "
        "refuse it")


def check_ingraph_support(cfg: OL4ELConfig, executor: Any = None, *,
                          caller: str = "the in-graph fast path",
                          single_run: bool = False) -> None:
    """Validate a config/executor combination against the supported matrix.

    Raises ``ValueError`` naming the unsupported (policy, cost_model,
    scenario, executor) combination — every message carries the full
    :func:`support_matrix` so the caller sees the menu, not just the
    rejection — or ``TypeError`` when the executor is not in-graph
    capable.  The per-policy mode support lives in the policy registry
    (``Policy.ingraph_modes``): ``ol4el`` compiles in both modes — one
    shared bandit in sync, per-edge bandits in async — and the
    task-allocation baselines compile through the sync scenario policy
    switch (``repro.el.scenarios.baselines``).  An executor of token
    rows (a language model) is admitted only for a ``single_run`` in
    sync mode without a scenario.
    """
    from repro.el import policies as el_policies
    from repro.el.scenarios.spec import ScenarioSpec
    if cfg.mode not in ("sync", "async"):
        raise ValueError(
            f"{caller} does not support mode={cfg.mode!r}; in-graph modes "
            "are 'sync' (repro.el.ingraph) and 'async' (repro.el.events)\n"
            + support_matrix())
    scn = cfg.scenario
    if scn is not None and not isinstance(scn, ScenarioSpec):
        raise TypeError(
            f"{caller}: cfg.scenario must be a "
            "repro.el.scenarios.ScenarioSpec (or None), got "
            f"{type(scn).__name__}\n" + support_matrix())
    if cfg.mode not in el_policies.ingraph_modes(cfg.policy):
        raise ValueError(
            f"{caller} does not support {_combo(cfg, executor)} in "
            f"mode={cfg.mode!r}: the compiled programs implement the "
            "'ol4el' selection rule (shared bandit in sync, one bandit "
            "per edge in async) plus the sync scenario policy switch; "
            "run other policies through the host paths "
            "ELSession.run_sync()/run_async()\n" + support_matrix())
    if cfg.policy != "ol4el":
        if scn is None:
            raise ValueError(
                f"{caller} does not support {_combo(cfg, executor)}: "
                f"policy {cfg.policy!r} compiles only through the "
                "scenario policy switch — set cfg.scenario "
                "(ScenarioSpec() is the identity scenario)\n"
                + support_matrix())
        if cfg.mode != "sync":
            raise ValueError(
                f"{caller} does not support {_combo(cfg, executor)} in "
                f"mode={cfg.mode!r}: the policy switch is sync-only (the "
                "async program keeps the paper's per-edge 'ol4el' "
                "bandit)\n" + support_matrix())
    if cfg.cost_model not in _INGRAPH_COST_MODELS:
        hint = ""
        if cfg.cost_model in ("pareto", "lognormal") or str(
                cfg.cost_model).startswith("trace"):
            hint = (f" — {cfg.cost_model!r} is a ScenarioSpec cost KIND, "
                    "not a cfg.cost_model: set cfg.scenario="
                    "ScenarioSpec(cost=CostSpec(kind=...))")
        raise ValueError(
            f"{caller} does not support {_combo(cfg, executor)}: "
            f"cost_model must be one of {_INGRAPH_COST_MODELS}{hint}\n"
            + support_matrix())
    if cfg.utility not in _INGRAPH_UTILITIES:
        raise ValueError(
            f"{caller} does not support utility={cfg.utility!r} with "
            f"{_combo(cfg, executor)}: in-graph utilities are "
            f"{_INGRAPH_UTILITIES}\n" + support_matrix())
    if executor is not None and is_token_data(
            getattr(executor, "edge_data", None)) and not (
            single_run and cfg.mode == "sync" and scn is None):
        raise ValueError(
            f"{caller} does not support {_combo(cfg, executor)} in "
            f"mode={cfg.mode!r}: a language model on token rows compiles "
            "only as a single sync run (run_sync_ingraph) with "
            "scenario=None\n" + support_matrix())
    if executor is not None:
        missing = [a for a in INGRAPH_EXECUTOR_ATTRS
                   if not hasattr(executor, a)]
        if missing:
            raise TypeError(
                f"{type(executor).__name__} is not in-graph capable "
                f"(missing .{missing[0]}); {caller} with "
                f"{_combo(cfg, executor)} needs an InGraphExecutor such "
                "as ClassicExecutor (raw per-edge arrays + a jittable "
                "model.local_step)")


def base_cost_knobs(cfg: OL4ELConfig) -> Dict[str, np.ndarray]:
    """The mode-independent control-plane knobs both compiled programs
    share: scalars ``ucb_c`` / ``budget`` / ``cost_noise`` and the
    per-edge cost arrays.  One derivation keeps the sync round and the
    async event-horizon program (``repro.el.events``) in lockstep with
    the host coordinator's feasibility/termination arithmetic."""
    speed = edge_speed_factors(cfg.n_edges, cfg.heterogeneity)
    comp = np.asarray(cfg.comp_cost * speed, np.float32)            # [E]
    comm = np.full((cfg.n_edges,), cfg.comm_cost, np.float32)       # [E]
    return {
        "ucb_c": np.float32(cfg.ucb_c),
        "budget": np.float32(cfg.budget),
        "comp": comp,
        "comm": comm,
        "min_edge_cost": comp + comm,                               # [E]
        # noise applies only in variable-cost mode (host realized_cost
        # semantics); the programs always trace the noise path — a 0.0
        # knob multiplies costs by exactly 1.0, bit-for-bit fixed.
        "cost_noise": np.float32(cfg.cost_noise
                                 if cfg.cost_model == "variable" else 0.0),
    }


def sync_knobs(cfg: OL4ELConfig) -> Dict[str, np.ndarray]:
    """Host-side control-plane inputs of the compiled sync program.

    All float32, computed with the exact numpy arithmetic the scalar fast
    path used to bake in as constants, so passing them as traced inputs
    reproduces the same program bit-for-bit.  The sweep engine calls this
    once per cell and stacks along a leading ``[n_cells]`` axis.
    """
    knobs = base_cost_knobs(cfg)
    intervals_f = np.arange(1, cfg.max_interval + 1, dtype=np.float32)
    # sync feasibility is scored against the binding (slowest) edge
    worst = int(np.argmax(knobs["comp"]))
    knobs["costs_k"] = (intervals_f * knobs["comp"][worst]
                        + knobs["comm"][worst])                     # [K]
    if cfg.scenario is not None:
        from repro.el.scenarios.schedule import scenario_knobs
        knobs.update(scenario_knobs(cfg))
    return knobs


def sync_knob_names(cfg: OL4ELConfig) -> Tuple[str, ...]:
    """The traced-input names of this config's compiled sync program:
    ``KNOB_NAMES``, plus the scenario schedule knobs and the policy
    selector when ``cfg.scenario`` is set (exactly the keys
    ``sync_knobs(cfg)`` returns)."""
    if cfg.scenario is not None:
        from repro.el.scenarios.schedule import scenario_knob_names
        return KNOB_NAMES + scenario_knob_names("sync")
    return KNOB_NAMES


def _pad_edge_data(edge_data: List[Dict[str, np.ndarray]]
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stack per-edge datasets [E, Nmax, d] / [E, Nmax] with wraparound
    padding (padding rows repeat real rows, so uniform index sampling over
    [0, n_e) never sees them)."""
    n = np.array([len(d["y"]) for d in edge_data], np.int32)
    n_max = int(n.max())
    dim = edge_data[0]["x"].shape[-1]
    xs = np.zeros((len(edge_data), n_max, dim), np.float32)
    ys = np.zeros((len(edge_data), n_max), np.int32)
    for e, d in enumerate(edge_data):
        reps = -(-n_max // len(d["y"]))
        xs[e] = np.tile(np.asarray(d["x"], np.float32), (reps, 1))[:n_max]
        ys[e] = np.tile(np.asarray(d["y"], np.int32), reps)[:n_max]
    return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(n)


def default_metric_fn(model, eval_set, metric_name: str
                      ) -> Optional[Callable[[Params], jax.Array]]:
    """A jittable eval metric when the model supports one (SVM accuracy,
    a language model's held-out negative loss); None means the in-graph
    path must run with a params-only utility."""
    if metric_name == "neg_loss" and hasattr(model, "token_loss"):
        toks = jnp.asarray(eval_set["tokens"], jnp.int32)
        return lambda params: -model.token_loss(params, toks)
    if metric_name == "accuracy" and hasattr(model, "scores"):
        xe = jnp.asarray(eval_set["x"], jnp.float32)
        ye = jnp.asarray(eval_set["y"], jnp.int32)

        def accuracy(params):
            pred = jnp.argmax(model.scores(params, xe), -1)
            return jnp.mean((pred == ye).astype(jnp.float32))

        return accuracy
    return None


def _tree_l2(a: Params, b: Params) -> jax.Array:
    total = sum(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    return jnp.sqrt(total)


def make_local_block(model, xs: jax.Array, ys: jax.Array,
                     n_per_edge: jax.Array, batch: int, lr: float,
                     k: int, *, drift: bool = False) -> Callable:
    """``local_block(params, edge, interval, key)`` — ``interval`` masked
    local iterations on one edge's shard (a fixed-length ``lax.scan`` of
    ``k`` steps, steps past ``interval`` masked out).  Shared by the sync
    round body, the async event body (``repro.el.events``) and its host
    reference loop, so all three sample identical minibatch streams from
    identical keys.

    ``drift=True`` (the scenario path) adds a trailing ``shift`` argument
    — the traced drift phase ``scn_drift * t`` — and rotates every
    sampled index by ``floor(shift * n_e) mod n_e``, so the effective
    local distribution walks over the edge's shard round by round
    (non-stationary data drift).  ``shift=0`` rotates by zero, and with
    ``drift=False`` the rotation is statically absent — the classic
    block, unchanged.
    """

    def local_block(params: Params, edge: jax.Array, interval: jax.Array,
                    key: jax.Array, shift: jax.Array = None) -> Params:
        def body(p, step):
            u = jax.random.uniform(jax.random.fold_in(key, step), (batch,))
            idx = (u * n_per_edge[edge].astype(jnp.float32)).astype(jnp.int32)
            if drift:
                off = (shift * n_per_edge[edge].astype(jnp.float32)
                       ).astype(jnp.int32)
                idx = jnp.mod(idx + off, n_per_edge[edge])
            b = {"x": xs[edge][idx], "y": ys[edge][idx]}
            p2, _ = model.local_step(p, b, lr)
            take = step < interval
            return jax.tree.map(
                lambda a, c: jnp.where(take, c, a), p, p2), None

        params, _ = lax.scan(body, params, jnp.arange(k))
        return params

    return local_block


def _shard_edge_data(mesh, n_edges: int, *arrays: jax.Array):
    """Place the ``[E, ...]`` padded datasets on the mesh with their edge
    dim over (``pod``, ``data``) — replicated when the fleet does not
    tile the edge axes (the ``el_run_partition_specs`` policy)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import el_run_partition_specs
    edge_spec, _ = el_run_partition_specs(
        mesh.axis_names, dict(zip(mesh.axis_names,
                                  np.shape(mesh.devices))), n_edges, ())
    return tuple(
        jax.device_put(a, NamedSharding(
            mesh, P(*edge_spec, *([None] * (a.ndim - 1)))))
        for a in arrays)


def _edge_stack_constraints(mesh, n_edges: int
                            ) -> Tuple[Callable, Callable]:
    """Two trace-time pytree constraints for the ``[E, ...]`` per-edge
    parameter stack: ``constrain`` pins it to the sharded
    ``el_stacked_param_specs`` layout (edge dim over pod/data, tensor
    dims by the per-arch resolver), ``gather`` pins it replicated — the
    explicit all-gather in front of every cross-edge reduction that
    keeps sharded runs bit-identical to unsharded ones.  Both are
    identity when ``mesh`` is None.
    """
    if mesh is None:
        ident = lambda tree: tree                              # noqa: E731
        return ident, ident

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import el_stacked_param_specs, to_shardings

    def constrain(tree):
        specs = el_stacked_param_specs(mesh, n_edges, tree)
        return lax.with_sharding_constraint(tree,
                                            to_shardings(mesh, specs))

    def gather(tree):
        return lax.with_sharding_constraint(
            tree, jax.tree.map(lambda _: NamedSharding(mesh, P()), tree))

    return constrain, gather


def is_token_data(edge_data) -> bool:
    """Whether per-edge datasets are token rows (``{"tokens": [N_e,
    S+1]}``, a language model's edges) rather than ``x``/``y`` rows."""
    return bool(edge_data) and "tokens" in edge_data[0]


def _pad_token_data(edge_data: List[Dict[str, np.ndarray]]
                    ) -> Tuple[jax.Array, jax.Array]:
    """Stack per-edge token rows ``[E, Nmax, S+1]`` (int32, wraparound
    padding as ``_pad_edge_data``) and the per-edge counts ``[E]``."""
    n = np.array([len(d["tokens"]) for d in edge_data], np.int32)
    n_max = int(n.max())
    toks = np.stack([np.resize(np.asarray(d["tokens"], np.int32),
                               (n_max,) + np.shape(d["tokens"])[1:])
                     for d in edge_data])
    return jnp.asarray(toks), jnp.asarray(n)


def make_token_block(model, batch: int, lr: float) -> Callable:
    """``local_block(params, toks, n, interval, key)`` — ``interval``
    local SGD steps (``model.local_step``) on one edge's token rows
    ``toks`` ``[N, S+1]`` of which the first ``n`` are its own.  Each
    step draws ``batch`` rows as ``make_local_block`` draws minibatch
    rows (row = floor(u * n), ``u`` from ``fold_in(key, step)``), so the
    streams are the classic block's; steps past ``interval`` are not
    run (a loop of ``interval`` trips, not a masked scan: a language
    model's step is too dear to compute and throw away)."""

    def local_block(params: Params, toks: jax.Array, n: jax.Array,
                    interval: jax.Array, key: jax.Array) -> Params:
        def step(s, p):
            u = jax.random.uniform(jax.random.fold_in(key, s), (batch,))
            idx = (u * n.astype(jnp.float32)).astype(jnp.int32)
            p2, _ = model.local_step(p, {"tokens": toks[idx]}, lr)
            return p2

        return lax.fori_loop(0, interval, step, params)

    return local_block


def fixed_order_mean(stack: Params, w: jax.Array) -> Params:
    """``sum_e w[e] stack[e]`` leaf by leaf, edge 0 first, one edge at a
    time: the same sum in the same order on whatever slice of a leaf it
    is given, which is what makes the sharded exchange below equal the
    unsharded mean bit for bit."""
    def leaf_mean(x):
        acc = x[0] * w[0]
        for e in range(1, x.shape[0]):
            acc = acc + x[e] * w[e]
        return acc

    return jax.tree.map(leaf_mean, stack)


def _exchange_mean(leaf: jax.Array, w: jax.Array, axes, n_shards: int
                   ) -> jax.Array:
    """Inside ``shard_map``: the weighted mean over every edge of one
    leaf whose local stack ``[E_local, ...]`` holds this chip's edges.
    An all-to-all gives each chip a 1/n_shards slice of every edge's
    leaf (a reduce-scatter that keeps the sum's order), the chip sums
    its slice in ``fixed_order_mean``'s order, and an all-gather of the
    sums rebuilds the leaf: no chip ever holds the ``[E, ...]`` stack."""
    shape = leaf.shape[1:]
    dims = [d for d in range(len(shape)) if shape[d] % n_shards == 0]
    d = max(dims, key=lambda i: shape[i]) if dims else len(shape) - 1
    pad = -shape[d] % n_shards
    if pad:                    # no dim divides: pad one, slice it back
        widths = [(0, 0)] * leaf.ndim
        widths[1 + d] = (0, pad)
        leaf = jnp.pad(leaf, widths)
    part = lax.all_to_all(leaf, axes, 1 + d, 0, tiled=True)
    full = lax.all_gather(fixed_order_mean(part, w), axes, axis=d,
                          tiled=True)
    if pad:
        full = lax.slice_in_dim(full, 0, full.shape[d] - pad, axis=d)
    return full.reshape(shape)


def make_token_round(model, edge_data, n_edges: int, *, lr: float,
                     batch: int, w_agg: jax.Array, mesh=None) -> Callable:
    """``edge_round(params, interval, keys)`` for a language model's
    sync round: every edge runs ``interval`` local steps from the global
    ``params`` on its token rows, and the cloud takes the n_e-weighted
    mean (``fixed_order_mean``).  Without a mesh (or where the edges do
    not tile its edge axes) the edges run one after another and the
    mean is taken whole (on a mesh, by every chip alike).  With edges
    that tile the mesh's edge axes, the round is a ``shard_map`` over
    them: each chip runs its own edges, one at a time, on its shard of
    the token rows, and the mean is ``_exchange_mean``'s, so a chip
    holds the global copy, its edge's copy and one parameter-sized
    accumulator, never the ``[E, params]`` stack (the classic round's
    all-gather would put ``E`` copies on every chip)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import el_edge_dim_axes
    toks, n_per_edge = _pad_token_data(edge_data)
    local_block = make_token_block(model, batch, lr)
    axes = None
    if mesh is not None:
        axes = el_edge_dim_axes(mesh.axis_names, dict(
            zip(mesh.axis_names, np.shape(mesh.devices))), n_edges)
        (toks,) = _shard_edge_data(mesh, n_edges, toks)

    def run_edges(params, toks, n, keys, interval):
        """The ``[E_here, ...]`` stack of the edges whose rows ``toks``
        holds, run one after another."""
        return lax.map(lambda a: local_block(params, toks[a[0]], a[1],
                                             interval, a[2]),
                       (jnp.arange(toks.shape[0]), n, keys))

    def whole_mean(params, toks, n, keys, interval):
        return fixed_order_mean(run_edges(params, toks, n, keys, interval),
                                w_agg)

    if mesh is None:
        return lambda params, interval, keys: whole_mean(
            params, toks, n_per_edge, keys, interval)
    if axes is None:
        # every chip runs every edge: the model's kernels cannot be
        # partitioned, so even a replicated round is a shard_map
        body, edge = whole_mean, P()
    else:
        sizes = dict(zip(mesh.axis_names, np.shape(mesh.devices)))
        n_shards = int(np.prod([sizes[a] for a in axes]))
        edge = P(axes)

        def body(params, toks, n, keys, interval):
            return jax.tree.map(
                lambda leaf: _exchange_mean(leaf, w_agg, axes, n_shards),
                run_edges(params, toks, n, keys, interval))

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(), edge, edge, edge, P()),
                            out_specs=P(), check_vma=False)
    return lambda params, interval, keys: sharded(
        params, toks, n_per_edge, keys, interval)


@dataclasses.dataclass(frozen=True)
class ELCell:
    """One EL run's compiled loop, split into composable pieces.

    The four closures share the program's dict carry (``carry["t"]`` is
    the round/event counter, ``carry["hist"]`` the ``[horizon]`` history
    arrays) and all take the traced knob dict explicitly, so callers can
    compose them into different drivers:

      * ``make_sync_program`` / ``make_async_program`` fuse
        ``init → while(cond, body) → finalize`` into ONE program per run
        (the single-run and sweep fast paths);
      * the fleet server (``repro.el.fleet``) instead vmaps a bounded
        chunk of ``body`` over tenant *slots* and carries the stacked
        state across calls — continuous batching over the same cell,
        bit-identical because ``body`` is the same traced function.
    """

    init: Callable       # (init_params, rng, knobs) -> carry
    cond: Callable       # (carry, knobs) -> bool scalar (continue?)
    body: Callable       # (carry, knobs) -> carry (one round/event)
    finalize: Callable   # (carry, knobs) -> (params, out dict)
    horizon: int         # history length (max_rounds / max_events)


def make_sync_cell(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                   lr: float, batch: int,
                   n_samples: Optional[np.ndarray] = None,
                   metric_fn: Optional[Callable] = None,
                   metric_name: str = "accuracy",
                   max_rounds: int = 512, mesh=None,
                   telemetry=None) -> ELCell:
    """The budgeted sync round as an :class:`ELCell` — the unfused form
    of ``make_sync_program`` (which recomposes exactly these closures
    into one ``lax.while_loop``); see that function for the semantics,
    knob contract and mesh placement.

    ``telemetry=`` is the static in-graph observability gate
    (``repro.obs.rings.as_spec`` coercions: None/False off, True/int/
    ``TelemetrySpec`` on).  Off builds exactly the carry below — no
    extra key, no extra op, the same traced program bit-for-bit.  On
    adds a ``carry["telem"]`` ring subtree, each round recording arm,
    straggler cost, residual budget and the bandit's per-arm statistics
    at ``t % ring_size`` (under ``jax.named_scope("obs.telemetry")``),
    surfaced by ``finalize`` as ``out["telemetry"]``.
    """
    from repro.obs.rings import (as_spec, finalize_telemetry,
                                 sync_ring_init, sync_ring_record)
    spec = as_spec(telemetry)
    check_ingraph_support(cfg, caller="make_sync_program")
    # fleet-dynamics scenario: None keeps every closure below EXACTLY
    # today's traced code (the scenario branch is statically absent);
    # a ScenarioSpec swaps in the mask-aware cond/body variants.
    scn = cfg.scenario
    period = scn.period if scn is not None else 0

    n_edges, k = cfg.n_edges, cfg.max_interval

    w_agg = (np.ones(n_edges) if n_samples is None
             else np.asarray(n_samples, np.float64))
    w_agg = jnp.asarray(w_agg / w_agg.sum(), jnp.float32)
    tokens = is_token_data(edge_data)
    if tokens:
        token_round = make_token_round(model, edge_data, n_edges, lr=lr,
                                       batch=batch, w_agg=w_agg, mesh=mesh)
        seq_len = int(np.shape(edge_data[0]["tokens"])[1]) - 1
    else:
        xs, ys, n_per_edge = _pad_edge_data(edge_data)
        constrain_edge_stack, gather_edge_stack = _edge_stack_constraints(
            mesh, n_edges)
        if mesh is not None:
            xs, ys = _shard_edge_data(mesh, n_edges, xs, ys)
        local_block = make_local_block(model, xs, ys, n_per_edge, batch,
                                       lr, k, drift=scn is not None)

    if metric_fn is None:
        metric_fn = default_metric_fn(model, eval_set, metric_name)
    if cfg.utility == "eval_gain" and metric_fn is None:
        raise ValueError(
            "utility='eval_gain' needs a jittable metric; pass metric_fn= "
            "or use utility='param_delta'")
    if tokens and mesh is not None and metric_fn is not None:
        # a language model's metric runs its kernels, which XLA cannot
        # partition: every chip computes it whole on its global copy
        from jax.sharding import PartitionSpec as P
        metric_fn = jax.shard_map(metric_fn, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False)

    def weighted_mean(trees: Params) -> Params:
        return jax.tree.map(
            lambda leaf: jnp.einsum(
                "e...,e->...", leaf.astype(jnp.float32), w_agg
            ).astype(leaf.dtype), trees)

    def classic_round(params: Params, interval: jax.Array,
                      keys: jax.Array) -> Params:
        edge_ids = jnp.arange(n_edges)
        bcast = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_edges,) + x.shape), params)
        # data plane: the per-edge param stack (and with it the
        # vmapped local blocks) shards over the mesh's edge axes ...
        bcast = constrain_edge_stack(bcast)
        edge_params = jax.vmap(local_block, in_axes=(0, 0, None, 0))(
            bcast, edge_ids, interval, keys)
        # ... and is all-gathered BEFORE the aggregation so the
        # einsum reduces replicated, in the unsharded program's
        # exact accumulation order (bit-identity; a psum over the
        # sharded edge dim would be an ulp off)
        edge_params = gather_edge_stack(edge_params)
        return weighted_mean(edge_params)

    edge_round = token_round if tokens else classic_round

    def init(init_params: Params, rng: jax.Array,
             knobs: Dict[str, jax.Array]) -> Dict[str, Any]:
        bstate = jax_bandit_init(k)
        consumed = jnp.zeros((n_edges,), jnp.float32)
        if metric_fn is not None:
            prev_metric = metric_fn(init_params)
        else:
            prev_metric = jnp.float32(jnp.nan)
        hist = {
            "metric": jnp.full((max_rounds,), jnp.nan, jnp.float32),
            "utility": jnp.zeros((max_rounds,), jnp.float32),
            "interval": jnp.zeros((max_rounds,), jnp.int32),
            "consumed": jnp.zeros((max_rounds,), jnp.float32),
            "wall": jnp.zeros((max_rounds,), jnp.float32),
        }
        if scn is not None:
            hist["active_edges"] = jnp.zeros((max_rounds,), jnp.int32)
        carry = {"params": init_params, "bstate": bstate,
                 "consumed": consumed, "t": jnp.int32(0), "rng": rng,
                 "prev_metric": prev_metric, "wall": jnp.float32(0.0),
                 "hist": hist}
        if spec is not None:
            carry["telem"] = sync_ring_init(spec, k,
                                            scenario=scn is not None)
        return carry

    def cond(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        resid = knobs["budget"] - carry["consumed"]                  # [E]
        affordable = (jnp.min(resid)
                      >= jnp.min(knobs["costs_k"]) - 1e-12)
        exhausted = jnp.any(resid < knobs["min_edge_cost"])
        return (carry["t"] < max_rounds) & affordable & ~exhausted

    def body(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        ucb_c = knobs["ucb_c"]
        budget = knobs["budget"]
        comp, comm = knobs["comp"], knobs["comm"]
        costs_k = knobs["costs_k"]
        cost_noise = knobs["cost_noise"]
        params, bstate = carry["params"], carry["bstate"]
        consumed, t = carry["consumed"], carry["t"]
        prev_metric, wall = carry["prev_metric"], carry["wall"]
        hist = carry["hist"]

        rng, k_sel, k_data = jax.random.split(carry["rng"], 3)
        resid = jnp.min(budget - consumed)
        w = jax_selection_weights(bstate, resid, costs_k, ucb_c)
        logits = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)),
                           -jnp.inf)
        arm = jax.random.categorical(k_sel, logits)
        interval = arm + 1

        edge_ids = jnp.arange(n_edges)
        keys = jax.vmap(lambda e: jax.random.fold_in(k_data, e))(edge_ids)
        new_params = edge_round(params, interval, keys)

        # straggler semantics: every edge's clock advances by the
        # slowest edge's round time (matches CloudCoordinator.charge
        # in run_sync)
        round_costs = interval.astype(jnp.float32) * comp + comm  # [E]
        # host semantics (CloudCoordinator.realized_cost): each
        # edge's realized cost is the expected cost times an
        # i.i.d. multiplier max(0.1, 1 + noise·N(0,1)).  The key
        # is derived from k_data OUTSIDE the per-edge fold range
        # [0, n_edges), so the fixed-cost RNG streams are
        # untouched.  ``cost_noise`` is a TRACED knob (sweepable):
        # a 0.0 knob multiplies by exactly 1.0, so fixed-cost runs
        # are the noise-0 program bit-for-bit.
        k_cost = jax.random.fold_in(k_data, n_edges)
        eps = jax.random.normal(k_cost, (n_edges,))
        mult = jnp.maximum(0.1, 1.0 + cost_noise * eps)
        round_costs = round_costs * mult
        slot = jnp.max(round_costs)
        consumed = consumed + slot

        if metric_fn is not None:
            metric = metric_fn(new_params)
        else:
            metric = jnp.float32(jnp.nan)
        if cfg.utility == "eval_gain":
            utility = metric - prev_metric
        else:                              # param_delta (§III.A)
            utility = 1.0 / (1.0 + _tree_l2(params, new_params))

        bstate = jax_bandit_update(bstate, arm, utility, slot)
        wall = wall + slot
        hist = {
            "metric": hist["metric"].at[t].set(metric),
            "utility": hist["utility"].at[t].set(utility),
            "interval": hist["interval"].at[t].set(interval),
            "consumed": hist["consumed"].at[t].set(jnp.sum(consumed)),
            "wall": hist["wall"].at[t].set(wall),
        }
        new_carry = {"params": new_params, "bstate": bstate,
                     "consumed": consumed, "t": t + 1, "rng": rng,
                     "prev_metric": metric, "wall": wall, "hist": hist}
        if spec is not None:
            with jax.named_scope("obs.telemetry"):
                new_carry["telem"] = sync_ring_record(
                    carry["telem"], spec, t=t, arm=arm, round_cost=slot,
                    budget_resid=jnp.min(budget - consumed),
                    bstate=bstate)
        return new_carry

    def cond_scn(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        # feasibility paces on the tightest ACTIVE edge this round —
        # dropped edges neither spend nor constrain the fleet
        resid = knobs["budget"] - carry["consumed"]                  # [E]
        act = knobs["scn_active"][jnp.mod(carry["t"], period)] > 0
        affordable = (jnp.min(jnp.where(act, resid, jnp.inf))
                      >= jnp.min(knobs["costs_k"]) - 1e-12)
        exhausted = jnp.any(act & (resid < knobs["min_edge_cost"]))
        return (carry["t"] < max_rounds) & affordable & ~exhausted

    def body_scn(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        from repro.el.scenarios.baselines import select_arm_switch
        ucb_c = knobs["ucb_c"]
        budget = knobs["budget"]
        comp, comm = knobs["comp"], knobs["comm"]
        costs_k = knobs["costs_k"]
        cost_noise = knobs["cost_noise"]
        scn_active, scn_mult = knobs["scn_active"], knobs["scn_mult"]
        params, bstate = carry["params"], carry["bstate"]
        consumed, t = carry["consumed"], carry["t"]
        prev_metric, wall = carry["prev_metric"], carry["wall"]
        hist = carry["hist"]

        slot_i = jnp.mod(t, period)
        act = scn_active[slot_i] > 0                                 # [E]

        rng, k_sel, k_data = jax.random.split(carry["rng"], 3)
        resid = jnp.min(jnp.where(act, budget - consumed, jnp.inf))
        # traced policy switch: OL4EL bandit vs the task-allocation
        # baselines, selected by the policy_id knob (sweepable axis)
        arm = select_arm_switch(knobs["policy_id"], bstate, resid,
                                costs_k, ucb_c, k_sel)
        interval = arm + 1

        edge_ids = jnp.arange(n_edges)
        keys = jax.vmap(lambda e: jax.random.fold_in(k_data, e))(edge_ids)
        bcast = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_edges,) + x.shape), params)
        bcast = constrain_edge_stack(bcast)
        # a dropped edge runs ZERO masked work: interval 0 masks every
        # scan step; the drift shift rotates its sampling window
        edge_iv = jnp.where(act, interval, 0)
        shift = knobs["scn_drift"] * t.astype(jnp.float32)
        edge_params = jax.vmap(local_block, in_axes=(0, 0, 0, 0, None))(
            bcast, edge_ids, edge_iv, keys, shift)
        edge_params = gather_edge_stack(edge_params)
        # mask-aware aggregation: dead edges carry zero weight and the
        # live weights renormalize (the merge chain skips them)
        w_act = w_agg * act.astype(jnp.float32)
        w_act = w_act / jnp.maximum(jnp.sum(w_act), 1e-12)
        new_params = jax.tree.map(
            lambda leaf: jnp.einsum(
                "e...,e->...", leaf.astype(jnp.float32), w_act
            ).astype(leaf.dtype), edge_params)

        round_costs = interval.astype(jnp.float32) * comp + comm  # [E]
        k_cost = jax.random.fold_in(k_data, n_edges)
        eps = jax.random.normal(k_cost, (n_edges,))
        mult = jnp.maximum(0.1, 1.0 + cost_noise * eps)
        # scenario straggler spikes compose with the i.i.d. noise model
        round_costs = round_costs * mult * scn_mult[slot_i]
        # the slot paces on the slowest ACTIVE edge, and only active
        # edges are charged — a dropped edge's budget is untouched
        slot = jnp.max(jnp.where(act, round_costs, 0.0))
        consumed = consumed + jnp.where(act, slot, 0.0)

        if metric_fn is not None:
            metric = metric_fn(new_params)
        else:
            metric = jnp.float32(jnp.nan)
        if cfg.utility == "eval_gain":
            utility = metric - prev_metric
        else:                              # param_delta (§III.A)
            utility = 1.0 / (1.0 + _tree_l2(params, new_params))

        bstate = jax_bandit_update(bstate, arm, utility, slot)
        wall = wall + slot
        n_active = jnp.sum(act.astype(jnp.int32))
        hist = {
            "metric": hist["metric"].at[t].set(metric),
            "utility": hist["utility"].at[t].set(utility),
            "interval": hist["interval"].at[t].set(interval),
            "consumed": hist["consumed"].at[t].set(jnp.sum(consumed)),
            "wall": hist["wall"].at[t].set(wall),
            "active_edges": hist["active_edges"].at[t].set(n_active),
        }
        new_carry = {"params": new_params, "bstate": bstate,
                     "consumed": consumed, "t": t + 1, "rng": rng,
                     "prev_metric": metric, "wall": wall, "hist": hist}
        if spec is not None:
            # dropout/rejoin deltas vs the previous round's mask (round
            # 0 measures against the nominal full fleet)
            prev = jnp.where(t > 0,
                             scn_active[jnp.mod(t - 1, period)],
                             jnp.ones((n_edges,), jnp.float32)) > 0
            dropouts = jnp.sum((prev & ~act).astype(jnp.int32))
            rejoins = jnp.sum((~prev & act).astype(jnp.int32))
            with jax.named_scope("obs.telemetry"):
                new_carry["telem"] = sync_ring_record(
                    carry["telem"], spec, t=t, arm=arm, round_cost=slot,
                    budget_resid=jnp.min(budget - consumed),
                    bstate=bstate, scn=(n_active, dropouts, rejoins))
        return new_carry

    def finalize(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        out = dict(carry["hist"])
        out["n_rounds"] = carry["t"]
        out["budgets_left"] = knobs["budget"] - carry["consumed"]
        out["arm_pulls"] = carry["bstate"]["counts"]
        out["wall_time"] = carry["wall"]
        if tokens:
            # the local steps and tokens each edge trained (every edge
            # runs each round's interval in sync)
            steps = jnp.full((n_edges,), jnp.sum(carry["hist"]["interval"]),
                             jnp.int32)
            out["edge_steps"] = steps
            out["edge_tokens"] = steps * (batch * seq_len)
        if spec is not None:
            out["telemetry"] = finalize_telemetry(carry["telem"],
                                                  carry["t"], spec)
        return carry["params"], out

    if scn is not None:
        cond, body = cond_scn, body_scn
    return ELCell(init=init, cond=cond, body=body, finalize=finalize,
                  horizon=max_rounds)


def make_sync_program(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                      lr: float, batch: int,
                      n_samples: Optional[np.ndarray] = None,
                      metric_fn: Optional[Callable] = None,
                      metric_name: str = "accuracy",
                      max_rounds: int = 512, mesh=None, telemetry=None):
    """Build ``program(init_params, rng, knobs) -> (params, out)`` — the
    whole budgeted sync run as one ``lax.while_loop``, with the
    control-plane knobs (see ``KNOB_NAMES`` / ``sync_knobs``) as traced
    inputs so one compiled program serves any (ucb_c, budget, cost) point
    — and so ``repro.el.sweep`` can vmap it over a whole ablation grid.

    With ``mesh=`` the run's ``[n_edges, ...]`` data plane shards over
    the mesh's (``pod``, ``data``) axes and model tensors over ``model``
    (``repro.sharding.el_run_partition_specs`` placement): the per-edge
    datasets and the broadcast per-edge parameter stack live sharded, so
    the vmapped local blocks — the hot path — run edge-parallel.  The
    control plane (bandit state, budgets, history) stays replicated, and
    the per-edge params are explicitly all-gathered *before* the
    aggregation einsum so every reduction executes replicated in the
    same order as the unsharded program — that is what makes a sharded
    run bit-identical to the mesh-less one (tested on a debug mesh)
    rather than an ulp off from partial-sum reordering.

    ``out`` is a dict of device arrays: per-round ``metric``, ``utility``,
    ``interval``, ``consumed`` (cumulative total across edges), ``wall``
    (cumulative straggler time), plus scalars ``n_rounds`` and the final
    per-edge ``budgets_left``.  With ``telemetry=`` (see
    ``make_sync_cell``) it gains a nested ``out["telemetry"]`` ring
    subtree; without it the program is today's, bit-for-bit.
    """
    cell = make_sync_cell(
        model, edge_data, eval_set, cfg, lr=lr, batch=batch,
        n_samples=n_samples, metric_fn=metric_fn, metric_name=metric_name,
        max_rounds=max_rounds, mesh=mesh, telemetry=telemetry)

    def program(init_params: Params, rng: jax.Array,
                knobs: Dict[str, jax.Array]):
        carry = lax.while_loop(lambda c: cell.cond(c, knobs),
                               lambda c: cell.body(c, knobs),
                               cell.init(init_params, rng, knobs))
        return cell.finalize(carry, knobs)

    return program

