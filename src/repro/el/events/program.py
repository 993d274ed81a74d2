"""The compiled asynchronous EL engine: one XLA program per async run.

The host ``ELSession.run_async`` drives a Python priority queue: pop the
next finishing edge, train its block, staleness-merge it into the global
model, update that edge's bandit, schedule its next block.  This module
reformulates that event loop with **no host priority queue** (à la
Mohammad & Sorour's asynchronous mobile edge learning): edge finish
times live in an ``[n_edges]`` array, and each ``lax.while_loop`` step

    argmin finish-time  (the next event)
      → masked local block on the event edge (shared ``make_local_block``)
      → staleness-weighted masked merge (``jnp.where``-free tree mix,
        scatter into the per-edge fetched-params stack)
      → in-graph utility → per-edge ``jax_bandit_update`` + budget charge
      → schedule the edge's next block (``schedule_block``), advancing
        its finish time — or ``+inf`` when its budget affords no arm

until budget exhaustion silences every edge or the fixed event horizon
is reached.  An entire async run — hundreds of events — is ONE compiled
program with zero host synchronization, the async half of the paper's
headline claim joining the fast path.

**K-event waves** (``batch_k > 1``, the sharded fast path): instead of
one argmin pop per loop step, a wave pops the K earliest completions
with ``lax.top_k``, accepts the prefix of lanes that provably precede
any block an earlier lane could reschedule (``wave_safe_gap`` — a
rescheduled block costs at least ``fl(min_edge_cost · mult_floor)``, so
every lane with ``f_(j) < fl(f_(0) + gap)`` is order-safe), runs the
accepted lanes' local blocks in one ``lax.map`` over a slice-local
``[K, ...]`` gather of the fetched-params stack (per lane, not vmapped:
a TPU rounds a batched block's minibatch sums differently), and replays
the merge / bandit / schedule control plane sequentially per lane
(masked ``lax.cond``) so every computed value equals the one-event
program's.
Wave lanes are always DISTINCT edges (one in-flight block per edge), the
per-event RNG chain advances exactly ``n_batch`` splits, and history /
telemetry writes coalesce into one drop-mode vector scatter per field —
the processed event order, merge values, charged costs and arm pulls are
identical to ``batch_k=1`` (tested), while the while-loop iterates ~K
times fewer, amortizing the sharded control plane's per-step collectives.

Like the sync program, the control-plane knobs (``ASYNC_KNOB_NAMES``)
are traced inputs — ``make_async_program`` returns
``program(init_params, rng, knobs)`` — so ``repro.el.sweep`` vmaps one
program over a flattened ablation grid (now including ``async_alpha``
and ``cost_noise`` axes) and shards it over the mesh like sync cells.

``make_async_kernels`` jits the *same* sub-computations individually for
the host reference event queue (``repro.el.events.reference``); in
fixed-cost mode the two paths are bit-identical (tested).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import OL4ELConfig
from repro.core.bandit import jax_bandit_update
from repro.el.events.knobs import ASYNC_KNOB_NAMES  # noqa: F401 (re-export)
from repro.el.events.knobs import resolve_async_batch_k
from repro.el.events.scheduler import (schedule_block, split_event_keys,
                                       split_init_keys, staleness_alpha,
                                       staleness_merge, wave_safe_gap)
from repro.el.events.state import (bandit_fleet_init, bandit_place,
                                   bandit_slice)
from repro.el.ingraph import (ELCell, _edge_stack_constraints,
                              _pad_edge_data, _shard_edge_data, _tree_l2,
                              check_ingraph_support, default_metric_fn,
                              make_local_block)

Params = Any


def _build_parts(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                 lr: float, batch: int, metric_fn: Optional[Callable],
                 metric_name: str, mesh=None, drift: bool = False):
    """The data-plane pieces both async paths share: the masked local
    block (identical minibatch streams to the sync program's) and the
    jittable eval metric.  With ``mesh=`` the per-edge datasets live
    sharded over the mesh's edge axes (the host reference kernels never
    pass one).  ``drift=`` builds the scenario path's drift-aware block
    (see ``make_local_block``)."""
    xs, ys, n_per_edge = _pad_edge_data(edge_data)
    if mesh is not None:
        xs, ys = _shard_edge_data(mesh, cfg.n_edges, xs, ys)
    local_block = make_local_block(model, xs, ys, n_per_edge, batch, lr,
                                   cfg.max_interval, drift=drift)
    if metric_fn is None:
        metric_fn = default_metric_fn(model, eval_set, metric_name)
    if cfg.utility == "eval_gain" and metric_fn is None:
        raise ValueError(
            "utility='eval_gain' needs a jittable metric; pass metric_fn= "
            "or use utility='param_delta'")

    # ONE closure computes (metric, utility) for both async paths: XLA
    # may fuse the metric's final multiply into the gain subtraction as
    # an FMA (skipping the intermediate rounding), so the compiled
    # program and the reference kernels must present it the identical
    # expression to round identically.
    def eval_step(params, prev_params, prev_metric):
        if metric_fn is not None:
            metric = metric_fn(params)
        else:
            metric = jnp.float32(jnp.nan)
        if cfg.utility == "eval_gain":
            utility = metric - prev_metric
        else:                              # param_delta (§III.A)
            utility = 1.0 / (1.0 + _tree_l2(prev_params, params))
        return metric, utility

    return local_block, metric_fn, eval_step


def make_async_cell(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                    lr: float, batch: int,
                    n_samples: Optional[np.ndarray] = None,
                    metric_fn: Optional[Callable] = None,
                    metric_name: str = "accuracy",
                    max_events: int = 256, mesh=None,
                    telemetry=None,
                    batch_k: Optional[int] = None) -> ELCell:
    """The budgeted async event loop as an :class:`repro.el.ingraph.ELCell`
    — the unfused form of ``make_async_program`` (which recomposes
    exactly these closures into one ``lax.while_loop`` over events); see
    that function for the semantics, knob contract and mesh placement.

    ``telemetry=`` is the static in-graph observability gate (see
    ``make_sync_cell``): off builds exactly today's carry; on adds a
    ``carry["telem"]`` ring subtree recording, per event, the edge, arm,
    realized charge, the edge's residual budget, the staleness-weighted
    merge ``alpha`` (and the raw staleness), event inter-arrival time
    and the event edge's per-arm bandit statistics.

    ``batch_k=`` is the static K-event wave width (see the module
    docstring); ``None`` resolves it from the config and mesh
    (``resolve_async_batch_k``).  ``batch_k=1`` builds exactly the
    single-event argmin-pop body; ``> 1`` builds the order-equivalent
    wave body.
    """
    from repro.obs.rings import (as_spec, async_ring_init,
                                 async_ring_record,
                                 async_ring_record_wave,
                                 finalize_telemetry)
    spec = as_spec(telemetry)
    del n_samples
    check_ingraph_support(cfg, caller="make_async_program")
    # fleet-dynamics scenario: None keeps every closure below EXACTLY
    # today's traced code; a ScenarioSpec swaps in the churn-aware
    # single-event body (dropout probes, uncharged dead edges).
    scn = cfg.scenario
    period = scn.period if scn is not None else 0

    n_edges, k = cfg.n_edges, cfg.max_interval
    if batch_k is None:
        batch_k = resolve_async_batch_k(cfg, mesh)
    batch_k = max(1, min(int(batch_k), n_edges))
    if scn is not None and batch_k > 1:
        raise ValueError(
            f"async_batch_k={batch_k} with a ScenarioSpec: the scenario "
            "path (per-event activity masks, dropout probes) is defined "
            "on the single-event program only — pin async_batch_k=1 or "
            "leave it 0 (auto resolves to 1 under a scenario)")
    if spec is not None and batch_k > spec.ring_size:
        raise ValueError(
            f"async_batch_k={batch_k} exceeds the telemetry ring size "
            f"{spec.ring_size}: a wave's per-event ring writes would "
            "collide within one scatter — raise telemetry= or lower "
            "the batch width")
    local_block, metric_fn, eval_step = _build_parts(
        model, edge_data, eval_set, cfg, lr=lr, batch=batch,
        metric_fn=metric_fn, metric_name=metric_name, mesh=mesh,
        drift=scn is not None)
    constrain_edge_stack, gather_edge_stack = _edge_stack_constraints(
        mesh, n_edges)

    def init(init_params: Params, rng: jax.Array,
             knobs: Dict[str, jax.Array]) -> Dict[str, Any]:
        ucb_c, budget = knobs["ucb_c"], knobs["budget"]
        costs_ek = knobs["costs_ek"]                            # [E, K]

        fleet = bandit_fleet_init(n_edges, k)
        # initial scheduling: every edge selects its first block, in edge
        # order (host loop's pre-event decide/realized_cost round)
        rng, k_sel0, k_cost0 = split_init_keys(rng)

        def init_edge(e):
            return schedule_block(
                bandit_slice(fleet, e), budget, costs_ek[e], ucb_c,
                knobs["min_edge_cost"][e], knobs["cost_noise"],
                knobs["comp"][e], knobs["comm"][e],
                jnp.float32(0.0), jax.random.fold_in(k_sel0, e),
                jax.random.fold_in(k_cost0, e))

        _, interval0, cost0, finish0 = jax.vmap(init_edge)(
            jnp.arange(n_edges))

        edge_params = constrain_edge_stack(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_edges,) + x.shape),
            init_params))
        if metric_fn is not None:
            prev_metric = metric_fn(init_params)
        else:
            prev_metric = jnp.float32(jnp.nan)
        hist = {
            "metric": jnp.full((max_events,), jnp.nan, jnp.float32),
            "utility": jnp.zeros((max_events,), jnp.float32),
            "interval": jnp.zeros((max_events,), jnp.int32),
            "edge": jnp.full((max_events,), -1, jnp.int32),
            "cost": jnp.zeros((max_events,), jnp.float32),
            "consumed": jnp.zeros((max_events,), jnp.float32),
            "wall": jnp.zeros((max_events,), jnp.float32),
        }
        if scn is not None:
            hist["active_edges"] = jnp.zeros((max_events,), jnp.int32)
        carry = {"gparams": init_params, "edge_params": edge_params,
                 "fleet": fleet,
                 "consumed": jnp.zeros((n_edges,), jnp.float32),
                 "finish": finish0, "infl_i": interval0, "infl_c": cost0,
                 "fetch_ver": jnp.zeros((n_edges,), jnp.int32),
                 "version": jnp.int32(0), "t": jnp.int32(0), "rng": rng,
                 "prev_metric": prev_metric, "wall": jnp.float32(0.0),
                 "hist": hist}
        if spec is not None:
            carry["telem"] = async_ring_init(spec, k,
                                             scenario=scn is not None)
        return carry

    def cond(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        # the static horizon sizes the history arrays (bucketed to a
        # power of two by the callers); the traced event_cap knob is the
        # run's exact cap, so nearby caps share one executable
        cap = jnp.minimum(jnp.int32(max_events),
                          knobs["event_cap"].astype(jnp.int32))
        return ((carry["t"] < cap)
                & jnp.any(jnp.isfinite(carry["finish"])))

    def body_one(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        ucb_c, budget = knobs["ucb_c"], knobs["budget"]
        costs_ek = knobs["costs_ek"]                            # [E, K]
        alpha0 = knobs["async_alpha"]
        gparams, edge_params = carry["gparams"], carry["edge_params"]
        fleet, consumed = carry["fleet"], carry["consumed"]
        finish = carry["finish"]
        infl_i, infl_c = carry["infl_i"], carry["infl_c"]
        fetch_ver, version = carry["fetch_ver"], carry["version"]
        t, prev_metric = carry["t"], carry["prev_metric"]
        hist = carry["hist"]

        rng, k_sel, k_data, k_cost = split_event_keys(carry["rng"])
        # the event horizon: the earliest-finishing in-flight block
        e = jnp.argmin(finish)
        wall = finish[e]
        interval, cost = infl_i[e], infl_c[e]
        # edge e finishes `interval` local iterations and uploads;
        # its slice of the sharded stack is gathered replicated so
        # the block/merge arithmetic runs identically on every
        # device (the event path is control plane)
        p_e = gather_edge_stack(jax.tree.map(lambda a: a[e],
                                             edge_params))
        p_new = local_block(p_e, e, interval,
                            jax.random.fold_in(k_data, e))
        # the SAME realized-cost draw set the finish time and is
        # charged at completion (charged == scheduled)
        consumed = consumed.at[e].add(cost)
        alpha = staleness_alpha(alpha0, version, fetch_ver[e], n_edges)
        if spec is not None:
            # the raw staleness (staleness_alpha's exact f32
            # expression), recorded in the telemetry ring below
            stale = ((version - fetch_ver[e]).astype(jnp.float32)
                     / jnp.float32(max(n_edges, 1)))
        new_global = staleness_merge(gparams, p_new, alpha)
        version = version + 1
        metric, utility = eval_step(new_global, gparams, prev_metric)
        bstate_e = jax_bandit_update(bandit_slice(fleet, e),
                                     interval - 1, utility, cost)
        fleet = bandit_place(fleet, e, bstate_e)
        # edge fetches the fresh global model, schedules next block
        # (the scatter re-pins the stack's sharding so the
        # while-loop carry layout is stable across iterations)
        edge_params = constrain_edge_stack(jax.tree.map(
            lambda a, g: a.at[e].set(g), edge_params, new_global))
        fetch_ver = fetch_ver.at[e].set(version)
        resid = budget - consumed[e]
        _, nxt_i, nxt_c, fin = schedule_block(
            bstate_e, resid, costs_ek[e], ucb_c,
            knobs["min_edge_cost"][e], knobs["cost_noise"],
            knobs["comp"][e], knobs["comm"][e], wall,
            jax.random.fold_in(k_sel, e),
            jax.random.fold_in(k_cost, e))
        finish = finish.at[e].set(fin)
        infl_i = infl_i.at[e].set(nxt_i)
        infl_c = infl_c.at[e].set(nxt_c)
        hist = {
            "metric": hist["metric"].at[t].set(metric),
            "utility": hist["utility"].at[t].set(utility),
            "interval": hist["interval"].at[t].set(interval),
            "edge": hist["edge"].at[t].set(e.astype(jnp.int32)),
            "cost": hist["cost"].at[t].set(cost),
            "consumed": hist["consumed"].at[t].set(jnp.sum(consumed)),
            "wall": hist["wall"].at[t].set(wall),
        }
        new_carry = {"gparams": new_global, "edge_params": edge_params,
                     "fleet": fleet, "consumed": consumed,
                     "finish": finish, "infl_i": infl_i,
                     "infl_c": infl_c, "fetch_ver": fetch_ver,
                     "version": version, "t": t + 1, "rng": rng,
                     "prev_metric": metric, "wall": wall, "hist": hist}
        if spec is not None:
            with jax.named_scope("obs.telemetry"):
                new_carry["telem"] = async_ring_record(
                    carry["telem"], spec, t=t, edge=e,
                    arm=interval - 1, cost=cost, budget_resid=resid,
                    alpha=alpha, staleness=stale,
                    interarrival=wall - carry["wall"],
                    bstate_e=bstate_e)
        return new_carry

    def body_wave(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        ucb_c, budget = knobs["ucb_c"], knobs["budget"]
        costs_ek = knobs["costs_ek"]                            # [E, K]
        alpha0 = knobs["async_alpha"]
        edge_params = carry["edge_params"]
        finish = carry["finish"]
        infl_i, infl_c = carry["infl_i"], carry["infl_c"]
        t0, hist = carry["t"], carry["hist"]

        # -- wave selection: the K earliest completions, sorted (ties
        # lower-edge-first, matching successive argmin pops).  A lane is
        # accepted while it finishes strictly before ANY block an
        # earlier lane's reschedule could produce (wave_safe_gap's f32
        # lower bound); every guard is monotone in the lane index, so
        # `valid` is a prefix mask and lane j's event index is t0 + j.
        neg_f, e_sorted = lax.top_k(-finish, batch_k)
        f_sorted = -neg_f
        gap = wave_safe_gap(knobs["min_edge_cost"], knobs["cost_noise"])
        cap = jnp.minimum(jnp.int32(max_events),
                          knobs["event_cap"].astype(jnp.int32))
        lane = jnp.arange(batch_k, dtype=jnp.int32)
        valid = (lane == 0) | (jnp.isfinite(f_sorted)
                               & (f_sorted < f_sorted[0] + gap)
                               & (t0 + lane < cap))
        n_batch = jnp.sum(valid.astype(jnp.int32))

        # -- the per-event RNG chain advances exactly n_batch splits:
        # lane j's keys are the (t0+j)-th split of the run's one chain,
        # identical to batch_k=1 processing the same events
        r = carry["rng"]
        rng_steps, k_sels, k_datas, k_costs = [r], [], [], []
        for _ in range(batch_k):
            r, ks, kd, kc = split_event_keys(r)
            rng_steps.append(r)
            k_sels.append(ks)
            k_datas.append(kd)
            k_costs.append(kc)
        rng = jnp.stack(rng_steps)[n_batch]

        # -- data plane: the wave's lanes, one local block after another.
        # Lanes are distinct edges and each trains from the params its
        # edge fetched BEFORE this wave, so the lanes are data-
        # independent; only the K event slices of the sharded stack are
        # gathered replicated (slice-local), never the full [E, ...]
        # edge stack.  Not vmapped: on a TPU the batched block sums its
        # minibatch in another order than the single-event block (the
        # bias gradient differs in the last bit), and a wave must equal
        # K single events bit for bit.
        interval_l = infl_i[e_sorted]                           # [Kw]
        cost_l = infl_c[e_sorted]
        # K scalar gathers, stacked — NOT one vector-index gather: the
        # SPMD partitioner lowers `a[e_sorted]` on the sharded edge
        # stack through a one-hot contraction (all-reduce), while the
        # scalar form keeps the single-event path's slice-local
        # all-gather lowering (the dispatch contract pins all-reduce==0)
        p_stack = gather_edge_stack(jax.tree.map(
            lambda a: jnp.stack([a[e_sorted[j]]
                                 for j in range(batch_k)]),
            edge_params))
        data_keys = jnp.stack([
            jax.random.fold_in(k_datas[j], e_sorted[j])
            for j in range(batch_k)])
        p_new_stack = lax.map(lambda lane: local_block(*lane),
                              (p_stack, e_sorted, interval_l, data_keys))

        # -- control plane: the merge chain is inherently sequential
        # (lane j+1 merges into lane j's global), so replay it per lane
        # under a validity mask — the exact op sequence of batch_k=1.
        def lane_step(j, state):
            (gparams, fleet, consumed, fetch_ver, version,
             prev_metric) = state
            e = e_sorted[j]
            wall_j = f_sorted[j]
            interval, cost = interval_l[j], cost_l[j]
            p_new = jax.tree.map(lambda a: a[j], p_new_stack)
            consumed = consumed.at[e].add(cost)
            alpha = staleness_alpha(alpha0, version, fetch_ver[e],
                                    n_edges)
            stale = ((version - fetch_ver[e]).astype(jnp.float32)
                     / jnp.float32(max(n_edges, 1)))
            new_global = staleness_merge(gparams, p_new, alpha)
            version = version + 1
            metric, utility = eval_step(new_global, gparams, prev_metric)
            bstate_e = jax_bandit_update(bandit_slice(fleet, e),
                                         interval - 1, utility, cost)
            fleet = bandit_place(fleet, e, bstate_e)
            fetch_ver = fetch_ver.at[e].set(version)
            resid = budget - consumed[e]
            _, nxt_i, nxt_c, fin = schedule_block(
                bstate_e, resid, costs_ek[e], ucb_c,
                knobs["min_edge_cost"][e], knobs["cost_noise"],
                knobs["comp"][e], knobs["comm"][e], wall_j,
                jax.random.fold_in(k_sels[j], e),
                jax.random.fold_in(k_costs[j], e))
            outs = {"metric": metric, "utility": utility,
                    "interval": interval, "cost": cost,
                    "consumed_sum": jnp.sum(consumed),
                    "resid": resid, "alpha": alpha, "stale": stale,
                    "bcounts": bstate_e["counts"],
                    "butil": bstate_e["utility_sum"],
                    "nxt_i": nxt_i, "nxt_c": nxt_c, "fin": fin,
                    "new_global": new_global}
            return ((new_global, fleet, consumed, fetch_ver, version,
                     metric), outs)

        def lane_skip(state):
            outs = {"metric": jnp.float32(0), "utility": jnp.float32(0),
                    "interval": jnp.int32(0), "cost": jnp.float32(0),
                    "consumed_sum": jnp.float32(0),
                    "resid": jnp.float32(0), "alpha": jnp.float32(0),
                    "stale": jnp.float32(0),
                    "bcounts": jnp.zeros((k,), jnp.int32),
                    "butil": jnp.zeros((k,), jnp.float32),
                    "nxt_i": jnp.int32(0), "nxt_c": jnp.float32(0),
                    "fin": jnp.float32(0), "new_global": state[0]}
            return state, outs

        state = (carry["gparams"], carry["fleet"], carry["consumed"],
                 carry["fetch_ver"], carry["version"],
                 carry["prev_metric"])
        lanes = []
        for j in range(batch_k):
            if j == 0:          # lane 0 is the argmin event: always valid
                state, outs = lane_step(0, state)
            else:
                state, outs = lax.cond(
                    j < n_batch,
                    lambda s, j=j: lane_step(j, s),
                    lane_skip, state)
            lanes.append(outs)
        (gparams, fleet, consumed, fetch_ver, version,
         prev_metric) = state

        stk = {name: jnp.stack([o[name] for o in lanes])
               for name in lanes[0] if name != "new_global"}
        g_stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[o["new_global"] for o in lanes])

        # -- coalesced state scatters: invalid lanes route to index
        # n_edges / the horizon and drop
        e_scatter = jnp.where(valid, e_sorted, jnp.int32(n_edges))
        edge_params = constrain_edge_stack(jax.tree.map(
            lambda a, g: a.at[e_scatter].set(g, mode="drop"),
            edge_params, g_stack))
        finish = finish.at[e_scatter].set(stk["fin"], mode="drop")
        infl_i = infl_i.at[e_scatter].set(stk["nxt_i"], mode="drop")
        infl_c = infl_c.at[e_scatter].set(stk["nxt_c"], mode="drop")
        idx = jnp.where(valid, t0 + lane, jnp.int32(max_events))
        hist = {
            "metric": hist["metric"].at[idx].set(stk["metric"],
                                                 mode="drop"),
            "utility": hist["utility"].at[idx].set(stk["utility"],
                                                   mode="drop"),
            "interval": hist["interval"].at[idx].set(stk["interval"],
                                                     mode="drop"),
            "edge": hist["edge"].at[idx].set(e_sorted.astype(jnp.int32),
                                             mode="drop"),
            "cost": hist["cost"].at[idx].set(stk["cost"], mode="drop"),
            "consumed": hist["consumed"].at[idx].set(stk["consumed_sum"],
                                                     mode="drop"),
            "wall": hist["wall"].at[idx].set(f_sorted, mode="drop"),
        }
        wall_out = f_sorted[n_batch - 1]
        new_carry = {"gparams": gparams, "edge_params": edge_params,
                     "fleet": fleet, "consumed": consumed,
                     "finish": finish, "infl_i": infl_i,
                     "infl_c": infl_c, "fetch_ver": fetch_ver,
                     "version": version, "t": t0 + n_batch, "rng": rng,
                     "prev_metric": prev_metric, "wall": wall_out,
                     "hist": hist}
        if spec is not None:
            with jax.named_scope("obs.telemetry"):
                prev_walls = jnp.concatenate(
                    [carry["wall"][None], f_sorted[:-1]])
                new_carry["telem"] = async_ring_record_wave(
                    carry["telem"], spec, t0=t0, valid=valid,
                    edge=e_sorted, arm=interval_l - 1, cost=cost_l,
                    budget_resid=stk["resid"], alpha=stk["alpha"],
                    staleness=stk["stale"],
                    interarrival=f_sorted - prev_walls,
                    arm_counts=stk["bcounts"],
                    arm_utility=stk["butil"])
        return new_carry

    def body_one_scn(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        # the scenario variant of body_one: the popped edge's activity
        # bit decides between a real completion and a dropout PROBE —
        # a probe discards the block (no merge, no charge, no bandit
        # pull, no version bump) and retries the same in-flight block
        # after a reconnect delay, so churned edges burn wall clock but
        # never budget, and the merge chain skips them entirely.
        ucb_c, budget = knobs["ucb_c"], knobs["budget"]
        costs_ek = knobs["costs_ek"]                            # [E, K]
        alpha0 = knobs["async_alpha"]
        scn_active, scn_mult = knobs["scn_active"], knobs["scn_mult"]
        gparams, edge_params = carry["gparams"], carry["edge_params"]
        fleet, consumed = carry["fleet"], carry["consumed"]
        finish = carry["finish"]
        infl_i, infl_c = carry["infl_i"], carry["infl_c"]
        fetch_ver, version = carry["fetch_ver"], carry["version"]
        t, prev_metric = carry["t"], carry["prev_metric"]
        hist = carry["hist"]

        rng, k_sel, k_data, k_cost = split_event_keys(carry["rng"])
        e = jnp.argmin(finish)
        wall = finish[e]
        slot_i = jnp.mod(t, period)
        act_row = scn_active[slot_i] > 0                        # [E]
        is_act = act_row[e]
        interval, cost = infl_i[e], infl_c[e]
        p_e = gather_edge_stack(jax.tree.map(lambda a: a[e],
                                             edge_params))
        # a dropped edge runs zero masked work (interval 0) and the
        # drift shift rotates the sampling window
        shift = knobs["scn_drift"] * t.astype(jnp.float32)
        p_new = local_block(p_e, e, jnp.where(is_act, interval, 0),
                            jax.random.fold_in(k_data, e), shift)
        # charge-at-completion, live edges only: probes are free
        consumed = consumed.at[e].add(jnp.where(is_act, cost, 0.0))
        alpha = staleness_alpha(alpha0, version, fetch_ver[e], n_edges)
        if spec is not None:
            stale = ((version - fetch_ver[e]).astype(jnp.float32)
                     / jnp.float32(max(n_edges, 1)))
        merged = staleness_merge(gparams, p_new, alpha)
        new_global = jax.tree.map(
            lambda m, g: jnp.where(is_act, m, g), merged, gparams)
        version = version + jnp.where(is_act, 1, 0)
        metric, utility = eval_step(new_global, gparams, prev_metric)
        # arm -1 makes the bandit update a no-op (its valid guard), so
        # a probe pulls nothing
        bstate_e = jax_bandit_update(
            bandit_slice(fleet, e),
            jnp.where(is_act, interval - 1, -1), utility, cost)
        fleet = bandit_place(fleet, e, bstate_e)
        # only a live edge refetches the global model
        edge_params = constrain_edge_stack(jax.tree.map(
            lambda a, g: a.at[e].set(jnp.where(is_act, g, a[e])),
            edge_params, new_global))
        fetch_ver = fetch_ver.at[e].set(
            jnp.where(is_act, version, fetch_ver[e]))
        resid = budget - consumed[e]
        # straggler spikes scale the NEXT block's cost surface at
        # scheduling time (cost = m * (i*comp + comm) by linearity)
        m = scn_mult[slot_i, e]
        _, nxt_i, nxt_c, fin = schedule_block(
            bstate_e, resid, costs_ek[e] * m, ucb_c,
            knobs["min_edge_cost"][e] * m, knobs["cost_noise"],
            knobs["comp"][e] * m, knobs["comm"][e] * m, wall,
            jax.random.fold_in(k_sel, e),
            jax.random.fold_in(k_cost, e))
        # a probe keeps its in-flight block and retries after a
        # reconnect delay of the edge's minimum block cost
        fin = jnp.where(is_act, fin,
                        wall + knobs["min_edge_cost"][e])
        nxt_i = jnp.where(is_act, nxt_i, interval)
        nxt_c = jnp.where(is_act, nxt_c, cost)
        finish = finish.at[e].set(fin)
        infl_i = infl_i.at[e].set(nxt_i)
        infl_c = infl_c.at[e].set(nxt_c)
        n_act_fleet = jnp.sum(act_row.astype(jnp.int32))
        hist = {
            "metric": hist["metric"].at[t].set(metric),
            "utility": hist["utility"].at[t].set(
                jnp.where(is_act, utility, 0.0)),
            "interval": hist["interval"].at[t].set(
                jnp.where(is_act, interval, 0)),
            "edge": hist["edge"].at[t].set(e.astype(jnp.int32)),
            "cost": hist["cost"].at[t].set(
                jnp.where(is_act, cost, 0.0)),
            "consumed": hist["consumed"].at[t].set(jnp.sum(consumed)),
            "wall": hist["wall"].at[t].set(wall),
            "active_edges": hist["active_edges"].at[t].set(n_act_fleet),
        }
        new_carry = {"gparams": new_global, "edge_params": edge_params,
                     "fleet": fleet, "consumed": consumed,
                     "finish": finish, "infl_i": infl_i,
                     "infl_c": infl_c, "fetch_ver": fetch_ver,
                     "version": version, "t": t + 1, "rng": rng,
                     "prev_metric": metric, "wall": wall, "hist": hist}
        if spec is not None:
            with jax.named_scope("obs.telemetry"):
                new_carry["telem"] = async_ring_record(
                    carry["telem"], spec, t=t, edge=e,
                    arm=interval - 1,
                    cost=jnp.where(is_act, cost, 0.0),
                    budget_resid=resid, alpha=alpha, staleness=stale,
                    interarrival=wall - carry["wall"],
                    bstate_e=bstate_e,
                    scn=(n_act_fleet,
                         1 - is_act.astype(jnp.int32),
                         jnp.int32(0)))
        return new_carry

    if scn is not None:
        body = body_one_scn
    else:
        body = body_one if batch_k == 1 else body_wave

    def finalize(carry: Dict[str, Any], knobs: Dict[str, jax.Array]):
        out = dict(carry["hist"])
        out["n_rounds"] = carry["t"]
        out["budgets_left"] = knobs["budget"] - carry["consumed"]
        out["arm_pulls"] = carry["fleet"]["counts"]             # [E, K]
        out["wall_time"] = carry["wall"]
        # blocks still in flight at exit: 0 means the budgets silenced
        # every edge (terminated_reason="budget_exhausted"), >0 means
        # the event horizon cut the run short ("max_events")
        out["n_active"] = jnp.sum(
            jnp.isfinite(carry["finish"]).astype(jnp.int32))
        if spec is not None:
            out["telemetry"] = finalize_telemetry(carry["telem"],
                                                  carry["t"], spec)
        return carry["gparams"], out

    return ELCell(init=init, cond=cond, body=body, finalize=finalize,
                  horizon=max_events)


def make_async_program(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                       lr: float, batch: int,
                       n_samples: Optional[np.ndarray] = None,
                       metric_fn: Optional[Callable] = None,
                       metric_name: str = "accuracy",
                       max_events: int = 256, mesh=None,
                       telemetry=None,
                       batch_k: Optional[int] = None):
    """Build ``program(init_params, rng, knobs) -> (params, out)`` — the
    whole budgeted async run as one ``lax.while_loop`` over events, with
    the control-plane knobs (``ASYNC_KNOB_NAMES`` / ``async_knobs``) as
    traced inputs.

    ``batch_k=`` is the static K-event wave width (module docstring);
    ``None`` auto-resolves from the config and mesh
    (``resolve_async_batch_k``), ``1`` is the single-event argmin-pop
    program, ``> 1`` dispatches K-event waves whose processed order,
    merge values, charged costs and arm pulls are identical (tested).

    ``n_samples`` is accepted for signature parity with the sync program
    and ignored: the async global update is the staleness mix, not a
    weighted average.

    With ``mesh=`` the big per-edge state — the datasets and the
    ``[n_edges, ...]`` fetched-params stack each edge trains from —
    shards over the mesh's (``pod``, ``data``) axes and its tensor dims
    over ``model`` (``el_stacked_param_specs`` layout), so a large fleet's
    model copies spread across devices instead of replicating E-fold.
    The event edge's slice is gathered replicated before its local
    block, merge and bandit update (the replicated control plane:
    finish times, budgets, bandit fleet), which keeps every computed
    value — and hence the whole run — bit-identical to the unsharded
    program (tested on a debug mesh).

    ``out`` is a dict of device arrays: per-event ``metric``,
    ``utility``, ``interval``, ``edge``, ``cost`` (the charge),
    ``consumed`` (cumulative total across edges) and ``wall`` (the event
    time), plus scalars ``n_rounds`` (events completed), ``wall_time``,
    the final per-edge ``budgets_left`` and the per-edge bandit
    ``arm_pulls`` ``[E, K]``.
    """
    cell = make_async_cell(
        model, edge_data, eval_set, cfg, lr=lr, batch=batch,
        n_samples=n_samples, metric_fn=metric_fn, metric_name=metric_name,
        max_events=max_events, mesh=mesh, telemetry=telemetry,
        batch_k=batch_k)

    def program(init_params: Params, rng: jax.Array,
                knobs: Dict[str, jax.Array]):
        carry = lax.while_loop(lambda c: cell.cond(c, knobs),
                               lambda c: cell.body(c, knobs),
                               cell.init(init_params, rng, knobs))
        return cell.finalize(carry, knobs)

    return program


def make_async_kernels(model, edge_data, eval_set, cfg: OL4ELConfig, *,
                       lr: float, batch: int,
                       metric_fn: Optional[Callable] = None,
                       metric_name: str = "accuracy") -> Dict[str, Any]:
    """The per-event sub-computations of ``make_async_program``, jitted
    individually for the host reference event queue — same closures,
    same ops, same key contracts, so the reference reproduces the
    compiled program's arithmetic exactly."""
    check_ingraph_support(cfg, caller="make_async_kernels")
    local_block, metric_fn, eval_step = _build_parts(
        model, edge_data, eval_set, cfg, lr=lr, batch=batch,
        metric_fn=metric_fn, metric_name=metric_name)
    n_edges = cfg.n_edges

    def merge(gparams, p_new, alpha0, version, fetch_ver):
        alpha = staleness_alpha(alpha0, version, fetch_ver, n_edges)
        return staleness_merge(gparams, p_new, alpha)

    return {
        "local_train": jax.jit(local_block),
        "schedule": jax.jit(schedule_block),
        "merge": jax.jit(merge),
        "metric": None if metric_fn is None else jax.jit(metric_fn),
        "eval_step": jax.jit(eval_step),
        "bandit_update": jax.jit(jax_bandit_update),
    }
