"""The event-horizon scheduling math — pure jnp, shared verbatim by the
compiled program (``repro.el.events.program``) and the host reference
event queue (``repro.el.events.reference``).

Sharing these functions is what makes the two paths bit-comparable: the
reference loop calls them as tiny jitted kernels in the exact order the
``lax.while_loop`` body inlines them, with identical key derivations, so
in fixed-cost mode every selection, realized cost, merge coefficient and
budget charge agrees bit-for-bit.

Everything here is control plane: in a mesh-sharded run
(``make_async_program(mesh=...)``) these functions execute replicated on
every device — selections, realized costs and merge coefficients are
scalars derived from replicated bandit/budget state, so the shared
``jax.random`` chain advances identically on every shard and the sharded
program stays bit-identical to the unsharded one (only the per-edge
datasets and the fetched-params stack shard).

Key schedule (one ``jax.random`` chain per run, seeded like the sync
program with ``jax.random.key(cfg.seed + 17)``):

  * init:       ``rng -> (rng, k_sel, k_cost)``; per-edge keys are
                ``fold_in(k_sel, e)`` / ``fold_in(k_cost, e)``.
  * per event:  ``rng -> (rng, k_sel, k_data, k_cost)``; the event
                edge's keys are ``fold_in(k_*, e)`` (``k_data`` feeds
                the shared minibatch sampler ``make_local_block``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.bandit import jax_select_arm


def split_init_keys(rng: jax.Array) -> Tuple[jax.Array, ...]:
    """Keys for the initial round of per-edge scheduling."""
    rng, k_sel, k_cost = jax.random.split(rng, 3)
    return rng, k_sel, k_cost


def split_event_keys(rng: jax.Array) -> Tuple[jax.Array, ...]:
    """Keys for one event: selection, minibatch data, cost noise."""
    rng, k_sel, k_data, k_cost = jax.random.split(rng, 4)
    return rng, k_sel, k_data, k_cost


def schedule_block(bstate_e, resid, costs_e, ucb_c, min_cost_e, cost_noise,
                   comp_e, comm_e, wall, k_sel_e, k_cost_e):
    """Select edge ``e``'s next interval and realize its block cost.

    Mirrors the host loop's ``coord.decide(e)`` →
    ``coord.realized_cost(e, i)`` → schedule-if-affordable sequence:
    the arm is the in-graph ol4el draw (``jax_select_arm``), the cost is
    ``interval·comp_e + comm_e`` times the variable-cost multiplier
    ``max(0.1, 1 + noise·N(0,1))`` (a 0.0 noise knob multiplies by
    exactly 1.0), and the block is scheduled only when an arm was
    affordable and the residual still covers the cheapest block
    (``not coord.exhausted(e)``).

    Returns ``(active, interval, cost, finish)`` with ``finish`` =
    ``wall + cost`` for scheduled blocks and ``+inf`` for stopped edges.
    """
    arm = jax_select_arm(k_sel_e, bstate_e, resid, costs_e, ucb_c)
    interval = arm + 1
    eps = jax.random.normal(k_cost_e, ())
    mult = jnp.maximum(0.1, 1.0 + cost_noise * eps)
    # the maximum() pins the charged cost to its f32 rounding (costs are
    # strictly positive, so it never changes the value): without it XLA
    # may contract `wall + expr·mult` into an FMA in one compilation
    # context but not another, and the compiled program and the host
    # reference would disagree by an ulp in variable-cost mode
    cost = jnp.maximum((interval.astype(jnp.float32) * comp_e + comm_e)
                       * mult, 0.0)
    active = (arm >= 0) & (resid >= min_cost_e)
    finish = jnp.where(active, wall + cost, jnp.inf)
    return active, interval, cost, finish


def wave_safe_gap(min_edge_cost, cost_noise):
    """Lower bound (f32) on ANY rescheduled block's realized cost — the
    K-event wave-safety margin.

    ``schedule_block`` charges ``cost = fl(fl(fl(i·comp_e) + comm_e) ·
    mult)`` with ``i >= 1`` and ``mult >= 0.1`` (``== 1.0`` exactly when
    the noise knob is zero).  Round-to-nearest is monotone, so ``cost >=
    fl(min(min_edge_cost) · floor)`` — this gap.  A wave may therefore
    batch every lane ``j`` with ``f_(j) < fl(f_(0) + gap)`` (strict:
    rescheduled finishes ``fl(f_i + cost) >= fl(f_(0) + gap)`` land
    at-or-after the bound, and ties against in-wave lanes must fall to
    the next wave where argmin/top-k tie-breaking orders them), and the
    processed order equals the one-event-at-a-time program's exactly.
    """
    floor = jnp.where(cost_noise > 0, jnp.float32(0.1), jnp.float32(1.0))
    return jnp.min(min_edge_cost) * floor


def staleness_alpha(base, version, fetch_version, n_edges: int):
    """The staleness-discounted mixing rate in float32.

    Same math as the host loop: raw version staleness normalized by the
    fleet size (staleness in *epochs*), then the polynomial discount
    ``base / (1 + s)`` — all in f32 so the compiled and reference paths
    round identically.
    """
    s = (version - fetch_version).astype(jnp.float32) \
        / jnp.float32(max(n_edges, 1))
    return base / (1.0 + s)


def _rounded(x):
    """``x`` itself, held at its own f32 rounding: an add fed by this is
    never contracted with the multiply that made ``x`` into one FMA.
    ``max(x, 0) + min(x, 0)`` is exact (one side is zero), and neither
    XLA nor LLVM folds it away — the signed twin of the ``maximum(...,
    0.0)`` pin in :func:`schedule_block`."""
    return jnp.maximum(x, 0.0) + jnp.minimum(x, 0.0)


def staleness_merge(global_params, edge_params, alpha):
    """Masked asynchronous global update ``G <- (1-a)·G + a·θ_e`` (f32
    accumulation, cast back to the leaf dtype) — the jnp twin of
    ``repro.federated.aggregation.staleness_mix`` with a traced alpha.

    Both products are pinned to their f32 rounding: XLA fuses the merge
    with whatever surrounds it, and contracted ``a·b + c·d`` into an FMA
    in the single-run program but not in the fleet's vmapped slot batch,
    so a tenant drifted an ulp from its independent run."""
    def mix(g, e):
        out = (_rounded((1.0 - alpha) * g.astype(jnp.float32))
               + _rounded(alpha * e.astype(jnp.float32)))
        return out.astype(g.dtype)

    return jax.tree.map(mix, global_params, edge_params)
