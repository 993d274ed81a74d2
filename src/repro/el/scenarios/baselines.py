"""In-graph selection-policy switch: OL4EL vs task-allocation baselines.

With a scenario, the sync program routes arm selection through one
``lax.switch`` over these branches, selected by the traced ``policy_id``
knob — so "OL4EL vs baselines under churn" is ONE vmapped program with
``policy`` as an ordinary sweep axis, every cell sharing the executable.

Branch 0 is the OL4EL budget-limited UCB bandit, ``ol4el_arm``
(``jax_selection_weights`` → log-weights → ``categorical``), the same
function the scenario-less sync round calls directly.  Branches 1–2 are
the PAPERS.md task-allocation baselines:

- ``task_alloc`` — modeled on "Adaptive task allocation for mobile edge
  learning" (arXiv 1811.03748): allocate the largest locally-feasible
  workload each round (max updates per global sync the budget still
  covers), adapting to the residual instead of learning utilities.
- ``delay_energy`` — modeled on the delay/energy-constrained task
  allocation of arXiv 2012.00143: pace consumption so the budget lasts,
  picking the arm whose cost best matches a geometric pace
  ``sqrt(residual * min_cost)`` between spending-it-all-now and the
  cheapest sustainable rate.

All branches share the signature ``(bstate, resid, costs, ucb_c, key)
-> arm`` (int32); only OL4EL consumes the bandit state and the key, but
a uniform signature is what ``lax.switch`` requires.  Feasibility is
guaranteed by the loop condition (the program only enters the body while
the binding edge can afford the cheapest arm), matching the bandit
branch's assumption.

Host-loop counterparts are registered in ``repro.el.policies`` under the
same names; ``INGRAPH_POLICY_ORDER`` is the switch's branch order and
the single source of truth for which policies the compiled scenario
program implements.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bandit import jax_selection_weights

#: ``lax.switch`` branch order; index == the ``policy_id`` knob value.
INGRAPH_POLICY_ORDER = ("ol4el", "task_alloc", "delay_energy")


def ingraph_policy_id(name: str) -> int:
    """The ``policy_id`` knob value for a registry policy name."""
    if name not in INGRAPH_POLICY_ORDER:
        raise ValueError(
            f"policy {name!r} has no in-graph scenario branch; the "
            f"compiled policy switch implements {INGRAPH_POLICY_ORDER} "
            f"(other registry policies run host-side only)")
    return INGRAPH_POLICY_ORDER.index(name)


def ol4el_arm(bstate, resid, costs, ucb_c, key):
    """The OL4EL budget-limited UCB draw: the compiled sync round's
    selection, with or without a scenario."""
    w = jax_selection_weights(bstate, resid, costs, ucb_c)
    logits = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _task_alloc_arm(bstate, resid, costs, ucb_c, key):
    # largest feasible workload: arm order == interval order, so the
    # max feasible index is the max updates-per-sync the budget covers
    feasible = costs <= resid + 1e-12
    arms = jnp.arange(costs.shape[0], dtype=jnp.int32)
    return jnp.max(jnp.where(feasible, arms, -1)).astype(jnp.int32)


def _delay_energy_arm(bstate, resid, costs, ucb_c, key):
    # budget pacing: target cost = geometric mean of "spend the whole
    # residual now" and "spend the cheapest sustainable amount"
    min_c = jnp.min(costs)
    pace = jnp.sqrt(jnp.maximum(resid, min_c) * min_c)
    feasible = costs <= resid + 1e-12
    score = jnp.where(feasible, jnp.abs(costs - pace), jnp.inf)
    return jnp.argmin(score).astype(jnp.int32)


_BRANCHES = (ol4el_arm, _task_alloc_arm, _delay_energy_arm)


def select_arm_switch(policy_id, bstate, resid, costs, ucb_c, key):
    """Traced arm selection: dispatch on the ``policy_id`` knob."""
    return jax.lax.switch(policy_id, _BRANCHES, bstate, resid, costs,
                          ucb_c, key)
