"""Shared scheduler front-end: pending-batch selection + constraint scoring.

Every scheduler in the registry consumes the same two passes before its
proposal runs:

* :func:`pending_batch` — top-P pending task slots by priority (descending),
  the fixed-size working set a window's scheduling pass considers;
* :func:`base_pass` — the (P, N) constraint-match/best-fit score matrix from
  the ``constraint_match`` kernel, plus the derived feasibility mask.

Keeping these out of the per-scheduler code is what lets the scenario fleet
``lax.switch`` over *proposals only*: the expensive shared passes run once
per lane no matter how many schedulers the fleet mixes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import SimConfig
from repro.core import trace
from repro.core.state import SimState, TASK_PENDING
from repro.kernels.constraint_match.ops import constraint_match

NEG = -jnp.inf


def pending_batch(state: SimState, cfg: SimConfig):
    """Top-P pending task slots by priority (descending), equal priorities
    in slot order, then the non-pending slots in slot order.

    Every key is distinct — (priority + 1) * T plus the reversed slot for a
    pending slot, the reversed slot alone otherwise — because ``top_k``
    leaves the order of equal keys to the backend, and the TPU's differs
    from the CPU's: the commit would then place equal-priority tasks in
    another order."""
    P, T = cfg.sched_batch, cfg.max_tasks
    pend = state.task_state == TASK_PENDING
    top = (jnp.iinfo(jnp.int32).max // T) - 2     # keys stay in int32
    rank = jnp.where(pend, jnp.clip(state.task_prio, 0, top) + 1, 0)
    key = rank * T + (T - 1 - jnp.arange(T, dtype=jnp.int32))
    _, idx = jax.lax.top_k(key, P)
    valid = pend[idx]
    return idx, valid


@trace.scoped(trace.BASE)
def base_pass(state: SimState, cfg: SimConfig):
    """Pending batch + constraint-match scores: (idx, valid, base_ok, scores).

    scores is (P, N) f32 with -inf for infeasible (task, node) pairs;
    base_ok is its finiteness mask.
    """
    idx, valid = pending_batch(state, cfg)
    scores = constraint_match(
        state.task_req[idx], state.task_constraints[idx],
        state.node_total, state.node_reserved, state.node_attrs,
        state.node_active, use_kernel=cfg.use_kernels)         # (P, N)
    base_ok = jnp.isfinite(scores)
    return idx, valid, base_ok, scores
