"""Pure-jnp reference for the fused window-stats reductions.

One logical pass over the task table (running/pending counts, the masked
usage sum behind ``usage_mean``, the (12, 2) per-priority population) plus
one small pass over the node table (active capacity, reserved/used sums,
and both utilisation-spread variances). ``core.stats.window_stats`` composes
the final per-window stats dict from these raw reductions; the Pallas kernel
(kernel.py) produces the same tuple with every task-side accumulator
resident in VMEM across one grid sweep.

The expressions here mirror ``core.stats.window_stats_ref`` (the pre-fusion
stats body) term for term, so on exact-arithmetic (grid-aligned) data the
composed stats rows are bitwise identical to the unfused path — the bar the
equivalence suite holds all three paths (unfused / fused ref / kernel) to.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.state import TASK_PENDING, TASK_RUNNING

N_PRIO = 12           # GCD priority classes 0-11


class WindowReductions(NamedTuple):
    """Raw reductions a stats row is assembled from (per lane)."""
    n_running: jax.Array    # ()        i32
    n_pending: jax.Array    # ()        i32
    n_nodes: jax.Array      # ()        i32 active nodes
    by_prio: jax.Array      # (12, 2)   i32 [running, pending] populations
    usage_sum: jax.Array    # (U,)      f32 usage summed over running tasks
    cap: jax.Array          # (R,)      f32 active capacity
    reserved: jax.Array     # (R,)      f32 node_reserved.sum(0)
    used: jax.Array         # (R,)      f32 node_used.sum(0)
    util_var: jax.Array     # ()        f32 spread of per-node cpu utilisation
    res_var: jax.Array      # ()        f32 spread of per-node reserved frac


def task_reductions_ref(task_state: jax.Array, task_usage: jax.Array,
                        task_prio: jax.Array):
    """Task-table side: (counts (3,) i32 w/ n_nodes slot zeroed,
    by_prio (12, 2) i32, usage_sum (U,) f32).

    The priority histogram is built from a one-hot compare + sum instead of
    the scatter the unfused path used: integer sums are exact, so the two
    formulations agree bitwise, and the compare/reduce vectorises where the
    scatter serialises.  Both state classes ride the same one-hot, so the
    task table is walked once.
    """
    running = task_state == TASK_RUNNING
    pending = task_state == TASK_PENDING
    prio = jnp.clip(task_prio, 0, N_PRIO - 1)
    rp = jnp.stack([running, pending], axis=1).astype(jnp.float32)  # (T, 2)
    onehot = (prio[:, None] == jnp.arange(N_PRIO, dtype=prio.dtype)
              ).astype(jnp.float32)                                 # (T, 12)
    # counts < 2^24, so the f32 matmul is exact and the i32 cast bitwise
    # (0/1 operands are exact in bf16: this holds at TPU default precision)
    by_prio = jax.lax.dot_general(
        onehot, rp, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)       # (12, 2)
    n_running = by_prio[:, 0].sum()          # == running.sum() exactly
    n_pending = by_prio[:, 1].sum()
    # masked sum, the unfused path's expression: an exact select + reduce
    # (an f32 matmul would run as one bf16 pass on a TPU)
    usage_sum = jnp.where(running[:, None], task_usage, 0.0).sum(0)
    return n_running, n_pending, by_prio, usage_sum


def node_reductions_ref(node_active: jax.Array, node_total: jax.Array,
                        node_reserved: jax.Array, node_used: jax.Array):
    """Node-table side: capacity / tally sums + both balance variances.

    Term-for-term the expressions of the unfused stats body (the MASB
    load-balance metric), so the composed row matches it bitwise.
    """
    active = node_active
    cap = jnp.where(active[:, None], node_total, 0.0).sum(0)        # (R,)
    reserved = node_reserved.sum(0)
    used = node_used.sum(0)
    n_nodes = active.sum().astype(jnp.int32)
    n_div = jnp.maximum(active.sum(), 1)

    node_util = jnp.where(active[:, None],
                          node_used / jnp.maximum(node_total, 1e-9),
                          0.0)[:, 0]
    util_mean = node_util.sum() / n_div
    util_var = jnp.where(active, (node_util - util_mean) ** 2, 0.0).sum() \
        / n_div
    node_res = jnp.where(active[:, None],
                         node_reserved / jnp.maximum(node_total, 1e-9),
                         0.0).mean(-1)
    res_mean = node_res.sum() / n_div
    res_var = jnp.where(active, (node_res - res_mean) ** 2, 0.0).sum() / n_div
    return n_nodes, cap, reserved, used, util_var, res_var


def window_reductions_ref(task_state: jax.Array, task_usage: jax.Array,
                          task_prio: jax.Array, node_active: jax.Array,
                          node_total: jax.Array, node_reserved: jax.Array,
                          node_used: jax.Array) -> WindowReductions:
    n_running, n_pending, by_prio, usage_sum = task_reductions_ref(
        task_state, task_usage, task_prio)
    n_nodes, cap, reserved, used, util_var, res_var = node_reductions_ref(
        node_active, node_total, node_reserved, node_used)
    return WindowReductions(n_running=n_running, n_pending=n_pending,
                            n_nodes=n_nodes, by_prio=by_prio,
                            usage_sum=usage_sum, cap=cap, reserved=reserved,
                            used=used, util_var=util_var, res_var=res_var)
