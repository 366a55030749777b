"""Per-window statistics — the fine-grained reporting that is AGOCS's selling
point over CloudSim (Table II 'Supported and reported resource types').

Each window emits a flat dict of scalars/vectors covering requested *and*
actually-used resources (users waste up to 98% of requests — paper §I), the
secondary parameters (disk I/O time, CPI, MAI, page cache), task/node
population, and scheduler activity.

Two implementations produce the row:

* **fused** (``cfg.fused_window_stats``, the default): every task-table
  reduction (running/pending counts, masked usage sum, per-priority
  population) comes out of ONE pass via ``kernels/window_stats`` — the
  pure-jnp fused reference, or the Pallas kernel under ``cfg.use_kernels``
  (grid-stepped task tiles with all accumulators VMEM-resident, natively
  batched across fleet lanes via ``custom_vmap``);
* **unfused** (``fused_window_stats=False``): :func:`window_stats_ref`, the
  pre-fusion body (~6 independent full passes) — kept as the equivalence
  oracle and the PR-3-era baseline the engine benchmark measures against.

On exact-arithmetic (grid-aligned) data the two are bitwise identical —
integer reductions always are, and the float expressions mirror each other
term for term (tests/test_window_stats.py holds all paths to that bar).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.config import SimConfig
from repro.core import trace
from repro.core.state import SimState, TASK_PENDING, TASK_RUNNING
from repro.kernels.window_stats.ops import window_reductions

# task_usage column layout (GCD task_usage table, condensed)
U_CPU, U_CANON_MEM, U_ASSIGN_MEM, U_PAGE_CACHE = 0, 1, 2, 3
U_DISK_IO, U_DISK_SPACE, U_CPI, U_MAI = 4, 5, 6, 7

USAGE_NAMES = ("cpu_rate", "canonical_mem", "assigned_mem", "page_cache",
               "disk_io_time", "disk_space", "cpi", "mai")

# usage columns aligned with the (cpu, memory, disk) resource axes — the
# slice of task_usage that flows into node_used (full recomputes and the
# engine's incremental deltas must agree on this, so it lives here once)
ACCOUNTED_USAGE_COLS = (U_CPU, U_CANON_MEM, U_DISK_SPACE)


@trace.scoped(trace.STATS)
def window_stats(state: SimState, cfg: SimConfig) -> Dict[str, jax.Array]:
    """One stats row from the current state (fused path; see module doc)."""
    if not cfg.fused_window_stats:
        return window_stats_ref(state, cfg)
    red = window_reductions(
        state.task_state, state.task_usage, state.task_prio,
        state.node_active, state.node_total, state.node_reserved,
        state.node_used, use_kernel=cfg.use_kernels)
    denom = jnp.maximum(red.cap, 1e-9)
    usage_mean = jnp.where(red.n_running > 0,
                           red.usage_sum / jnp.maximum(red.n_running, 1),
                           0.0)
    return {
        "n_nodes": red.n_nodes,
        "n_running": red.n_running,
        "n_pending": red.n_pending,
        "running_by_priority": red.by_prio[:, 0],
        "pending_by_priority": red.by_prio[:, 1],
        "capacity": red.cap,
        "reserved": red.reserved,
        "used": red.used,
        "reserved_frac": red.reserved / denom,
        "used_frac": red.used / denom,
        "overestimate_frac": 1.0 - red.used / jnp.maximum(red.reserved, 1e-9),
        "usage_mean": usage_mean,
        "util_balance_var": red.util_var,
        "reserved_balance_var": red.res_var,
        "evictions": state.evictions,
        "completions": state.completions,
        "placements": state.placements,
        "overflow_drops": state.overflow_drops,
    }


def window_stats_ref(state: SimState, cfg: SimConfig) -> Dict[str, jax.Array]:
    """The pre-fusion stats body: ~6 independent full passes over the task
    table.  Equivalence oracle for the fused path and the stats half of the
    PR-3-era full baseline in ``benchmarks/engine_bench.py``."""
    running = state.task_state == TASK_RUNNING
    pending = state.task_state == TASK_PENDING
    active = state.node_active

    cap = jnp.where(active[:, None], state.node_total, 0.0).sum(0)   # (R,)
    reserved = state.node_reserved.sum(0)
    used = state.node_used.sum(0)
    denom = jnp.maximum(cap, 1e-9)

    usage_mean = jnp.where(
        running.sum() > 0,
        (state.task_usage * running[:, None].astype(jnp.float32)).sum(0)
        / jnp.maximum(running.sum(), 1),
        0.0)                                                          # (U,)

    # per-node utilisation spread (load-balance quality — the MASB metric)
    node_util = jnp.where(active[:, None],
                          state.node_used / jnp.maximum(state.node_total, 1e-9),
                          0.0)[:, 0]
    util_mean = node_util.sum() / jnp.maximum(active.sum(), 1)
    util_var = (jnp.where(active, (node_util - util_mean) ** 2, 0.0).sum()
                / jnp.maximum(active.sum(), 1))
    # same spread over *reserved* fractions (defined even without usage logs)
    node_res = jnp.where(active[:, None],
                         state.node_reserved / jnp.maximum(state.node_total,
                                                           1e-9),
                         0.0).mean(-1)
    res_mean = node_res.sum() / jnp.maximum(active.sum(), 1)
    res_var = (jnp.where(active, (node_res - res_mean) ** 2, 0.0).sum()
               / jnp.maximum(active.sum(), 1))

    # per-priority-class population (GCD priorities 0-11; Table II rows
    # 'Local Scheduler (Priority Class)' / 'Jobs and Tasks Priority') —
    # one fused scatter over the task table, split into the two columns
    prio = jnp.clip(state.task_prio, 0, 11)
    by_prio = jnp.zeros((12, 2), jnp.int32).at[prio].add(
        jnp.stack([running, pending], axis=1).astype(jnp.int32))
    run_by_prio, pend_by_prio = by_prio[:, 0], by_prio[:, 1]

    return {
        "n_nodes": active.sum().astype(jnp.int32),
        "n_running": running.sum().astype(jnp.int32),
        "n_pending": pending.sum().astype(jnp.int32),
        "running_by_priority": run_by_prio,
        "pending_by_priority": pend_by_prio,
        "capacity": cap,
        "reserved": reserved,
        "used": used,
        "reserved_frac": reserved / denom,
        "used_frac": used / denom,
        "overestimate_frac": 1.0 - used / jnp.maximum(reserved, 1e-9),
        "usage_mean": usage_mean,
        "util_balance_var": util_var,
        "reserved_balance_var": res_var,
        "evictions": state.evictions,
        "completions": state.completions,
        "placements": state.placements,
        "overflow_drops": state.overflow_drops,
    }
