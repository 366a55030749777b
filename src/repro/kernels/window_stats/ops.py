"""jit-able wrapper for the fused window-stats kernel: padding, kernel/ref
dispatch — and the ``custom_vmap`` rule that makes the scenario fleet's lane
axis ride ONE batched kernel invocation instead of Pallas's serialising vmap
fallback (the ``placement_commit`` pattern).

``core.stats.window_stats`` is the only caller; it composes the final stats
dict from the returned :class:`WindowReductions` so the unfused, fused-ref
and kernel paths all share one assembly (and therefore one key set).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core.state import TASK_EMPTY
from repro.kernels import by_platform
from repro.kernels.window_stats.kernel import window_stats_pallas
from repro.kernels.window_stats.ref import (WindowReductions,
                                            node_reductions_ref,
                                            window_reductions_ref)


@functools.lru_cache(maxsize=None)
def _make_reduce(tile_t: Optional[int]):
    """Build the (cached) kernel entry for one static configuration.

    The primal path runs the batched kernel at B=1; the ``custom_vmap`` rule
    broadcasts any unbatched operand and runs the SAME kernel with the real
    lane axis inside the block, so vmapped stats rows (the scenario fleet)
    vectorise across lanes instead of being serialised into grid steps.
    """

    def call_batched(n_lanes, state, usage, prio):
        T = state.shape[1]

        def run(state, usage, prio, *, interpret):
            # the interpreter runs the whole table as one tile (each grid
            # step is a trip through its loop); on a TPU 8,192-row tiles
            # keep the usage block small in VMEM
            tt = min(tile_t or (T if interpret else 8192), T)
            Tp = -(-T // tt) * tt
            pad = ((0, 0), (0, Tp - T))
            # EMPTY rows are neither running nor pending: no contribution
            state = jnp.pad(state.astype(jnp.int32), pad,
                            constant_values=TASK_EMPTY)
            prio = jnp.pad(prio, pad)
            usage = jnp.pad(usage, pad + ((0, 0),)).transpose(0, 2, 1)
            return window_stats_pallas(state, usage, prio, n_lanes=n_lanes,
                                       tile_t=tt, interpret=interpret)

        by_prio, usage_sum = by_platform(run, state, usage, prio)
        return by_prio.transpose(1, 2, 0), usage_sum

    @custom_vmap
    def reduce(state, usage, prio):
        out = call_batched(1, state[None], usage[None], prio[None])
        return tuple(x[0] for x in out)

    @reduce.def_vmap
    def _batched_rule(axis_size, in_batched, *args):
        # unbatched (lane-shared) operands keep a size-1 lane axis — the
        # kernel broadcasts them instead of materialising B copies
        lanes = [x if b else x[None] for x, b in zip(args, in_batched)]
        return call_batched(axis_size, *lanes), (True, True)

    return reduce


def window_reductions(task_state, task_usage, task_prio, node_active,
                      node_total, node_reserved, node_used, *,
                      use_kernel: bool = False,
                      tile_t: Optional[int] = None) -> WindowReductions:
    """Every reduction a stats row needs, in one pass over each table.

    task_state (T,) i8, task_usage (T, U) f32, task_prio (T,) i32,
    node_active (N,) bool, node_total/node_reserved/node_used (N, R) f32
    -> :class:`WindowReductions`.  With ``use_kernel`` the Pallas kernel
    grid-steps task tiles once with all accumulators VMEM-resident
    (compiled on a TPU, interpreted elsewhere); otherwise the pure-jnp
    reference runs the same fused formulation. The node-table side is the
    reference on both paths.  Under ``jax.vmap`` the
    kernel path dispatches through a ``custom_vmap`` rule to one
    natively-batched kernel call.

    Not jit-wrapped here: every caller (engine scan, scenario fleet, tests)
    already traces it.
    """
    if not use_kernel:
        return window_reductions_ref(task_state, task_usage, task_prio,
                                     node_active, node_total, node_reserved,
                                     node_used)
    by_prio, usage_sum = _make_reduce(tile_t)(task_state, task_usage,
                                              task_prio)
    n_nodes, cap, reserved, used, util_var, res_var = node_reductions_ref(
        node_active, node_total, node_reserved, node_used)
    return WindowReductions(
        n_running=by_prio[..., 0].sum(-1), n_pending=by_prio[..., 1].sum(-1),
        n_nodes=n_nodes, by_prio=by_prio, usage_sum=usage_sum, cap=cap,
        reserved=reserved, used=used, util_var=util_var, res_var=res_var)
