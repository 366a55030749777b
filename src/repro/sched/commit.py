"""The capacity-checked finaliser every scheduler shares.

Proposals only rank nodes; *this* pass decides. An in-priority-order scan
re-checks resource fit against the running reservation tally, so **no
scheduler can overcommit a node** regardless of what it proposes — the
engine invariant the tests verify. The scan itself lives in
``kernels/placement_commit``: where the program lowers for a TPU it is
always the compiled Pallas kernel (tally resident in VMEM); for any other
platform it is the jnp reference, or the interpreted kernel where
``cfg.use_kernels`` asks for it. This module derives the kernel operands
from the simulation state and applies the resulting assignment vector
back to it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import SimConfig
from repro.core import trace
from repro.core.stats import ACCOUNTED_USAGE_COLS
from repro.core.state import SimState, TASK_RUNNING
from repro.kernels.placement_commit.ops import placement_commit


def commit_operands(state: SimState, cfg: SimConfig, idx):
    """The commit kernel's node/request operands derived from sim state:
    (total (N, R) with inactive nodes folded to -1, denom (N, R) best-fit
    normaliser, req (P, R) gathered requests). Shared by :func:`finalize`
    and the fleet's switchless dispatch, so both paths feed the kernel the
    same bits."""
    total = jnp.where(state.node_active[:, None], state.node_total, -1.0)
    denom = jnp.maximum(state.node_total, 1e-6)
    req = state.task_req[idx]                                   # (P, R)
    return total, denom, req


@trace.scoped(trace.COMMIT)
def finalize(state: SimState, cfg: SimConfig, idx, valid, base_ok, pref,
             dynamic_bestfit=False) -> SimState:
    """Sequential capacity-checked assignment in priority order.

    pref: (P, N) preference scores (higher better; NEG = never).
    base_ok: (P, N) bool feasibility, or None where it is
    ``isfinite(pref)`` (the proposal handed back the base pass's scores).
    dynamic_bestfit: recompute best-fit scores against the *running*
    reservation tally (true best-fit-decreasing) instead of static pref.
    May be a traced bool scalar (the scenario fleet dispatches schedulers
    per-lane at runtime); the static True/False fast paths stay unchanged.

    Under incremental accounting the commit pass also settles the books: the
    kernel's final reservation tally (held resident across its grid steps)
    becomes node_reserved directly, and the placed tasks' usage rows are
    scattered into node_used — O(P) work replacing the engine's post-commit
    O(max_tasks) segment-sum recompute.
    """
    total, denom, req = commit_operands(state, cfg, idx)

    node_of, tally = placement_commit(pref, req, base_ok, valid, total, denom,
                                      state.node_reserved, dynamic_bestfit,
                                      use_kernel=cfg.use_kernels,
                                      tile_p=cfg.commit_tile_p or None,
                                      stream_n=cfg.commit_tile_n or None,
                                      return_tally=True)
    return apply_commit(state, cfg, idx, node_of, tally)


def apply_commit(state: SimState, cfg: SimConfig, idx, node_of,
                 tally) -> SimState:
    """Fold a commit result (node_of (P,) i32, tally (N, R) f32) back into
    the sim state — the back half of :func:`finalize`, split out so the
    fleet's switchless dispatch can run the batched fused commit kernel
    between the two halves."""
    placed = node_of >= 0
    task_state = state.task_state.at[idx].set(
        jnp.where(placed, TASK_RUNNING, state.task_state[idx]).astype(jnp.int8))
    task_node = state.task_node.at[idx].set(
        jnp.where(placed, node_of, state.task_node[idx]))
    state = state._replace(
        task_state=task_state, task_node=task_node,
        placements=state.placements + placed.sum().astype(jnp.int32))
    if cfg.incremental_accounting:
        used_cols = state.task_usage[idx][:, jnp.array(ACCOUNTED_USAGE_COLS)]
        node_used = state.node_used.at[
            jnp.where(placed, node_of, cfg.max_nodes)].add(
                jnp.where(placed[:, None], used_cols, 0.0), mode="drop")
        state = state._replace(node_reserved=tally, node_used=node_used)
    return state
