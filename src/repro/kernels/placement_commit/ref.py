"""Pure-jnp oracle for the placement-commit kernel: the sequential
capacity-checked assignment loop lifted verbatim out of the seed scheduler
finaliser (now ``sched.commit.finalize``), so the kernel and the engine are
validated against a single source of truth.

The loop walks the P pending tasks in priority order; each step re-checks
resource fit against the *running* reservation tally (no proposal can
overcommit a node, whatever preference matrix it hands over) and either
assigns the argmax-feasible node or leaves the task pending (-1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ordered import sum_columns

NEG = -jnp.inf


def placement_commit_ref(pref: jax.Array, req: jax.Array, base_ok: jax.Array,
                         valid: jax.Array, total: jax.Array,
                         denom: jax.Array, reserved0: jax.Array,
                         dynamic_bestfit=False):
    """pref (P,N) f32, req (P,R) f32, base_ok (P,N) bool, valid (P,) bool,
    total (N,R) f32 (inactive nodes folded to -1), denom (N,R) f32,
    reserved0 (N,R) f32 -> (node_of (P,) i32 (-1 = not placed),
    reserved (N,R) f32 — the final tally, reserved0 + every placed request,
    which incremental accounting adopts as the post-commit node_reserved).

    dynamic_bestfit: recompute best-fit scores against the running
    reservation tally (true best-fit-decreasing) instead of the static pref.
    May be a traced bool scalar (the scenario fleet dispatches schedulers
    per-lane at runtime); the static True/False fast paths stay unchanged.
    """
    P = pref.shape[0]
    is_traced = isinstance(dynamic_bestfit, jax.Array)

    def body(i, carry):
        reserved, node_of = carry
        free = total - reserved                                 # (N, R)
        fit = (req[i][None, :] <= free + 1e-9).all(-1) & base_ok[i]
        if is_traced or dynamic_bestfit:
            sc_dyn = -sum_columns((free - req[i][None, :]) / denom)
        if is_traced:
            sc = jnp.where(dynamic_bestfit, sc_dyn, pref[i])
            sc = jnp.where(fit, sc, NEG)
        elif dynamic_bestfit:
            sc = jnp.where(fit, sc_dyn, NEG)
        else:
            sc = jnp.where(fit, pref[i], NEG)
        n = jnp.argmax(sc).astype(jnp.int32)
        can = fit[n] & valid[i]
        add = jnp.where(can, req[i], 0.0)
        reserved = reserved.at[n].add(add)
        node_of = node_of.at[i].set(jnp.where(can, n, -1))
        return reserved, node_of

    node_of0 = jnp.full((P,), -1, jnp.int32)
    reserved, node_of = jax.lax.fori_loop(0, P, body, (reserved0, node_of0))
    return node_of, reserved


def sched_pref_ref(scores: jax.Array, start, family: int, ext=None):
    """Reference proposal-family expansion for the fused scheduler pass:
    derive the (P, N) preference matrix the family implies, so the fused
    kernel can be validated against ``pref -> placement_commit_ref``.

    family is a ``kernel.FAM_*`` code: SCORES passes the base-pass score
    matrix through (greedy), NODE_ORDER ranks nodes by ``-((col - start) %
    N)`` (first-fit at start=0, round-robin at a rotating start), EXTERNAL
    returns the pre-evaluated ``ext`` (opaque proposal — nothing to fuse).
    """
    from repro.kernels.placement_commit.kernel import (FAM_NODE_ORDER,
                                                       FAM_SCORES)
    if family == FAM_SCORES:
        return scores
    if family == FAM_NODE_ORDER:
        N = scores.shape[-1]
        order = (jnp.arange(N, dtype=jnp.int32) - start) % N
        return jnp.broadcast_to(-order.astype(jnp.float32)[None, :],
                                scores.shape)
    if ext is None:
        raise ValueError("FAM_EXTERNAL needs the evaluated ext preference")
    return ext
