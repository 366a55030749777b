"""Task-constraint / node-attribute matching (paper §III, Table II row
'Attribute constraints'; §VIII calls constraints logic "critical and
time-consuming" — this is the simulator's compute hot spot).

A task carries up to C constraints, each ``(attr_idx, op, value)`` with
op ∈ {=, ≠, <, >} over the node's int32 attribute columns — the exact GCD
task_constraints semantics (attribute names/values are obfuscated ints).
A node is *eligible* for a task iff all its constraints pass AND the node has
enough free (unreserved) capacity for the task's requested resources.

``eligibility`` below is the pure-jnp oracle; the Pallas kernel in
``kernels/constraint_match`` computes the same (P, N) matrix tiled for VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.events import OP_EQ, OP_GT, OP_LT, OP_NE, OP_NONE
from repro.core.ordered import sum_columns


def constraints_ok(cons: jax.Array, node_attrs: jax.Array) -> jax.Array:
    """cons: (P, C, 3); node_attrs: (N, K) -> ok (P, N) bool."""
    attr_idx = cons[:, :, 0]                       # (P, C)
    op = cons[:, :, 1]
    val = cons[:, :, 2]
    # gather node attr values: (N, P, C)
    got = node_attrs[:, attr_idx]                  # fancy-gather -> (N, P, C)
    op_b = op[None]                                # (1, P, C)
    val_b = val[None]
    ok = jnp.where(op_b == OP_EQ, got == val_b,
         jnp.where(op_b == OP_NE, got != val_b,
         jnp.where(op_b == OP_LT, got < val_b,
         jnp.where(op_b == OP_GT, got > val_b, True))))
    return ok.all(axis=-1).T                       # (P, N)


def resource_fit(req: jax.Array, free: jax.Array) -> jax.Array:
    """req: (P, R); free: (N, R) -> fit (P, N) bool."""
    return (req[:, None, :] <= free[None, :, :] + 1e-9).all(axis=-1)


def placement_scores(req: jax.Array, cons: jax.Array, node_total: jax.Array,
                     node_reserved: jax.Array, node_attrs: jax.Array,
                     node_active: jax.Array) -> jax.Array:
    """Best-fit placement score matrix (P, N); -inf where infeasible.

    Score = negated normalised leftover capacity, i.e. prefer the node whose
    free capacity most tightly fits the request (classic best-fit decreasing).
    """
    free = node_total - node_reserved              # (N, R)
    ok = constraints_ok(cons, node_attrs) & resource_fit(req, free)
    ok = ok & node_active[None, :]
    denom = jnp.maximum(node_total, 1e-6)          # (N, R)
    leftover = (free[None] - req[:, None]) / denom[None]   # (P, N, R)
    score = -sum_columns(leftover)
    return jnp.where(ok, score, -jnp.inf)
