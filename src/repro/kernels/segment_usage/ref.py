"""Pure-jnp oracle: per-node accumulation of per-task values (segment-sum)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ordered import ordered_scatter_add


def segment_usage_ref(task_node: jax.Array, values: jax.Array,
                      mask: jax.Array, n_nodes: int) -> jax.Array:
    """task_node (T,) i32 (may be -1), values (T,V) f32, mask (T,) bool
    -> (N, V) f32 sums over tasks placed on each node, in one fixed order
    on every backend (``core.ordered``): the accounting resync sets the
    tallies that later fits compare against."""
    out = jnp.zeros((n_nodes, values.shape[1]), jnp.float32)
    return ordered_scatter_add(out, task_node, mask,
                               values.astype(jnp.float32))
