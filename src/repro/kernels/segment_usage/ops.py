"""jit'd wrapper for the segment-usage kernel: masking, padding, dispatch.

Under incremental accounting (``SimConfig.incremental_accounting``, the
default) this full O(max_tasks) pass is no longer the engine's inner loop:
it serves the periodic drift *resync* (``engine.resync_accounting_jit``),
the full-recompute equivalence path, and masked-subset debits (the scenario
fleet's eviction storm). Those callers set node tallies that decisions
read, so they take the reference, whose sums are bitwise the same on every
backend; the kernel agrees with it to the last bits only."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import by_platform
from repro.kernels.segment_usage.kernel import segment_usage_pallas
from repro.kernels.segment_usage.ref import segment_usage_ref


@functools.partial(jax.jit, static_argnames=("n_nodes", "use_kernel",
                                             "tile_t"))
def segment_usage(task_node: jax.Array, values: jax.Array, mask: jax.Array,
                  n_nodes: int, *, use_kernel: bool = False,
                  tile_t: int = 1024) -> jax.Array:
    """Sum `values` rows into their task's node row. (T,),(T,V),(T,)->(N,V).
    The kernel compiles for a TPU and is interpreted elsewhere
    (:func:`repro.kernels.by_platform`)."""
    if not use_kernel:
        return segment_usage_ref(task_node, values, mask, n_nodes)
    T = task_node.shape[0]
    idx = jnp.where(mask & (task_node >= 0), task_node, n_nodes)  # -> dropped

    def run(idx, values, *, interpret):
        # on a TPU the (tile, N) one-hot must fit VMEM: 128-row tiles
        tile = min(tile_t if interpret else 128, T)
        Tp = -(-T // tile) * tile
        if Tp != T:
            idx = jnp.pad(idx, (0, Tp - T), constant_values=n_nodes)
            values = jnp.pad(values, ((0, Tp - T), (0, 0)))
        return segment_usage_pallas(idx, values, n_nodes, tile_t=tile,
                                    interpret=interpret).T

    return by_platform(run, idx, values)
