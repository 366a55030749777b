"""Pallas TPU kernel: sorted-free segment-sum of task values into node rows.

AGOCS recomputes per-node reserved/used resources every collection window
(the TrieMap equivalent is thousands of tiny CAS updates). TPU adaptation:
grid-step over task tiles; each tile's contribution is a one-hot matmul
``values^T @ onehot(node_id)`` accumulated into the full (V, N) output block
(node columns on the lane axis), which stays resident in VMEM across the
whole grid. Revisiting the same output block across grid steps is
the canonical Pallas accumulation pattern.

Masked / unplaced tasks (node < 0) are routed to a virtual row N and dropped.
The matmul runs at HIGHEST precision: at the default one bf16 pass the
values would be rounded to 8 mantissa bits on a TPU. The sums accumulate in
the matmul's order, not the reference's fixed one, so they agree with it to
the last bits, not bitwise — which is why the engine's tallies, which later
fits compare against, take the reference on every path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem_limit


def _kernel(node_ref, val_ref, out_ref, *, n_nodes: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    node = node_ref[...]                          # (TT, 1) i32
    vals = val_ref[...].astype(jnp.float32)       # (TT, V)
    # one-hot over nodes; out-of-range rows contribute nothing
    narange = jax.lax.broadcasted_iota(jnp.int32, (node.shape[0], n_nodes), 1)
    onehot = (narange == node).astype(jnp.float32)           # (TT, N)
    contrib = jax.lax.dot_general(vals, onehot, (((0,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)  # (V, N)
    out_ref[...] += contrib


def segment_usage_pallas(task_node: jax.Array, values: jax.Array,
                         n_nodes: int, *, tile_t: int,
                         interpret: bool) -> jax.Array:
    """task_node (T,) i32 (>= n_nodes = dropped), values (T, V) -> (V, N)
    per-node sums, node columns on the lane axis."""
    T, V = values.shape
    assert T % tile_t == 0, (T, tile_t)
    grid = (T // tile_t,)
    kernel = functools.partial(_kernel, n_nodes=n_nodes)
    blocks = [((tile_t, 1), jnp.int32), ((tile_t, V), values.dtype),
              ((V, n_nodes), jnp.float32)]
    onehot_bytes = tile_t * (-(-n_nodes // 128) * 128) * 4
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_t, V), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((V, n_nodes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((V, n_nodes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the one-hot and the HIGHEST-precision operand splits
            vmem_limit_bytes=vmem_limit(blocks, headroom=4 * onehot_bytes)),
        interpret=interpret,
    )(task_node[:, None], values)
