"""Pallas TPU kernel: every task-table stats reduction in ONE sweep.

After PR 4 removed the accounting recomputes, ``window_stats`` was the
engine's last O(max_tasks) consumer per window: ~6 independent full passes
(running/pending masks and counts, the masked usage-mean sum, the
per-priority scatter), ×B in the scenario fleet. Here the task table is
grid-stepped ONCE: each grid step loads one task tile and accumulates — in
revisited output blocks resident in VMEM across the whole sweep (the
``segment_usage`` accumulation pattern) — the masked usage sum and the
per-priority running/pending populations (masked lane reductions; the
counts are their row sums).

The small node-table pass (capacity, tallies, balance variances) is not
here: it is ``ref.node_reductions_ref`` on every path, so its float sums —
whose order a kernel's lane reduction would change — are the same bits with
and without kernels.

The kernel is **natively batched** exactly like ``placement_commit``: every
operand carries a leading lane axis of size B or 1 (lane-shared), the
per-tile arithmetic broadcasts across lanes on the vector units, and the
``custom_vmap`` rule in ops.py routes the scenario fleet's vmap into one
kernel invocation instead of Pallas's serialising fallback.

Layout: task columns sit on the 128-wide lane axis — state/prio (B|1, T)
i32, usage (B|1, U, T) — so every reduction is a lane reduction of a 2-D
(lanes, columns) tile.

Integer outputs (counts, histogram) are exact. The usage sum accumulates
tile by tile, so it can differ from ``ref.window_reductions_ref`` in the
last bits (summation order); on grid-aligned data it is exact and equal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.state import TASK_PENDING, TASK_RUNNING
from repro.kernels import vmem_limit
from repro.kernels.window_stats.ref import N_PRIO


def _columns(parts, n_lanes):
    """Assemble (B|1, 1) columns into one (B, len(parts)) block."""
    col = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, len(parts)), 1)
    out = jnp.zeros((n_lanes, len(parts)), parts[0].dtype)
    for j, p in enumerate(parts):
        out = jnp.where(col == j, p, out)
    return out


def _kernel(state_ref, usage_ref, prio_ref, hist_ref, usum_ref, *,
            n_lanes: int):
    i = pl.program_id(0)
    B = n_lanes

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)
        usum_ref[...] = jnp.zeros_like(usum_ref)

    # --- task tile: one load, every accumulator updated -------------------
    state = state_ref[...]                             # (B|1, TT) i32
    prio = jnp.clip(prio_ref[...], 0, N_PRIO - 1)
    running = state == TASK_RUNNING
    pending = state == TASK_PENDING
    for c, mask in enumerate((running, pending)):
        m = mask.astype(jnp.int32)
        per_prio = [jnp.sum(jnp.where(prio == p, m, 0), axis=1, keepdims=True)
                    for p in range(N_PRIO)]
        hist_ref[c] += _columns(per_prio, B)           # (B, 12)
    n_use = usage_ref.shape[1]
    usum = [jnp.sum(jnp.where(running, usage_ref[:, u, :], 0.0), axis=1,
                    keepdims=True) for u in range(n_use)]
    usum_ref[...] += _columns(usum, B)                 # (B, U)


def window_stats_pallas(task_state, task_usage, task_prio, *, n_lanes: int,
                        tile_t: int, interpret: bool):
    """Fused task-table reductions over ``n_lanes`` scenario lanes (1 for
    the single-trajectory engine), in the layout of the module doc; each
    operand's lane axis is ``n_lanes`` or 1 (lane-shared, kept un-copied).
    Returns (by_prio (2, B, 12) i32 = [running, pending] populations,
    usage_sum (B, U) f32)."""
    T = task_state.shape[1]
    U = task_usage.shape[1]
    assert T % tile_t == 0, (T, tile_t)
    B = n_lanes

    def task_spec(x):
        return pl.BlockSpec(x.shape[:-1] + (tile_t,),
                            lambda i: (0,) * (x.ndim - 1) + (i,))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    operands = (task_state, task_usage, task_prio)
    in_specs = [task_spec(x) for x in operands]
    out_shape = (jax.ShapeDtypeStruct((2, B, N_PRIO), jnp.int32),
                 jax.ShapeDtypeStruct((B, U), jnp.float32))
    out_specs = tuple(whole(o.shape) for o in out_shape)
    blocks = [(s.block_shape, x.dtype) for s, x in zip(in_specs, operands)]
    blocks += [(o.shape, o.dtype) for o in out_shape]
    return pl.pallas_call(
        functools.partial(_kernel, n_lanes=B),
        grid=(T // tile_t,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(blocks)),
        interpret=interpret,
    )(*operands)
