"""Pallas TPU kernel: tiled (tasks x nodes) constraint-match + best-fit score.

TPU adaptation of AGOCS's constraint hot loop (paper §VIII): instead of
pointer-chasing per-task constraint lists, the (P, N) eligibility/score
matrix is computed in (TP, TN) tiles with the node tile's attributes,
capacities and reservations resident in VMEM.

Layout notes:
* node operands arrive transposed — total/reserved (R, N) f32 and attrs
  (K, N) i32 — so a node column sits on the 128-wide lane axis and every
  per-node row broadcasts over the task tile's sublanes;
* constraints arrive as three (P, C) int32 planes (idx / op / val);
* node_active is folded into total (inactive rows get capacity -1, which
  can never fit a non-negative request) by ops.py, keeping the kernel
  branch-free;
* R, C and K are compile-time constants, unrolled in the kernel body.

The attribute gather is an exact int32 select over the K attribute rows
(the TPU has no per-lane gather), so values compare exactly like the
reference's ``node_attrs[:, idx]`` at any magnitude — no float round trip.
Scores sum their R columns left to right (``core.ordered.sum_columns``),
term for term the reference expression.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.events import OP_EQ, OP_GT, OP_LT, OP_NE
from repro.kernels import vmem_limit

NEG_INF = float("-inf")


def _kernel(req_ref, cidx_ref, cop_ref, cval_ref,
            total_ref, reserved_ref, attrs_ref,
            out_ref, *, n_res: int, n_cons: int, n_attr: int):
    req = req_ref[...]                    # (TP, R) f32
    total = total_ref[...]                # (R, TN) f32
    free = total - reserved_ref[...]      # (R, TN)
    attrs = attrs_ref[...]                # (K, TN) i32

    # resource fit over all R columns (unrolled) — (TP, TN)
    fit = req[:, 0:1] <= free[0:1, :] + 1e-9
    for r in range(1, n_res):
        fit = fit & (req[:, r:r + 1] <= free[r:r + 1, :] + 1e-9)

    # constraints: exact select-gather + compare per slot (unrolled)
    cidx = cidx_ref[...]                  # (TP, C) i32
    cop = cop_ref[...]
    cval = cval_ref[...]
    ok = fit
    for c in range(n_cons):
        idx = cidx[:, c:c + 1]            # (TP, 1)
        got = jnp.zeros(out_ref.shape, jnp.int32)
        for k in range(n_attr):
            got = jnp.where(idx == k, attrs[k:k + 1, :], got)
        val = cval[:, c:c + 1]
        op = cop[:, c:c + 1]
        is_eq, is_ne = op == OP_EQ, op == OP_NE
        is_lt, is_gt = op == OP_LT, op == OP_GT
        unconstrained = ~(is_eq | is_ne | is_lt | is_gt)
        ok = ok & ((is_eq & (got == val)) | (is_ne & (got != val))
                   | (is_lt & (got < val)) | (is_gt & (got > val))
                   | unconstrained)

    # best-fit score: negated normalised leftover, columns left to right
    denom = jnp.maximum(total, 1e-6)
    score = (free[0:1, :] - req[:, 0:1]) / denom[0:1, :]
    for r in range(1, n_res):
        score = score + (free[r:r + 1, :] - req[:, r:r + 1]) / denom[r:r + 1, :]
    out_ref[...] = jnp.where(ok, -score, NEG_INF)


def constraint_match_pallas(req, cidx, cop, cval, total_t, reserved_t,
                            attrs_t, *, tile_p: int, tile_n: int,
                            interpret: bool):
    """req (P, R), cidx/cop/cval (P, C), total_t/reserved_t (R, N), attrs_t
    (K, N) i32 -> (P, N) f32 scores; P, N multiples of the tiles."""
    P, R = req.shape
    N = total_t.shape[1]
    C = cidx.shape[1]
    K = attrs_t.shape[0]
    assert P % tile_p == 0 and N % tile_n == 0, (P, N, tile_p, tile_n)

    grid = (P // tile_p, N // tile_n)
    kernel = functools.partial(_kernel, n_res=R, n_cons=C, n_attr=K)
    in_specs = [
        pl.BlockSpec((tile_p, R), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_p, C), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_p, C), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_p, C), lambda i, j: (i, 0)),
        pl.BlockSpec((R, tile_n), lambda i, j: (0, j)),
        pl.BlockSpec((R, tile_n), lambda i, j: (0, j)),
        pl.BlockSpec((K, tile_n), lambda i, j: (0, j)),
    ]
    blocks = [((tile_p, R), jnp.float32)] + [((tile_p, C), jnp.int32)] * 3 \
        + [((R, tile_n), jnp.float32)] * 2 + [((K, tile_n), jnp.int32),
                                               ((tile_p, tile_n), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_p, tile_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_limit(blocks)),
        interpret=interpret,
    )(req, cidx, cop, cval, total_t, reserved_t, attrs_t)
