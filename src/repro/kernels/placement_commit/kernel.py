"""Pallas TPU kernel: sequential placement commit with an on-chip tally.

The scheduler finaliser is the one inherently sequential pass in the engine:
tasks are walked in priority order and each assignment changes the free
capacity the next task sees. The XLA ``fori_loop`` formulation re-reads the
(N, R) free-capacity matrix from HBM on every task. Here the loop runs
inside one kernel:

* the grid steps over task rows in priority order (the TPU grid is
  sequential, which is exactly what a priority scan needs);
* the running reservation tally is a revisited output block resident in
  VMEM across the whole scan;
* per-task work is vector arithmetic on VMEM-resident blocks: fit mask,
  (optional) dynamic best-fit re-score, first-index argmax, and a one-node
  tally update — no HBM round-trips between tasks.

One kernel serves every caller. The preference row of a lane is derived in
the body from its proposal *family* (``FAM_*``, a static per-lane tuple):
the base-pass scores themselves (greedy; also the plain commit, whose
``pref`` is passed as the scores), a node-order ranking computed from a
per-lane start (first-fit / round-robin), or a pre-evaluated external row
(opaque proposals). So the (B, P, N) preference tensor of the fused
families never exists in HBM.

Layout (what ops.py hands in — node columns on the 128-wide lane axis,
scenario lanes on sublanes, task rows and resource columns on untiled
leading axes, so every per-task value is a 2-D (B, nodes) tile):

* row operands (P, B|1, Np): scores, ok (i32), ext; req (P, B|1, R);
  valid (P, B|1, 1) i32;
* node operands (R, K, B|1, TN): total (dead / padded nodes = -1), denom,
  reserved0 — the node axis cut into K tiles of TN columns;
* dyn (B, 1) i32 per-lane dynamic-best-fit flags; start (B|1, 1) i32.

The node axis is either whole (K = 1; any number of task rows per grid
step, looped in the body) or streamed in K > 1 tiles of TN columns (one
task row per step, grid (P, K)) with a cross-tile running argmax carried
in revisited output blocks: tile 0 is adopted unconditionally, later tiles
only on a STRICT improvement, which preserves the reference's global
first-index tie-break including the all ``-inf`` row (node 0, placed iff it
fits). Fit is carried with the score, not re-derived from it.

The assignment semantics are bit-identical to ``ref.placement_commit_ref``
per lane: same fit epsilon, same score expressions (the re-score sums its
R terms in column order, like the reference), the same first-index argmax,
and the tally update writes ``reserved[n] + add`` for the argmax node even
when the task cannot place (add = 0), exactly like ``.at[n].add(add)``.

``mode`` specialises the compiled body: 'static' never computes the dynamic
re-score, 'dynamic' never reads a preference, and 'both' selects at run
time from the per-lane flag (the fleet dispatches with a traced flag).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem_limit

NEG_INF = float("-inf")

# Proposal family codes: how a lane's preference row is derived inside the
# kernel. Defined here (not in repro.sched) so the kernel package stays
# importable without the scheduler registry.
FAM_EXTERNAL = 0    # pref comes in via the ``ext`` operand (opaque proposal)
FAM_SCORES = 1      # pref IS the score operand (greedy; the plain commit)
FAM_NODE_ORDER = 2  # pref = -((col - start) % N) — first-fit / round-robin


def _lane_mask(fam, target, n_lanes):
    """(B, 1) bool: lanes whose static family is ``target``."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, 1), 0)
    m = lanes < 0
    for i, f in enumerate(fam):
        if f == target:
            m = m | (lanes == i)
    return m


def _family_pref(scores, no, ext, fam, ext_row, n_lanes):
    """This task row's (B, TN) preference per lane from its static family.
    Single-family calls collapse to the bare operand (no select)."""
    pref = scores
    if any(f == FAM_NODE_ORDER for f in fam):
        if all(f == FAM_NODE_ORDER for f in fam):
            pref = no
        else:
            pref = jnp.where(_lane_mask(fam, FAM_NODE_ORDER, n_lanes), no,
                             pref)
    if any(f == FAM_EXTERNAL for f in fam):
        lanes = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, 1), 0)
        sel = jnp.broadcast_to(pref, (n_lanes, pref.shape[-1]))
        for i, (f, r) in enumerate(zip(fam, ext_row)):
            if f == FAM_EXTERNAL:
                sel = jnp.where(lanes == i, ext[r:r + 1], sel)
        pref = sel
    return pref


def _commit_kernel(scores_ref, ok_ref, req_ref, valid_ref, total_ref,
                   denom_ref, res0_ref, dyn_ref, start_ref, *rest,
                   mode: str, n_lanes: int, fam, ext_row, n_real: int,
                   n_tiles: int, n_rows: int):
    has_ext = any(f == FAM_EXTERNAL for f in fam)
    ext_ref = rest[0] if has_ext else None
    outs = rest[1:] if has_ext else rest
    if n_tiles > 1:
        node_ref, res_ref, bsc_ref, bn_ref, bfit_ref = outs
    else:
        node_ref, res_ref = outs
    i, k = pl.program_id(0), pl.program_id(1)
    B, R = n_lanes, total_ref.shape[0]
    TN = total_ref.shape[-1]

    @pl.when((i == 0) & (k == 0))
    def _init():
        res_ref[...] = jnp.broadcast_to(res0_ref[...], res_ref.shape)

    lane_col = jax.lax.broadcasted_iota(jnp.int32, (B, TN), 1)
    tot = [total_ref[r, k] for r in range(R)]                 # (B|1, TN)
    den = [denom_ref[r, k] for r in range(R)] if mode != "static" else None
    dyn = dyn_ref[...] != 0                                   # (B, 1)
    no = None
    if mode != "dynamic" and any(f == FAM_NODE_ORDER for f in fam):
        # node-order preference, shared by every task row: first-fit at
        # start = 0, round-robin at the window-rotated start. Padded
        # columns (>= n_real) give garbage the fit mask always rejects.
        col = k * TN + lane_col
        no = -(((col - start_ref[...]) % n_real).astype(jnp.float32))

    def choose(t, res):
        """Tile-local choice for task row t against tally tiles ``res``:
        (tile best score, tile argmax column, fit there, req columns)."""
        req_row = req_ref[t]                                  # (B|1, R)
        rq = [req_row[:, r:r + 1] for r in range(R)]          # (B|1, 1)
        free = [tot[r] - res[r] for r in range(R)]            # (B, TN)
        fit = ok_ref[t] != 0
        for r in range(R):
            fit = fit & (rq[r] <= free[r] + 1e-9)
        fit = jnp.broadcast_to(fit, (B, TN))
        if mode != "static":
            terms = [(free[r] - rq[r]) / den[r] for r in range(R)]
            sc_dyn = -functools.reduce(jnp.add, terms)
        if mode != "dynamic":
            pref = _family_pref(scores_ref[t], no,
                                ext_ref[t] if has_ext else None,
                                fam, ext_row, B)
        if mode == "both":
            sc = jnp.where(dyn, sc_dyn, pref)
        elif mode == "dynamic":
            sc = sc_dyn
        else:
            sc = pref
        sc = jnp.where(fit, jnp.broadcast_to(sc, (B, TN)), NEG_INF)
        best = jnp.max(sc, axis=1, keepdims=True)             # (B, 1)
        loc = jnp.min(jnp.where(sc == best, lane_col, TN), axis=1,
                      keepdims=True)
        fit_at = jnp.max(jnp.where(lane_col == loc, fit.astype(jnp.int32), 0),
                         axis=1, keepdims=True)
        return best, loc, fit_at, rq

    def place(t, best_n, best_fit, rq):
        """Write row t's decision; return the (B, 1) per-column adds."""
        can = (best_fit != 0) & (valid_ref[t] != 0)
        node_ref[t] = jnp.where(can, best_n, -1)
        return [jnp.where(can, rq[r], 0.0) for r in range(R)]

    if n_tiles == 1:
        def row(t, res):
            _, loc, fit_at, rq = choose(t, res)
            add = place(t, loc, fit_at, rq)
            hit = lane_col == loc
            return tuple(jnp.where(hit, res[r] + add[r], res[r])
                         for r in range(R))

        res = tuple(jnp.broadcast_to(res_ref[r, 0], (B, TN))
                    for r in range(R))
        res = jax.lax.fori_loop(0, n_rows, row, res)
        for r in range(R):
            res_ref[r, 0] = res[r]
        return

    # node-streamed: one task row per grid step, argmax carried over tiles
    best, loc, fit_at, rq = choose(0, [res_ref[r, k] for r in range(R)])
    adopt = (k == 0) | (best > bsc_ref[...])
    best_n = jnp.where(adopt, k * TN + loc, bn_ref[...])
    best_fit = jnp.where(adopt, fit_at, bfit_ref[...])
    bsc_ref[...] = jnp.where(adopt, best, bsc_ref[...])
    bn_ref[...] = best_n
    bfit_ref[...] = best_fit
    add = place(0, best_n, best_fit, rq)

    @pl.when(k == n_tiles - 1)
    def _commit():
        def tile(kk, carry):
            hit = kk * TN + lane_col == best_n
            for r in range(R):
                cur = res_ref[r, kk]
                res_ref[r, kk] = jnp.where(hit, cur + add[r], cur)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)


def commit_pallas(scores, ok, req, valid, total, denom, reserved0, dyn,
                  start, ext, *, n_lanes: int, fam, ext_row, n_real: int,
                  mode: str, tile_p: int, interpret: bool):
    """Batched commit over ``n_lanes`` lanes in the kernel layout (module
    doc). The node tiling is read off ``total`` (R, K, B|1, TN); K > 1
    needs ``tile_p == 1``. Returns (node_of (P, B, 1) i32, reserved
    (R, K, B, TN) f32 — the final VMEM-resident tally)."""
    P, Np = scores.shape[0], scores.shape[2]
    R, K, _, TN = total.shape
    assert mode in ("static", "dynamic", "both"), mode
    assert len(fam) in (1, n_lanes), (len(fam), n_lanes)
    assert P % tile_p == 0 and K * TN == Np, (P, tile_p, K, TN, Np)
    assert K == 1 or tile_p == 1, (K, tile_p)
    B = n_lanes

    def row_spec(x):
        return pl.BlockSpec((tile_p,) + x.shape[1:-1] + (TN,),
                            lambda i, k: (i, 0, k))

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i, k: (0,) * x.ndim)

    operands = [scores, ok, req, valid, total, denom, reserved0, dyn, start]
    in_specs = [row_spec(scores), row_spec(ok),
                pl.BlockSpec((tile_p,) + req.shape[1:],
                             lambda i, k: (i, 0, 0)),
                pl.BlockSpec((tile_p,) + valid.shape[1:],
                             lambda i, k: (i, 0, 0)),
                whole(total), whole(denom), whole(reserved0), whole(dyn),
                whole(start)]
    if ext is not None:
        operands.append(ext)
        in_specs.append(row_spec(ext))
    out_shape = [jax.ShapeDtypeStruct((P, B, 1), jnp.int32),
                 jax.ShapeDtypeStruct((R, K, B, TN), jnp.float32)]
    out_specs = [pl.BlockSpec((tile_p, B, 1), lambda i, k: (i, 0, 0)),
                 pl.BlockSpec((R, K, B, TN), lambda i, k: (0, 0, 0, 0))]
    if K > 1:
        for dt in (jnp.float32, jnp.int32, jnp.int32):
            out_shape.append(jax.ShapeDtypeStruct((B, 1), dt))
            out_specs.append(pl.BlockSpec((B, 1), lambda i, k: (0, 0)))

    blocks = [(s.block_shape, x.dtype) for s, x in zip(in_specs, operands)]
    blocks += [(s.block_shape, o.dtype) for s, o in zip(out_specs, out_shape)]
    kernel = functools.partial(_commit_kernel, mode=mode, n_lanes=B, fam=fam,
                               ext_row=ext_row, n_real=n_real, n_tiles=K,
                               n_rows=tile_p)
    out = pl.pallas_call(
        kernel,
        grid=(P // tile_p, K),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks)),
        interpret=interpret,
    )(*operands)
    return out[0], out[1]
