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

Layout (what ops.py hands in). Row operands are read straight from the
(lanes, task rows, nodes) arrays the base pass and the proposals produce,
so nothing is transposed in HBM: a block is (B|1, TP, TN), and one task
row of every lane at once is ``ref[:, t, :]``, a (B, TN) tile with the
lanes on sublanes and the nodes on lanes (a sublane-strided load):

* row operands (B, P, N), or (P, N) where every lane shares one: scores,
  ok (i32; absent where the fit mask is ``isfinite(scores)``, as the base
  pass defines it), ext; req (B|1, P, R); valid (B|1, P, 1) i32;
* node operands (R, K, B|1, TN): total (dead / padded nodes = -1), denom,
  reserved0 — the node axis cut into K tiles of TN columns;
* dyn (B, 1) i32 per-lane dynamic-best-fit flags; start (B|1, 1) i32;
* out: node_of (B, P) i32, lane-dense and resident, one column written per
  task; reserved (R, K, B, TN) f32.

The node axis is either whole (K = 1; TP task rows per grid step, looped in
the body) or streamed in K > 1 tiles of TN columns (one task row per step,
grid (P, K)) with a cross-tile running argmax carried in revisited output
blocks: tile 0 is adopted unconditionally, later tiles only on a STRICT
improvement, which preserves the reference's global first-index tie-break
including the all ``-inf`` row (node 0, placed iff it fits). Fit is carried
with the score, not re-derived from it.

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


def _row(ref, t):
    """Task row t of a row block, every lane at once: (B|1, TN). A
    lane-shared operand comes as a 2-D (TP, TN) block."""
    return ref[pl.ds(t, 1), :] if len(ref.shape) == 2 else ref[:, t, :]


def _commit_kernel(*refs, mode: str, n_lanes: int, fam, ext_row,
                   n_real: int, n_tiles: int, n_rows: int, has_scores: bool,
                   has_ok: bool, has_ext: bool):
    refs = list(refs)
    scores_ref = refs.pop(0) if has_scores else None
    ok_ref = refs.pop(0) if has_ok else None
    (req_ref, valid_ref, total_ref, denom_ref, res0_ref, dyn_ref,
     start_ref) = refs[:7]
    refs = refs[7:]
    ext_ref = refs.pop(0) if has_ext else None
    node_ref, res_ref = refs[:2]
    if n_tiles > 1:
        bsc_ref, bn_ref, bfit_ref = refs[2:]
    i, k = pl.program_id(0), pl.program_id(1)
    B, R = n_lanes, total_ref.shape[0]
    TN = total_ref.shape[-1]

    @pl.when((i == 0) & (k == 0))
    def _init():
        res_ref[...] = jnp.broadcast_to(res0_ref[...], res_ref.shape)

    lane_col = jax.lax.broadcasted_iota(jnp.int32, (B, TN), 1)
    task_col = jax.lax.broadcasted_iota(jnp.int32, node_ref.shape, 1)
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
        """Tile-local choice for row t of the block against tally tiles
        ``res``: (tile best score, tile argmax column, fit there, req
        columns)."""
        req_row = _row(req_ref, t)                            # (B|1, R)
        rq = [req_row[:, r:r + 1] for r in range(R)]          # (B|1, 1)
        free = [tot[r] - res[r] for r in range(R)]            # (B, TN)
        srow = _row(scores_ref, t) if has_scores else None
        if has_ok:
            fit = _row(ok_ref, t) != 0
        else:
            fit = jnp.abs(srow) < jnp.inf                     # isfinite
        for r in range(R):
            fit = fit & (rq[r] <= free[r] + 1e-9)
        fit = jnp.broadcast_to(fit, (B, TN))
        if mode != "static":
            terms = [(free[r] - rq[r]) / den[r] for r in range(R)]
            sc_dyn = -functools.reduce(jnp.add, terms)
        if mode != "dynamic":
            pref = _family_pref(srow, no,
                                _row(ext_ref, t) if has_ext else None,
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

    def place(g, t, best_n, best_fit, rq):
        """Write task g's decision (row t of the block) into its node_of
        column; return the (B, 1) per-column adds."""
        can = (best_fit != 0) & (_row(valid_ref, t) != 0)
        node_ref[...] = jnp.where(task_col == g,
                                  jnp.where(can, best_n, -1), node_ref[...])
        return [jnp.where(can, rq[r], 0.0) for r in range(R)]

    if n_tiles == 1:
        def row(t, res):
            _, loc, fit_at, rq = choose(t, res)
            add = place(i * n_rows + t, t, loc, fit_at, rq)
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
    t = i % n_rows
    best, loc, fit_at, rq = choose(t, [res_ref[r, k] for r in range(R)])
    adopt = (k == 0) | (best > bsc_ref[...])
    best_n = jnp.where(adopt, k * TN + loc, bn_ref[...])
    best_fit = jnp.where(adopt, fit_at, bfit_ref[...])
    bsc_ref[...] = jnp.where(adopt, best, bsc_ref[...])
    bn_ref[...] = best_n
    bfit_ref[...] = best_fit

    @pl.when(k == n_tiles - 1)
    def _commit():
        add = place(i, t, best_n, best_fit, rq)

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
    doc); ``scores``, ``ok`` and ``ext`` may each be None where the mode
    and families never read them (not both of scores and ok). The node
    tiling is read off ``total`` (R, K, B|1, TN); row blocks hold
    ``tile_p`` task rows. Returns (node_of (B, P) i32, reserved
    (R, K, B, TN) f32 — the final VMEM-resident tally)."""
    P = req.shape[1]
    R, K, _, TN = total.shape
    assert mode in ("static", "dynamic", "both"), mode
    assert len(fam) in (1, n_lanes), (len(fam), n_lanes)
    assert scores is not None or ok is not None
    assert P % tile_p == 0, (P, tile_p)
    B = n_lanes
    # whole node axis: tile_p rows a step; streamed: one row a step, its
    # block (the tile_p rows around it) fetched again for every tile
    steps = P // tile_p if K == 1 else P

    def row_block(i):
        return i if K == 1 else i // tile_p

    def row_spec(x):                  # (B, P, Np) or lane-shared (P, Np)
        if x.ndim == 2:
            return pl.BlockSpec((tile_p, TN), lambda i, k: (row_block(i), k))
        return pl.BlockSpec((x.shape[0], tile_p, TN),
                            lambda i, k: (0, row_block(i), k))

    def rows_of(x):                   # (B|1, P, C)
        return pl.BlockSpec((x.shape[0], tile_p, x.shape[2]),
                            lambda i, k: (0, row_block(i), 0))

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i, k: (0,) * x.ndim)

    operands, in_specs = [], []
    for x in (scores, ok):
        if x is not None:
            operands.append(x)
            in_specs.append(row_spec(x))
    operands += [req, valid, total, denom, reserved0, dyn, start]
    in_specs += [rows_of(req), rows_of(valid), whole(total), whole(denom),
                 whole(reserved0), whole(dyn), whole(start)]
    if ext is not None:
        operands.append(ext)
        in_specs.append(row_spec(ext))
    out_shape = [jax.ShapeDtypeStruct((B, P), jnp.int32),
                 jax.ShapeDtypeStruct((R, K, B, TN), jnp.float32)]
    out_specs = [pl.BlockSpec((B, P), lambda i, k: (0, 0)),
                 pl.BlockSpec((R, K, B, TN), lambda i, k: (0, 0, 0, 0))]
    if K > 1:
        for dt in (jnp.float32, jnp.int32, jnp.int32):
            out_shape.append(jax.ShapeDtypeStruct((B, 1), dt))
            out_specs.append(pl.BlockSpec((B, 1), lambda i, k: (0, 0)))

    blocks = [(s.block_shape, x.dtype) for s, x in zip(in_specs, operands)]
    blocks += [(s.block_shape, o.dtype) for s, o in zip(out_specs, out_shape)]
    # the body's (B, TN) values (tally, capacities, scores, masks) spill
    # to the kernel's own VMEM stack: room for 32 of them
    spill = 32 * (-(-B // 8) * 8) * (-(-TN // 128) * 128) * 4
    kernel = functools.partial(
        _commit_kernel, mode=mode, n_lanes=B, fam=fam, ext_row=ext_row,
        n_real=n_real, n_tiles=K, n_rows=tile_p,
        has_scores=scores is not None, has_ok=ok is not None,
        has_ext=ext is not None)
    out = pl.pallas_call(
        kernel,
        grid=(steps, K),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blocks, (4 << 20) + spill)),
        interpret=interpret,
        name="placement_commit",
    )(*operands)
    return out[0], out[1]
