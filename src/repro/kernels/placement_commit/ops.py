"""jit-able wrappers for the placement-commit kernel: padding, the kernel
layout (see kernel.py), static/dynamic/both mode selection, kernel/ref
dispatch — and the ``custom_vmap`` rule that makes the scenario fleet's
lane axis ride ONE batched kernel invocation instead of Pallas's
serialising vmap fallback.

What runs where is decided from the platform the call lowers for. For a
TPU the commit is always the compiled kernel (:func:`repro.kernels.on_tpu`
in :func:`placement_commit` and :func:`sched_pass`; the scenario fleet's
switchless dispatch makes the same choice around
:func:`sched_commit_fleet`), whatever ``use_kernel`` says; for any other
platform it is the jnp reference unless ``use_kernel`` asks for the
kernel, which then runs in the Pallas interpreter
(:func:`repro.kernels.by_platform`). On a TPU the row operands go in as
the callers hold them, (B, P, N) with no transpose, eight task rows a grid
step and the node axis whole (streamed: padded to the 128-wide lane
tile); under the interpreter the whole batch is one grid step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels import by_platform, on_tpu
from repro.kernels.placement_commit.kernel import (FAM_EXTERNAL,
                                                   FAM_NODE_ORDER,
                                                   FAM_SCORES,
                                                   commit_pallas)
from repro.kernels.placement_commit.ref import (placement_commit_ref,
                                                sched_pref_ref)

__all__ = ["FAM_EXTERNAL", "FAM_NODE_ORDER", "FAM_SCORES",
           "placement_commit", "sched_pass", "sched_commit_fleet"]


def _pad_to(x: jax.Array, n: int, axis: int, fill=0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _commit_batched(n_lanes, scores, ok, req, valid, total, denom, res0, dyn,
                    start, ext, *, fam, ext_row, mode, tile_p, tile_n):
    """Lane-batched commit. Operands carry a leading lane axis of B or 1:
    scores/ok/ext (B|1, P, N), req (B|1, P, R), valid (B|1, P), total/
    denom/res0 (B|1, N, R), dyn (B, 1) i32, start (B|1, 1) i32. ``ok``
    None: the fit mask is ``isfinite(scores)``, derived in the kernel.
    ``tile_n`` < N streams the node axis in tiles. Returns (node_of (B, P)
    i32, reserved (B, N, R) f32).

    The row operands reach the kernel as they are, (B|1, P, N), and are
    padded only where P is not a whole number of row blocks or a streamed
    node axis not a whole number of tiles; an operand the mode and the
    families never read is not handed over at all."""
    P, N = req.shape[1], total.shape[1]
    stream = tile_n is not None and tile_n < N
    if mode == "dynamic":
        ext = None
    if ok is not None and (mode == "dynamic" or FAM_SCORES not in fam):
        scores = None

    def run(scores, ok, req, valid, total, denom, res0, dyn, start, ext, *,
            interpret):
        # a TPU row block is a whole number of 8-row sublane tiles; the
        # interpreter takes the whole batch in one step unless streaming
        align = 1 if interpret else 8
        tp = tile_p or (P if interpret and not stream else 8)
        tp = -(-min(tp, P) // align) * align
        if stream:
            tn = tile_n if interpret else -(-tile_n // 128) * 128
        else:
            tn = N                    # a whole-dim block: never padded
        Pp = -(-P // tp) * tp
        Np = -(-N // tn) * tn

        def rows(x):                  # (B, P, N) -> (B, Pp, Np); a
            if x is None:             # lane-shared (1, P, N) -> (Pp, Np)
                return None
            x = x[0] if x.shape[0] == 1 else x
            return _pad_to(_pad_to(x, Pp, x.ndim - 2), Np, x.ndim - 1)

        def nodes(x, fill):           # (B|1, N, R) -> (R, K, B|1, TN)
            x = _pad_to(x, Np, 1, fill)
            b, _, r = x.shape
            return x.reshape(b, Np // tn, tn, r).transpose(3, 1, 0, 2)

        node_of, reserved = commit_pallas(
            rows(scores), rows(None if ok is None else ok.astype(jnp.int32)),
            _pad_to(req, Pp, 1),
            _pad_to(valid.astype(jnp.int32), Pp, 1)[:, :, None],
            nodes(total, -1.0),       # padded nodes can never fit
            nodes(denom, 1.0),        # keep the re-score finite
            nodes(res0, 0.0), dyn, start, rows(ext),
            n_lanes=n_lanes, fam=fam, ext_row=ext_row, n_real=N, mode=mode,
            tile_p=tp, interpret=interpret)
        R = reserved.shape[0]
        reserved = reserved.transpose(2, 1, 3, 0).reshape(n_lanes, Np, R)
        return node_of[:, :P], reserved[:, :N]

    return by_platform(run, scores, ok, req, valid, total, denom, res0, dyn,
                       start, ext)


def _mode_and_flag(dynamic_bestfit):
    """Kernel mode + (1,) i32 flag operand for a static or traced
    ``dynamic_bestfit``: static flags specialise the kernel body."""
    if isinstance(dynamic_bestfit, jax.Array):
        return "both", dynamic_bestfit.astype(jnp.int32).reshape(1)
    mode = "dynamic" if dynamic_bestfit else "static"
    return mode, jnp.full((1,), int(bool(dynamic_bestfit)), jnp.int32)


@functools.lru_cache(maxsize=None)
def _make_single(family: int, mode: str, tile_p: Optional[int],
                 tile_n: Optional[int], with_ok: bool = True):
    """Cached ``custom_vmap`` entry for one family, ``commit(scores, req,
    [ok,] valid, total, denom, res0, dyn, start)`` (no ``ok`` where
    ``with_ok`` is False: the fit mask is ``isfinite(scores)``): the primal
    path runs the batched kernel at B=1; the vmap rule keeps unbatched
    (lane-shared) operands at a size-1 lane axis and runs the SAME kernel
    with the real lane axis inside the block."""
    fam, ext_row = (family,), (0,)

    def call(n_lanes, scores, req, *rest):
        ok, rest = (rest[0], rest[1:]) if with_ok else (None, rest)
        valid, total, denom, res0, dyn, start = rest
        dyn = jnp.broadcast_to(dyn.reshape(-1, 1), (n_lanes, 1))
        return _commit_batched(
            n_lanes, scores, ok, req, valid, total, denom, res0, dyn,
            start.reshape(-1, 1), None, fam=fam, ext_row=ext_row, mode=mode,
            tile_p=tile_p, tile_n=tile_n)

    @custom_vmap
    def commit(*args):
        node_of, reserved = call(1, *(x[None] for x in args))
        return node_of[0], reserved[0]

    @commit.def_vmap
    def _batched_rule(axis_size, in_batched, *args):
        lanes = [x if b else x[None] for x, b in zip(args, in_batched)]
        return call(axis_size, *lanes), (True, True)

    return commit


def _ref_flag(mode: str, dyn):
    """The reference's ``dynamic_bestfit`` for a kernel mode and its flag
    operand: a traced scalar in 'both', a static bool otherwise."""
    return dyn[0] != 0 if mode == "both" else mode == "dynamic"


def sched_pass(scores, req, base_ok, valid, total, denom, reserved0,
               dynamic_bestfit=False, *, family: int = FAM_SCORES,
               start=0, ext=None, use_kernel: bool = False,
               tile_p: Optional[int] = None, tile_n: Optional[int] = None,
               return_tally: bool = False):
    """Fused proposal+commit for ONE proposal family: derive the preference
    matrix from the base-pass ``scores`` + family params (``kernel.FAM_*``)
    and run the capacity-checked commit without materialising pref in HBM.

    Same operand/return contract as :func:`placement_commit` with ``pref``
    replaced by (scores, family, start): FAM_SCORES uses scores directly
    (greedy), FAM_NODE_ORDER ranks by ``-((col - start) % N)`` (first-fit /
    round-robin; ``start`` may be a traced scalar — the window rotation),
    FAM_EXTERNAL takes the pre-evaluated ``ext`` (opaque proposal — the
    commit still kernelises, the derivation cannot). ``tile_n`` < N
    streams the node axis in tiles. Kernel and ref are bitwise-identical;
    the kernel path vmaps through a ``custom_vmap`` rule. Where the kernel
    runs follows :func:`placement_commit`."""
    if family == FAM_EXTERNAL:
        return placement_commit(sched_pref_ref(scores, start, family, ext),
                                req, base_ok, valid, total, denom,
                                reserved0, dynamic_bestfit,
                                use_kernel=use_kernel, tile_p=tile_p,
                                stream_n=tile_n, return_tally=return_tally)
    mode, dyn = _mode_and_flag(dynamic_bestfit)
    kernel = _make_single(family, mode, tile_p, tile_n)

    def ref(scores, req, base_ok, valid, total, denom, reserved0, dyn,
            start):
        return placement_commit_ref(
            sched_pref_ref(scores, start[0], family), req, base_ok, valid,
            total, denom, reserved0, _ref_flag(mode, dyn))

    out = on_tpu(kernel, kernel if use_kernel else ref, scores, req, base_ok,
                 valid, total, denom, reserved0, dyn,
                 jnp.asarray(start, jnp.int32).reshape(1))
    return out if return_tally else out[0]


def sched_commit_fleet(scores, ok, req, valid, total, denom, reserved0,
                       start, *, fam, dynamic, ext=None, ext_row=None,
                       tile_p: Optional[int] = None,
                       tile_n: Optional[int] = None):
    """Mixed-family fused pass for the switchless scenario fleet — natively
    batched (every operand already carries the lane axis B); always the
    kernel, compiled on a TPU and interpreted elsewhere.

    scores (B, P, N), ok (B, P, N) or None where the fit mask is
    ``isfinite(scores)`` (the base pass's ``base_ok``: the kernel derives
    it and no second (B, P, N) operand is shipped), req (B, P, R), valid
    (B, P), total/denom/reserved0 (B, N, R), start (B,) i32 per-lane
    node-order rotations; ``fam`` / ``dynamic`` / ``ext_row`` static
    per-lane tuples from the dispatch table; ``ext`` (BE, P, N) stacks the
    evaluated prefs of the external (non-fusable) lanes, indexed per-lane
    by ``ext_row``. Returns (node_of (B, P) i32, tally (B, N, R) f32) —
    bitwise-identical, lane-for-lane, to the ``lax.switch`` path's propose
    -> finalize."""
    B = scores.shape[0]
    dynamic = tuple(bool(d) for d in dynamic)
    if all(dynamic):
        mode = "dynamic"
    elif not any(dynamic):
        mode = "static"
    else:
        mode = "both"
    dyn = jnp.asarray([int(d) for d in dynamic], jnp.int32)[:, None]
    if ext_row is None:
        ext_row = (0,) * len(fam)
    return _commit_batched(
        B, scores, ok, req, valid, total, denom, reserved0, dyn,
        start.astype(jnp.int32)[:, None], ext, fam=tuple(fam),
        ext_row=tuple(ext_row), mode=mode, tile_p=tile_p, tile_n=tile_n)


def placement_commit(pref, req, base_ok, valid, total, denom, reserved0,
                     dynamic_bestfit=False, *, use_kernel: bool = False,
                     tile_p: Optional[int] = None,
                     stream_n: Optional[int] = None,
                     return_tally: bool = False):
    """Sequential capacity-checked assignment in priority (row) order.

    pref (P,N) f32 preference scores, req (P,R) f32 requests, base_ok (P,N)
    bool feasibility (None: ``isfinite(pref)``, derived in the kernel, so
    the mask never goes through HBM — the greedy commit, whose ``pref`` is
    the base pass's scores), valid (P,) bool, total (N,R) f32 with inactive nodes
    folded to -1, denom (N,R) f32 best-fit normaliser, reserved0 (N,R) f32
    starting tally -> node_of (P,) i32 (-1 = not placed); with
    ``return_tally=True`` -> (node_of, reserved (N,R) f32), where reserved
    is the scan's final reservation tally (reserved0 + every placed
    request) — the kernel holds it resident across grid steps anyway, and
    incremental accounting (engine/sched) adopts it as the post-commit
    node_reserved instead of re-deriving it with a segment-sum. Bit-identical
    between the Pallas kernel and the pure-jnp reference — the engine
    invariant (no overcommit) is enforced by both. ``dynamic_bestfit`` may
    be a traced bool scalar (per-lane scheduler dispatch in the scenario
    fleet); static True/False specialise the kernel to skip the unused
    score path.

    Where it runs is decided when the call lowers (:func:`repro.kernels.
    on_tpu`): for a TPU always the compiled kernel; for any other platform
    the interpreted kernel if ``use_kernel``, else the jnp reference.

    The kernel is the FAM_SCORES commit with ``pref`` as its scores.
    ``stream_n`` < N streams the node axis in tiles of that many columns
    with a cross-tile argmax carry (the full-cell path); ``tile_p`` sets the
    task rows per grid step (default: see the module doc). Under
    ``jax.vmap`` the kernel path dispatches through a ``custom_vmap`` rule
    to one natively-batched kernel call.

    Not jit-wrapped here: every caller (engine scan, scenario fleet, tests)
    already traces it, and a jit boundary would force the static/traced
    distinction of ``dynamic_bestfit`` into the signature.
    """
    mode, dyn = _mode_and_flag(dynamic_bestfit)
    commit = _make_single(FAM_SCORES, mode, tile_p, stream_n,
                          base_ok is not None)
    args = [pref, req] + ([] if base_ok is None else [base_ok])
    args += [valid, total, denom, reserved0, dyn]

    def kernel(*args):
        return commit(*args, jnp.zeros((1,), jnp.int32))

    def ref(pref, req, *rest):
        ok = jnp.isfinite(pref) if base_ok is None else rest[0]
        valid, total, denom, reserved0, dyn = rest[-5:]
        return placement_commit_ref(pref, req, ok, valid, total, denom,
                                    reserved0, _ref_flag(mode, dyn))

    out = on_tpu(kernel, kernel if use_kernel else ref, *args)
    return out if return_tally else out[0]
