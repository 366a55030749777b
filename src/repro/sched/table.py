"""Proposal table — switchless fleet scheduler dispatch.

The scenario fleet used to dispatch schedulers with a vmapped ``lax.switch``
over per-lane proposal branches. Under vmap a switch executes EVERY branch
on EVERY lane and selects afterwards, so one simulated-annealing lane taxed
the whole fleet with the SA loop. The proposal table removes the switch:

* each registered scheduler may supply a :class:`TableForm` — a
  parameterised score transform ``transform(cfg, ctx, rng, params) ->
  pref (P, N)`` over the shared ``base_pass`` output (:class:`SchedContext`);
* :func:`snapshot_dispatch` freezes the registry into an immutable
  :class:`DispatchTable` at fleet build time (plugins registered later
  cannot retarget a running fleet's scheduler indices);
* :func:`make_switchless_dispatch` statically groups the fleet's lanes by
  *distinct* (transform, params) family and evaluates each family once over
  only its lane sub-batch — a greedy lane never pays a metaheuristic's loop
  cost — then commits all lanes in one batched finaliser call. Where the
  program lowers for a TPU (and on any platform under ``cfg.use_kernels``,
  interpreted off the TPU) the commit is the fused ``sched_commit_fleet``
  kernel: score-derived preference tiles and the fit mask are generated
  *inside* the Pallas grid, so neither the (B, P, N) preference tensor nor
  a second mask operand is shipped; elsewhere it is the jnp reference, the
  lanes' preference stack through one vmapped ``finalize``.

Under a device mesh the fleet cuts its static lane map into the chips'
local maps and builds one switchless dispatch per distinct map; each chip
picks its own by shard index, a switch that is not vmapped and so runs one
branch (``scenarios.batch.run_scenarios_sharded``).

Schedulers without a table form (opaque plugins) are still first-class:
``DispatchTable.switchless`` is False the moment any fleet lane names one,
and the fleet falls back to the original ``lax.switch`` path — bitwise the
same trajectories, just slower (see ``scenarios.batch``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.kernels import on_tpu
from repro.kernels.placement_commit.kernel import (FAM_EXTERNAL,
                                                   FAM_NODE_ORDER,
                                                   FAM_SCORES)
from repro.kernels.placement_commit.ops import sched_commit_fleet
from repro.sched.base import base_pass
from repro.sched.commit import apply_commit, commit_operands, finalize


class SchedContext(NamedTuple):
    """Everything a table-form transform may read: the shared base-pass
    output plus the state slices the built-in proposals touch. One gather
    (``req``) replaces arbitrary state access so the fleet can batch a
    context across lanes with plain ``tree.map`` indexing."""
    idx: jax.Array            # (P,) pending task slots, priority-descending
    valid: jax.Array          # (P,) bool — slot actually pending
    base_ok: jax.Array        # (P, N) bool constraint feasibility
    scores: jax.Array         # (P, N) f32 best-fit scores (-inf infeasible)
    req: jax.Array            # (P, R) f32 gathered task requests
    node_total: jax.Array     # (N, R) f32 capacities
    node_reserved: jax.Array  # (N, R) f32 running reservations
    node_active: jax.Array    # (N,) bool
    window: jax.Array         # () i32 current window index


class TableForm(NamedTuple):
    """A scheduler's proposal-table registration.

    transform: ``(cfg, ctx, rng, params) -> pref (P, N)`` — pure JAX over
    the :class:`SchedContext`; lanes sharing ``(transform, params)`` are
    evaluated together, once. params: static floats baked into the trace
    (hashable — the table is a jit static argument). fused: the
    ``kernels.placement_commit`` family code the fused kernel derives this
    family's preferences from in-grid (``FAM_SCORES`` / ``FAM_NODE_ORDER``);
    ``FAM_EXTERNAL`` means the transform's output must be materialised and
    handed to the kernel as an external preference operand."""
    transform: Callable
    params: Tuple[float, ...] = ()
    fused: int = FAM_EXTERNAL


@dataclasses.dataclass(frozen=True)
class DispatchTable:
    """Immutable snapshot of the registry rows a fleet dispatches over.

    Column i describes scheduler ``names[i]`` — the fleet's per-lane
    ``sched_idx`` knobs index into exactly this tuple order (built by
    ``spec.build_knobs`` from the same name tuple). Hashable, so it rides
    the jit cache as a static argument: re-snapshotting an unchanged
    registry reuses the compiled program."""
    names: Tuple[str, ...]
    proposers: Tuple[Callable, ...]
    dynamic: Tuple[bool, ...]
    forms: Tuple[Optional[TableForm], ...]

    @property
    def switchless(self) -> bool:
        """True when every scheduler in the table has a table form — the
        precondition for switchless dispatch."""
        return all(f is not None for f in self.forms)


def context_from_state(state, idx, valid, base_ok, scores) -> SchedContext:
    """Assemble the transform context for one lane's state."""
    return SchedContext(idx=idx, valid=valid, base_ok=base_ok, scores=scores,
                        req=state.task_req[idx],
                        node_total=state.node_total,
                        node_reserved=state.node_reserved,
                        node_active=state.node_active,
                        window=state.window)


# --- built-in transform families ------------------------------------------

def tf_scores(cfg, ctx: SchedContext, rng, params):
    """Greedy/best-fit family: the base-pass score matrix IS the preference
    (fused in-kernel as FAM_SCORES — zero derivation cost)."""
    return ctx.scores


def tf_node_order(cfg, ctx: SchedContext, rng, params):
    """Node-order family: rank nodes by ``-((index - start) % N)`` where
    ``start = (window * rot) % N`` — first-fit at rot=0, round-robin at the
    registered rotation stride. Bitwise-identical to the classic proposals
    (int32 -> f32 casts are exact below 2**24 nodes)."""
    rot = int(params[0])
    start = (ctx.window * rot) % cfg.max_nodes
    order = (jnp.arange(cfg.max_nodes) - start) % cfg.max_nodes
    return jnp.broadcast_to(-order.astype(jnp.float32)[None, :],
                            ctx.base_ok.shape)


def tf_random(cfg, ctx: SchedContext, rng, params):
    """Uniform random preference draw (rng-derived — external family)."""
    return jax.random.uniform(rng, ctx.base_ok.shape)


def make_switchless_dispatch(cfg, table: DispatchTable,
                             lane_scheds: Tuple[int, ...]):
    """Build the fleet's batched switchless scheduler pass.

    lane_scheds: the STATIC per-lane scheduler index (lane i runs
    ``table.names[lane_scheds[i]]``) — exactly the values the knobs'
    ``sched_idx`` column carries at runtime; freezing them here is what
    removes the switch. Returns ``dispatch(state_B, rng) -> state_B`` over
    the (B, ...)-stacked fleet state; requires ``table.switchless``.

    Grouping: lanes sharing a (transform, params) family are evaluated in
    one vmapped transform call over their sub-batch — distinct families run
    once each, over only the lanes that want them. The per-lane preference
    stack is reassembled by a static inverse permutation (a gather, not a
    switch). Commit: on a TPU (or under ``cfg.use_kernels``) the fused
    ``sched_commit_fleet`` kernel commits all lanes with score/node-order
    preferences derived in-grid; otherwise one vmapped finalize with
    per-lane dynamic_bestfit flags.
    """
    assert table.switchless, "opaque scheduler in a switchless dispatch"
    B = len(lane_scheds)
    forms = [table.forms[s] for s in lane_scheds]
    dynamic = tuple(bool(table.dynamic[s]) for s in lane_scheds)

    # static lane grouping by distinct proposal family
    groups = {}               # (transform, params, fused) -> [lane, ...]
    for lane, f in enumerate(forms):
        groups.setdefault(f, []).append(lane)

    tile_p = cfg.commit_tile_p or None
    tile_n = cfg.commit_tile_n or None

    def eval_family(form, lanes, ctx, rng):
        """Run one family's transform over its lane sub-batch only."""
        sub = ctx
        if lanes != list(range(B)):
            sub = jax.tree.map(lambda x: x[jnp.asarray(lanes)], ctx)
        return jax.vmap(
            lambda c: form.transform(cfg, c, rng, form.params))(sub)

    @trace.scoped(trace.PROPOSE)
    def external_prefs(ctx, rng):
        """The fused path's materialised preferences: the external
        families' rows stacked, and each lane's row in the stack."""
        ext_parts, ext_row, n_rows = [], [0] * B, 0
        for form, lanes in groups.items():
            if form.fused != FAM_EXTERNAL:
                continue
            ext_parts.append(eval_family(form, lanes, ctx, rng))
            for j, lane in enumerate(lanes):
                ext_row[lane] = n_rows + j
            n_rows += len(lanes)
        ext = jnp.concatenate(ext_parts, axis=0) if ext_parts else None
        return ext, tuple(ext_row)

    @trace.scoped(trace.PROPOSE)
    def lane_prefs(ctx, rng):
        """The reference path's (B, P, N) preference stack: each family
        over its lanes, reassembled by static inverse permutation."""
        order, parts = [], []
        for form, lanes in groups.items():
            order.extend(lanes)
            parts.append(eval_family(form, lanes, ctx, rng))
        pref_B = jnp.concatenate(parts, axis=0)
        inv = sorted(range(B), key=order.__getitem__)
        if inv != list(range(B)):
            pref_B = pref_B[jnp.asarray(inv)]
        return pref_B

    def fused(state_B, ctx, rng):
        """The kernel commit: only external families materialise a
        preference; scores / node-order lanes are derived inside the kernel
        grid, and so is the fit mask (``base_ok`` is ``isfinite(scores)``,
        as ``base_pass`` defines it)."""
        fam = tuple(f.fused for f in forms)
        rots = [int(f.params[0]) if f.fused == FAM_NODE_ORDER else 0
                for f in forms]
        start_B = (state_B.window * jnp.asarray(rots, jnp.int32)) \
            % cfg.max_nodes
        ext, ext_row = external_prefs(ctx, rng)
        with trace.scope(trace.COMMIT):
            total_B, denom_B, _ = jax.vmap(
                lambda s, i: commit_operands(s, cfg, i))(state_B, ctx.idx)
            node_of, tally = sched_commit_fleet(
                ctx.scores, None, ctx.req, ctx.valid, total_B, denom_B,
                state_B.node_reserved, start_B, fam=fam, dynamic=dynamic,
                ext=ext, ext_row=ext_row, tile_p=tile_p, tile_n=tile_n)
            return jax.vmap(
                lambda s, i, n, t: apply_commit(s, cfg, i, n, t)
            )(state_B, ctx.idx, node_of, tally)

    def reference(state_B, ctx, rng):
        """The jnp commit: the lanes' preference stack through one vmapped
        finalize (traced per-lane dyn flags — bitwise-equal to the static
        selection)."""
        pref_B = lane_prefs(ctx, rng)
        dyn_B = jnp.asarray(dynamic)
        return jax.vmap(
            lambda s, i, v, ok, p, d: finalize(s, cfg, i, v, ok, p,
                                               dynamic_bestfit=d)
        )(state_B, ctx.idx, ctx.valid, ctx.base_ok, pref_B, dyn_B)

    def dispatch(state_B, rng):
        idx, valid, base_ok, scores = jax.vmap(
            base_pass, in_axes=(0, None))(state_B, cfg)
        req = jax.vmap(lambda tr, i: tr[i])(state_B.task_req, idx)
        ctx = SchedContext(idx=idx, valid=valid, base_ok=base_ok,
                           scores=scores, req=req,
                           node_total=state_B.node_total,
                           node_reserved=state_B.node_reserved,
                           node_active=state_B.node_active,
                           window=state_B.window)
        return on_tpu(fused, fused if cfg.use_kernels else reference,
                      state_B, ctx, rng)

    return dispatch
