"""The windowed simulation engine — AGOCS's WorkloadGenerator in JAX.

Every 5 sim-seconds (one *window*) AGOCS drains its parser buffers and applies
the collected events to the shared state, then the scheduler(s) under test
react. Here a window is one ``sim_window_step`` call: vectorised scatters
apply the event batch, per-node accounting is maintained, the pluggable
scheduler places pending tasks via the constraint-match kernel, and a stats
row is emitted.

``run_windows`` scans a stack of windows on-device; the host pipeline
(core/pipeline.py) streams stacked windows in while the device computes —
the JAX analogue of the paper's five buffering parser actors.

Accounting (``node_reserved`` / ``node_used``) has two modes:

* **incremental** (``cfg.incremental_accounting``, the default): every pass
  that moves a task on or off a node also applies the matching per-node
  delta — event application scatters O(events) corrections, invalid-placement
  eviction zeroes exactly the dead/overcommitted node rows, and the
  placement-commit kernel emits its on-chip reservation tally as an output.
  The full segment-sum recompute becomes a periodic *resync*
  (``cfg.resync_windows``, driven by core/pipeline.py) that bounds float
  accumulation drift.
* **full recompute** (``incremental_accounting=False``): the pre-delta
  behaviour — three O(max_tasks) segment-sum recomputes per window — kept
  for the equivalence suite and traces that break the pipeline's
  one-update-per-(slot, field-group) window guarantee.

Event-application order inside a window (matches the paper's timestamp
linearisation; the host pipeline guarantees at most one update per (slot,
field-group) per window):
  1. node add / update / attr / remove,
  2. task removals (EVICT/FAIL/FINISH/KILL/LOST),
  3. task adds + requirement/constraint updates,
  4. usage samples,
  5. node-removal evictions (running tasks on dead nodes -> back to pending),
  6. accounting (delta-maintained, or recomputed in full mode),
  7. scheduling (any ``repro.sched`` registry scheduler),
  8. stats.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import SimConfig
from repro.core import stats as stats_mod
from repro.core import trace
from repro.core.events import REMOVE_REASON_EVICT, EventKind, EventWindow
from repro.core.ordered import ordered_scatter_add
from repro.core.state import (SimState, TASK_EMPTY, TASK_PENDING,
                              TASK_RUNNING, init_state)
from repro.kernels.segment_usage.ops import segment_usage


def _masked_slot(mask: jax.Array, slot: jax.Array, overflow: int) -> jax.Array:
    """Route masked-out rows to a dummy overflow index (scatter no-op row)."""
    return jnp.where(mask, slot, overflow)


@trace.scoped(trace.EVENTS)
def apply_node_events(state: SimState, w: EventWindow, cfg: SimConfig
                      ) -> SimState:
    # node events never move accounting: a capacity change or removal leaves
    # placed tasks' contributions in the tallies until evict_invalid reacts
    N = cfg.max_nodes
    kind = w.kind

    def scat(arr, mask, val):
        return arr.at[_masked_slot(mask, w.slot, N)].set(val, mode="drop")

    add = kind == EventKind.ADD_NODE
    upd = kind == EventKind.UPDATE_NODE_RESOURCES
    rem = kind == EventKind.REMOVE_NODE

    node_active = state.node_active
    node_active = node_active.at[_masked_slot(add, w.slot, N)].set(True, mode="drop")
    node_total = scat(state.node_total, add | upd, w.a)

    aat = kind == EventKind.ADD_NODE_ATTR
    rat = kind == EventKind.REMOVE_NODE_ATTR
    attr_rows = _masked_slot(aat | rat, w.slot, N)
    attr_vals = jnp.where(aat, w.attr_val, 0)
    node_attrs = state.node_attrs.at[attr_rows, w.attr_idx].set(
        attr_vals, mode="drop")

    node_active = node_active.at[_masked_slot(rem, w.slot, N)].set(False, mode="drop")
    return state._replace(node_active=node_active, node_total=node_total,
                          node_attrs=node_attrs)


@trace.scoped(trace.EVENTS)
def apply_task_events(state: SimState, w: EventWindow, cfg: SimConfig
                      ) -> SimState:
    T = cfg.max_tasks
    kind = w.kind

    rem = kind == EventKind.REMOVE_TASK
    add = kind == EventKind.ADD_TASK
    upd = kind == EventKind.UPDATE_TASK_REQUIRED
    ucon = kind == EventKind.UPDATE_TASK_CONSTRAINTS
    use = kind == EventKind.UPDATE_TASK_USED

    # pre-mutation gathers (removal counters + incremental deltas)
    old_state_at = state.task_state[w.slot]
    live = old_state_at != TASK_EMPTY

    node_reserved, node_used = state.node_reserved, state.node_used
    if cfg.incremental_accounting:
        ucols = jnp.array(stats_mod.ACCOUNTED_USAGE_COLS)
        was_running = old_state_at == TASK_RUNNING
        ev_node = state.task_node[w.slot]                      # (E,)
        old_req = state.task_req[w.slot]                       # (E, R)
        old_used = state.task_usage[w.slot][:, ucols]          # (E, R)
        # lifecycle rows that end a RUNNING placement give back req + usage
        # (REMOVE, or an ADD reusing a slot that is still running — e.g. the
        # injection pool recycling before its synthesised REMOVE fired)
        gone = (rem | add) & was_running
        node_reserved = ordered_scatter_add(node_reserved, ev_node, gone,
                                            -old_req)
        node_used = ordered_scatter_add(node_used, ev_node, gone, -old_used)
        # requirement updates on running tasks move the reservation
        moved = upd & was_running
        node_reserved = ordered_scatter_add(node_reserved, ev_node, moved,
                                            w.a - old_req)

    # --- removals first (a slot can be freed and re-used next window) ---
    rem_rows = _masked_slot(rem, w.slot, T)
    evicted = rem & live & (w.a[:, 0] == float(REMOVE_REASON_EVICT))
    n_evict = jnp.sum(evicted).astype(jnp.int32)
    n_rem = jnp.sum(rem & live).astype(jnp.int32) - n_evict
    task_state = state.task_state.at[rem_rows].set(TASK_EMPTY, mode="drop")
    task_node = state.task_node.at[rem_rows].set(-1, mode="drop")

    # --- adds / updates ---
    task_state = task_state.at[_masked_slot(add, w.slot, T)].set(
        TASK_PENDING, mode="drop")
    task_node = task_node.at[_masked_slot(add, w.slot, T)].set(-1, mode="drop")
    task_req = state.task_req.at[_masked_slot(add | upd, w.slot, T)].set(
        w.a, mode="drop")
    task_prio = state.task_prio.at[_masked_slot(add | upd, w.slot, T)].set(
        w.prio, mode="drop")
    task_job = state.task_job.at[_masked_slot(add, w.slot, T)].set(
        w.job, mode="drop")
    task_constraints = state.task_constraints.at[
        _masked_slot(add | ucon, w.slot, T)].set(w.constraints, mode="drop")

    # --- usage samples ---
    if cfg.incremental_accounting:
        # a sample moves node_used only if the task still runs after the
        # lifecycle rows above (its own REMOVE in this window wins: the full
        # recompute would see an EMPTY slot, and `gone` already debited the
        # whole old contribution)
        samp = use & (task_state[w.slot] == TASK_RUNNING)
        node_used = ordered_scatter_add(node_used, ev_node, samp,
                                        w.u[:, ucols] - old_used)
    task_usage = state.task_usage.at[_masked_slot(use, w.slot, T)].set(
        w.u, mode="drop")

    return state._replace(
        task_state=task_state, task_node=task_node, task_req=task_req,
        task_prio=task_prio, task_job=task_job,
        task_constraints=task_constraints, task_usage=task_usage,
        node_reserved=node_reserved, node_used=node_used,
        completions=state.completions + n_rem,
        evictions=state.evictions + n_evict)


@trace.scoped(trace.EVENTS)
def evict_invalid(state: SimState, cfg: SimConfig) -> SimState:
    """Evict running tasks whose placement became invalid:

    * the node went inactive (maintenance/removal — paper §III bullet 4), or
    * a capacity UPDATE shrank the node below its current reservation
      (GCD machine updates; Google's scheduler would evict — so do we).

    Evicted tasks go back to pending, mirroring GCD's EVICT-then-clone cycle.
    Requires node_reserved to be current (incremental mode maintains it;
    full mode must recompute_accounting first). Under incremental accounting
    the per-node tallies are corrected here too: every running task on a
    dead/overcommitted node is evicted, so those node rows drop to exactly
    zero and all other rows are untouched — an O(max_nodes) select instead
    of a segment-sum pass.
    """
    node_idx = jnp.maximum(state.task_node, 0)
    dead = ~state.node_active[node_idx]
    over_nodes = (state.node_reserved > state.node_total + 1e-6).any(axis=1)
    bad = (state.task_state == TASK_RUNNING) & (dead | over_nodes[node_idx])
    n_evict = jnp.sum(bad).astype(jnp.int32)
    state = state._replace(
        task_state=jnp.where(bad, TASK_PENDING, state.task_state),
        task_node=jnp.where(bad, -1, state.task_node),
        evictions=state.evictions + n_evict)
    if cfg.incremental_accounting:
        bad_node = (~state.node_active | over_nodes)[:, None]
        state = state._replace(
            node_reserved=jnp.where(bad_node, 0.0, state.node_reserved),
            node_used=jnp.where(bad_node, 0.0, state.node_used))
    return state


@trace.scoped(trace.EVENTS)
def recompute_accounting(state: SimState, cfg: SimConfig) -> SimState:
    """node_reserved / node_used from the task table (segment usage).

    The whole inner loop in full-recompute mode; the periodic *resync* path
    (and the oracle the equivalence tests compare against) under incremental
    accounting. Later fits compare against these tallies, so they take the
    ordered reference sum under ``use_kernels`` too: the segment-usage
    kernel's matmul sums in its own order (``kernels/segment_usage``).
    """
    running = state.task_state == TASK_RUNNING
    reserved = segment_usage(state.task_node, state.task_req, running,
                             cfg.max_nodes)
    # align usage columns with the (cpu, memory, disk) resource axes
    used_cols = state.task_usage[:, jnp.array(stats_mod.ACCOUNTED_USAGE_COLS)]
    used = segment_usage(state.task_node, used_cols, running, cfg.max_nodes)
    return state._replace(node_reserved=reserved, node_used=used)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def resync_accounting_jit(state: SimState, cfg: SimConfig) -> SimState:
    """Donating jit of the full recompute — the drivers' periodic drift
    resync under incremental accounting (see ``SimConfig.resync_windows``)."""
    return recompute_accounting(state, cfg)


def make_window_advance(cfg: SimConfig, scheduler_fn: Callable
                        ) -> Callable[[SimState, EventWindow, jax.Array],
                                      SimState]:
    """Build the stats-free single-window transition (state in, state out).

    The stats row is deliberately NOT part of this function: under
    ``cfg.stats_stride > 1`` the scan advances k windows per emitted row, so
    skipped windows pay zero stats cost (counters are cumulative in the
    state, so nothing is lost)."""

    def advance(state: SimState, w: EventWindow, rng: jax.Array) -> SimState:
        state = apply_node_events(state, w, cfg)
        state = apply_task_events(state, w, cfg)
        if not cfg.incremental_accounting:
            state = recompute_accounting(state, cfg)
        state = evict_invalid(state, cfg)
        if not cfg.incremental_accounting:
            state = recompute_accounting(state, cfg)
        state = scheduler_fn(state, cfg, rng)
        if not cfg.incremental_accounting:
            state = recompute_accounting(state, cfg)
        return state._replace(window=state.window + 1)

    return advance


def make_window_step(cfg: SimConfig, scheduler_fn: Callable
                     ) -> Callable[[SimState, EventWindow, jax.Array],
                                   Tuple[SimState, Dict[str, jax.Array]]]:
    """Build the jit-able single-window transition (advance + stats row)."""
    advance = make_window_advance(cfg, scheduler_fn)

    def sim_window_step(state: SimState, w: EventWindow, rng: jax.Array
                        ) -> Tuple[SimState, Dict[str, jax.Array]]:
        state = advance(state, w, rng)
        return state, stats_mod.window_stats(state, cfg)

    return sim_window_step


def strided_chunks(tree, W: int, stride: int):
    """Split a (W, ...) pytree into ((M, k, ...) head, (r, ...) tail | None)
    with M = W // k full chunks — the shared chunking of the strided-stats
    scans (engine + scenario fleet), so their row cadence cannot drift."""
    M, r = divmod(W, stride)
    head = None
    if M:
        head = jax.tree.map(
            lambda x: x[:M * stride].reshape((M, stride) + x.shape[1:]), tree)
    tail = jax.tree.map(lambda x: x[M * stride:], tree) if r else None
    return head, tail


def scan_strided(chunk: Callable, state, tree, W: int, stride: int):
    """Scan ``chunk`` (state, (k, ...) slice -> (state, row)) over the W
    leading items of ``tree`` in stride-sized chunks, the non-divisible tail
    as ONE final partial chunk, concatenating the emitted rows — the single
    implementation of the strided-stats cadence, shared by
    ``run_windows`` and the scenario fleet's ``run_scenarios``. Requires
    W > 0 (callers route W == 0 through their stride-1 empty scan)."""
    assert W > 0, "scan_strided needs at least one item"
    head, tail = strided_chunks(tree, W, stride)
    rows = []
    if head is not None:
        state, r_head = jax.lax.scan(chunk, state, head)
        rows.append(r_head)
    if tail is not None:
        state, r_tail = chunk(state, tail)
        rows.append(jax.tree.map(lambda x: x[None], r_tail))
    stats = (rows[0] if len(rows) == 1 else
             jax.tree.map(lambda a, b: jnp.concatenate([a, b]), *rows))
    return state, stats


def run_windows(state: SimState, windows: EventWindow, cfg: SimConfig,
                scheduler_fn: Callable, seed: int = 0
                ) -> Tuple[SimState, Dict[str, jax.Array]]:
    """Scan the engine over stacked windows (W leading dim on every field).

    With ``cfg.stats_stride == k > 1`` the scan emits one stats row per k
    windows — row j is computed on the state after window (j+1)*k, i.e.
    exactly every k-th row of the stride-1 scan (cumulative counters make
    the skipped windows' events visible in the next emitted row).  A
    non-divisible tail still emits one final partial row, so the last row
    always reflects the final state.  RNG keys are derived per *window*
    (identically to stride 1), so the final state is bitwise independent of
    the stride.
    """
    advance = make_window_advance(cfg, scheduler_fn)
    W = windows.kind.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), W)
    stride = cfg.stats_stride

    if stride == 1 or W == 0:     # W == 0: the empty scan handles it cleanly
        def body(s, xs):
            w, k = xs
            s = advance(s, w, k)
            return s, stats_mod.window_stats(s, cfg)

        return jax.lax.scan(body, state, (windows, keys))

    def chunk(s, xs):
        s, _ = jax.lax.scan(lambda s2, x2: (advance(s2, *x2), None), s, xs)
        return s, stats_mod.window_stats(s, cfg)

    return scan_strided(chunk, state, (windows, keys), W, stride)


@functools.partial(jax.jit, static_argnames=("cfg", "scheduler_name"),
                   donate_argnames=("state",))
def run_windows_jit(state: SimState, windows: EventWindow, cfg: SimConfig,
                    scheduler_name: str, seed: int = 0):
    """Donating entry point: the (max_tasks, ...) task tables of ``state``
    are reused for the output instead of double-buffered between batches —
    callers must thread the returned state and not touch the argument again
    (the drive loop in core/pipeline.py does exactly that)."""
    from repro.sched import get_scheduler
    return run_windows(state, windows, cfg, get_scheduler(scheduler_name),
                       seed)
