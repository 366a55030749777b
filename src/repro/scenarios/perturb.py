"""Pure-JAX per-scenario perturbations of the shared event stream.

All scenarios in a fleet consume the SAME parsed ``EventWindow`` tensors (the
trace is parsed once, on the host); divergence is injected on-device by these
transforms, which are vmapped over the scenario axis in batch.py. Every
transform is:

* **deterministic** — membership decisions hash the event's slot (and, for
  per-window effects, the window counter) through a splitmix32-style mixer,
  so scenario B=0 today picks the same outage nodes as tomorrow's rerun;
* **an exact identity at the default knob values** — required for the
  bit-identity guarantee that lane 0 of a batched run equals the
  single-trajectory engine (tested in tests/test_scenarios.py);
* **shape-preserving** — events are masked to ``PAD`` rather than removed,
  so fixed shapes (and therefore one compiled program) cover all scenarios.

Semantics of the knobs (see spec.ScenarioSpec for the user-facing docs):

* outage: node slots with hash < frac never come up — their ADD_NODE /
  UPDATE_NODE_RESOURCES events are padded out. Tasks scheduled elsewhere are
  untouched; nothing ever runs on an outage node.
* capacity: ADD/UPDATE_NODE payloads are scaled, so the whole cell is
  uniformly bigger or smaller.
* arrival thinning (rate < 1): every task event (ADD and its follow-ups) for
  a thinned slot is padded out — the task never existed in this world.
* arrival amplification (rate > 1): extra SUBMIT events are *synthesised*
  into the window's reserved slot pool (``cfg.inject_slots`` rows at the
  tail of every packed window, kept PAD by the host packer). Each injected
  task is cloned from a deterministically sampled surviving real arrival —
  same requirements/priority/constraints, fresh task id from the reserved
  pool [cfg.real_task_slots, cfg.max_tasks) — so amplification genuinely
  adds schedulable load instead of the old removal-suppression proxy. The
  per-window injected count is round((rate - 1) * n_arrivals), capped at
  inject_slots; injected ids wrap modulo the pool, so a very long run
  recycles (re-submits) its oldest injected tasks rather than overflowing.
* priority surge: a hashed fraction of arriving tasks get surge_prio.
* usage inflation: UPDATE_TASK_USED payloads are scaled.
* eviction storm: each window, a hashed fraction of *running* tasks — up
  to ``cfg.resolved_storm_max_victims``, a bounded-storm cap shared by
  both accounting modes — is forcibly evicted back to pending (applied to
  state, not events); under incremental accounting the debit rides a
  victim-compacted O(V) scatter (see ``storm_debit``).
* injected-task lifecycles: amplification clones get a synthesised REMOVE
  after a deterministic per-slot lifetime (``expire_injected``, applied to
  state like the storm), counted as completions — amplified lanes churn
  instead of pinning their pool slots until recycling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import SimConfig
from repro.core import trace
from repro.core.events import EventKind, EventWindow
from repro.core.ordered import ordered_scatter_add
from repro.core.state import SimState, TASK_EMPTY, TASK_PENDING, TASK_RUNNING
from repro.core.stats import ACCOUNTED_USAGE_COLS
from repro.kernels.segment_usage.ops import segment_usage
from repro.scenarios.spec import ScenarioKnobs

# distinct per-knob salt offsets so one slot's fates are independent draws
_SALT_OUTAGE = 0x1
_SALT_THIN = 0x2
_SALT_SURGE = 0x4
_SALT_STORM = 0x5
_SALT_INJECT = 0x6
_SALT_LIFETIME = 0x7


def hash01(x: jax.Array, salt: int, cfg: SimConfig) -> jax.Array:
    """Deterministic int -> [0, 1) float32 (splitmix32-style finalizer)."""
    h = x.astype(jnp.uint32) ^ jnp.uint32((cfg.scenario_salt + salt) & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h.astype(jnp.float32) * jnp.float32(2.0 ** -32)


_TASK_KINDS = (EventKind.ADD_TASK, EventKind.UPDATE_TASK_REQUIRED,
               EventKind.UPDATE_TASK_USED, EventKind.UPDATE_TASK_CONSTRAINTS,
               EventKind.REMOVE_TASK)


@trace.scoped(trace.EVENTS)
def perturb_window(w: EventWindow, k: ScenarioKnobs, cfg: SimConfig,
                   window: jax.Array = None) -> EventWindow:
    """Apply one scenario's event-stream transforms to one window.

    ``k`` holds per-scenario *scalars* here — batch.py vmaps this function
    over the leading (B,) axis of ScenarioKnobs with ``w`` broadcast.
    ``window`` is the scalar window counter (state.window), which seeds the
    per-window injection draws; it defaults to 0 for unit tests.
    """
    kind = w.kind
    is_add_node = kind == EventKind.ADD_NODE
    is_upd_node = kind == EventKind.UPDATE_NODE_RESOURCES
    node_cap_ev = is_add_node | is_upd_node
    is_add_task = kind == EventKind.ADD_TASK
    is_task_ev = jnp.zeros_like(is_add_task)
    for tk in _TASK_KINDS:
        is_task_ev = is_task_ev | (kind == tk)

    # --- node outage: hashed node slots never come up ---
    outage_hit = hash01(w.slot, _SALT_OUTAGE, cfg) < k.outage_frac
    drop = node_cap_ev & outage_hit

    # --- capacity scaling on node capacity payloads ---
    a = jnp.where(node_cap_ev[:, None], w.a * k.capacity_scale, w.a)

    # --- arrival thinning: the whole task (and its follow-up events) goes ---
    thin_p = 1.0 - jnp.minimum(k.arrival_rate, 1.0)
    thinned_slot = hash01(w.slot, _SALT_THIN, cfg) < thin_p
    drop = drop | (is_task_ev & thinned_slot)

    kind = jnp.where(drop, jnp.int8(EventKind.PAD), kind)

    # --- priority surge on surviving arrivals AND requirement updates (an
    # UPDATE_TASK_REQUIRED rewrites task_prio, so it must stay surged too —
    # the per-slot hash keeps the decision consistent across a task's events)
    is_prio_ev = is_add_task | (w.kind == EventKind.UPDATE_TASK_REQUIRED)
    surged = (is_prio_ev & ~drop &
              (hash01(w.slot, _SALT_SURGE, cfg) < k.surge_frac))
    prio = jnp.where(surged, k.surge_prio, w.prio)

    # --- usage inflation ---
    is_use = w.kind == EventKind.UPDATE_TASK_USED
    u = jnp.where(is_use[:, None], w.u * k.usage_scale, w.u)

    w = w._replace(kind=kind, a=a, prio=prio, u=u)

    # --- arrival amplification (rate > 1): synthesise SUBMITs into the
    # reserved slot pool, cloned from the post-perturbation stream (so
    # injected tasks inherit surged priorities / scaled payloads)
    if cfg.inject_slots:
        if window is None:
            window = jnp.int32(0)
        w = inject_arrivals(w, k, cfg, window)
    return w


def inject_arrivals(w: EventWindow, k: ScenarioKnobs, cfg: SimConfig,
                    window: jax.Array) -> EventWindow:
    """Fill the window's reserved tail rows with synthesised SUBMIT events.

    round((rate - 1) * n_arrivals) clones (capped at ``cfg.inject_slots``)
    of deterministically sampled surviving real arrivals are written into
    rows [E - inject_slots, E), with fresh task ids drawn round-robin from
    the reserved pool [cfg.real_task_slots, max_tasks). At rate <= 1 (or
    with no surviving arrivals) every reserved row is written back with its
    original bits, keeping the lane-0 identity guarantee exact.
    """
    S = cfg.inject_slots
    E = w.kind.shape[0]
    rows = jnp.arange(E - S, E)
    j = jnp.arange(S, dtype=jnp.uint32)

    # surviving real arrivals are the cloning sources (reserved rows are
    # still PAD at this point, so they can't self-select)
    arrive = w.kind == jnp.int8(EventKind.ADD_TASK)
    n_arr = jnp.sum(arrive).astype(jnp.int32)
    n_inj = jnp.clip(
        jnp.round((k.arrival_rate - 1.0) * n_arr.astype(jnp.float32))
        .astype(jnp.int32), 0, S)
    active = (j.astype(jnp.int32) < n_inj) & (n_arr > 0)

    # pick the u*n_arr-th surviving arrival for each reserved row — the draw
    # mixes the window counter with the row index, so reruns are reproducible
    # and different windows sample different sources
    mix = (window.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
           + j * jnp.uint32(0x85EBCA77))
    pick = jnp.floor(hash01(mix, _SALT_INJECT, cfg)
                     * n_arr.astype(jnp.float32)).astype(jnp.int32)
    pick = jnp.clip(pick, 0, jnp.maximum(n_arr - 1, 0))
    src = jnp.clip(jnp.searchsorted(jnp.cumsum(arrive.astype(jnp.int32)),
                                    pick + 1), 0, E - 1)

    # fresh ids round-robin through the reserved pool: distinct within a
    # window (pool >= S is validated by SimConfig), wrapping across windows
    pool = cfg.resolved_inject_task_slots
    islot = (cfg.real_task_slots
             + (window * S + jnp.arange(S, dtype=jnp.int32)) % pool)

    def put(col, new):
        cur = col[rows]
        mask = active.reshape((S,) + (1,) * (cur.ndim - 1))
        return col.at[rows].set(jnp.where(mask, new, cur))

    return w._replace(
        kind=put(w.kind, jnp.int8(EventKind.ADD_TASK)),
        slot=put(w.slot, islot),
        a=put(w.a, w.a[src]),
        u=put(w.u, w.u[src]),
        prio=put(w.prio, w.prio[src]),
        job=put(w.job, w.job[src]),
        constraints=put(w.constraints, w.constraints[src]),
        attr_idx=put(w.attr_idx, w.attr_idx[src]),
        attr_val=put(w.attr_val, w.attr_val[src]),
        t_off=put(w.t_off, w.t_off[src]),
    )


@trace.scoped(trace.EVENTS)
def expire_injected(state: SimState, k: ScenarioKnobs, cfg: SimConfig
                    ) -> SimState:
    """Injected-task lifecycles: synthesised REMOVEs after a sampled duration.

    Trace tasks carry their own REMOVE events, but injected clones have no
    future in the stream — without this pass they run until their pool slot
    recycles, so amplified lanes add load that never churns. Each pool slot
    ``q`` gets a deterministic lifetime ``dur(q)`` in ``[1, L-1]`` windows
    (L = floor(pool / S), the slot-recycle period, so a REMOVE always fires
    before its slot is re-injected): the clone injected into ``q`` at window
    ``w0`` is removed — counted as a completion, exactly like a trace REMOVE
    — at window ``w0 + dur(q)``. Membership is closed-form (slot q was an
    injection target at w0 iff ``(q - w0*S) mod pool < S``) and the pass
    only ever touches *live* slots in the reserved pool, so lanes with
    ``arrival_rate <= 1`` (no injections, empty pool) are a bitwise no-op —
    the fleet's lane-0 identity guarantee survives.
    """
    S = cfg.inject_slots
    pool = cfg.resolved_inject_task_slots
    L = pool // S if S else 0
    if L <= 1:      # pool recycles immediately — no room for a lifetime
        return state
    q = jnp.arange(pool, dtype=jnp.int32)
    dur = 1 + jnp.floor(hash01(q.astype(jnp.uint32), _SALT_LIFETIME, cfg)
                        * (L - 1)).astype(jnp.int32)
    dur = jnp.clip(dur, 1, L - 1)
    w0 = state.window - dur                       # candidate injection window
    injected_then = jnp.mod(q - w0 * S, pool) < S
    rows = cfg.real_task_slots + q
    live = state.task_state[rows] != TASK_EMPTY
    victim = injected_then & (w0 >= 0) & live & (k.arrival_rate > 1.0)
    n = jnp.sum(victim).astype(jnp.int32)
    was_running = victim & (state.task_state[rows] == TASK_RUNNING)
    old_node = state.task_node[rows]
    task_state = state.task_state.at[rows].set(
        jnp.where(victim, jnp.int8(TASK_EMPTY), state.task_state[rows]))
    task_node = state.task_node.at[rows].set(
        jnp.where(victim, -1, state.task_node[rows]))
    state = state._replace(task_state=task_state, task_node=task_node,
                           completions=state.completions + n)
    if cfg.incremental_accounting:
        # debit removed *running* clones from their nodes — an O(pool)
        # scatter (the pool is small), matching what a full recompute of the
        # post-expiry table would drop. Lanes without victims subtract
        # exact zeros, so the lane-0 bitwise identity survives.
        ucols = jnp.array(ACCOUNTED_USAGE_COLS)
        state = state._replace(
            node_reserved=ordered_scatter_add(
                state.node_reserved, old_node, was_running,
                -state.task_req[rows]),
            node_used=ordered_scatter_add(
                state.node_used, old_node, was_running,
                -state.task_usage[rows][:, ucols]))
    return state


def storm_victims(state: SimState, k: ScenarioKnobs, cfg: SimConfig):
    """This window's eviction-storm victims: ((T,) bool mask, running
    victim-count cumsum or None).

    The draw mixes the window counter with the task slot so different
    windows hit different victims, yet reruns are reproducible.  When
    ``cfg.resolved_storm_max_victims < max_tasks`` the mask is capped to
    the first V hits in slot order (a *bounded* storm) — the cap is part of
    the storm's semantics, applied identically under both accounting modes,
    so incremental and full runs always evict the same set.  The cumsum the
    cap is derived from is returned too: it doubles as the victim
    compactor's rank index in :func:`storm_debit` (uncapped configs skip it
    and return None).
    """
    T = cfg.max_tasks
    slots = jnp.arange(T, dtype=jnp.uint32)
    mix = (slots * jnp.uint32(0x9E3779B1)
           + state.window.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    hit = hash01(mix, _SALT_STORM, cfg) < k.storm_frac
    victim = (state.task_state == TASK_RUNNING) & hit
    if cfg.resolved_storm_max_victims >= T:
        return victim, None
    cum = jnp.cumsum(victim.astype(jnp.int32))
    return victim & (cum <= cfg.resolved_storm_max_victims), cum


def storm_debit(state: SimState, victim: jax.Array, cum, cfg: SimConfig
                ) -> SimState:
    """Debit the storm victims' req/usage contributions from the node
    tallies (incremental accounting only).

    With ``resolved_storm_max_victims < max_tasks`` the victim rows are
    *compacted* first: ``searchsorted`` over the victim cumsum finds the
    j-th victim's row for every compact slot j < V (a vectorised binary
    search — crucially NOT a max_tasks-length scatter, whose per-row cost
    is what makes the legacy masked segment-sum expensive), and the debit
    becomes an O(V) gather + delta scatter.  Uncapped configs keep the
    legacy fused masked segment-sum (the equivalence oracle for the
    compacted path — see tests/test_window_stats.py).
    """
    T = cfg.max_tasks
    V = cfg.resolved_storm_max_victims
    ucols = jnp.array(ACCOUNTED_USAGE_COLS)
    if cum is None:
        # legacy: one fused masked segment-sum (req + usage debit together —
        # the scatter cost is dominated by the T-row walk, not value width)
        R = state.task_req.shape[1]
        vals = jnp.concatenate(
            [state.task_req, state.task_usage[:, ucols]], axis=1)
        # the ordered reference under use_kernels too: later fits read
        # these tallies (see engine.recompute_accounting)
        sub = segment_usage(state.task_node, vals, victim, cfg.max_nodes)
        return state._replace(node_reserved=state.node_reserved - sub[:, :R],
                              node_used=state.node_used - sub[:, R:])
    # victim compaction: the (j+1)-th victim lives at the first row whose
    # cumsum reaches j+1 (the inject_arrivals sampling trick); slots past
    # the victim count are masked and their scatter rows dropped
    vrows = jnp.searchsorted(cum, jnp.arange(1, V + 1, dtype=cum.dtype))
    valid = jnp.arange(V) < jnp.minimum(cum[-1], V)
    rows = jnp.minimum(vrows, T - 1)
    vnode = state.task_node[rows]
    return state._replace(
        node_reserved=ordered_scatter_add(state.node_reserved, vnode, valid,
                                          -state.task_req[rows]),
        node_used=ordered_scatter_add(state.node_used, vnode, valid,
                                      -state.task_usage[rows][:, ucols]))


@trace.scoped(trace.EVENTS)
def storm_evict(state: SimState, k: ScenarioKnobs, cfg: SimConfig) -> SimState:
    """Per-window eviction storm: force a hashed fraction of running tasks
    (up to ``cfg.resolved_storm_max_victims``) back to pending.

    Under incremental accounting the victims' contributions are debited via
    :func:`storm_debit` (victim-compacted O(V) delta scatter by default,
    masked segment-sum when uncapped); storm-free fleets skip this entirely
    via the ``has_storm`` static flag in batch.py.
    """
    victim, cum = storm_victims(state, k, cfg)
    n = jnp.sum(victim).astype(jnp.int32)
    if cfg.incremental_accounting:
        state = storm_debit(state, victim, cum, cfg)
    return state._replace(
        task_state=jnp.where(victim, jnp.int8(TASK_PENDING), state.task_state),
        task_node=jnp.where(victim, -1, state.task_node),
        evictions=state.evictions + n)
