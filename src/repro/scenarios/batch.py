"""Batched what-if engine: B scenarios in ONE device program.

``make_scenario_step`` builds a single-scenario window transition that
mirrors ``engine.make_window_step`` exactly (same event-application order,
same accounting recomputes) with two scenario hooks spliced in:

* the incoming window passes through :func:`perturb.perturb_window`;
* after invalid-placement eviction, :func:`perturb.storm_evict` runs, then
  :func:`perturb.expire_injected` retires amplification clones whose
  sampled lifetime expired (injected-task lifecycles);
* the scheduler is dispatched with ``lax.switch`` over the scenario's
  scheduler index, so scenarios may differ in scheduler inside one program.

``run_scenarios`` vmaps that step over the scenario axis — the window batch
is *broadcast* (parsed once, simulated B ways) — and scans over windows, so
the whole fleet advances in lock-step on-device. With identity knobs and
scheduler index 0, lane 0 computes bit-identically to ``engine.run_windows``
(all perturbation ``where``s select the untouched operand, and the RNG keys
are derived the same way).

``run_scenarios_sharded`` wraps the same program in ``shard_map`` over the
``'data'`` axis of a 1-D device mesh: the B scenario lanes are split across
devices (vmap inside each shard), the window batch is broadcast to every
device, and per-lane stats are gathered back along the scenario axis. Lanes
never communicate, so per-lane results are identical to the pure-vmap path
(tested in tests/test_scenarios_sharded.py). Given the fleet's static lane
map, each chip runs the switchless dispatch of its own lanes, picked by
shard index.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config import SimConfig
from repro.core import engine as eng
from repro.core import stats as stats_mod
from repro.core import trace
from repro.core.events import EventWindow
from repro.sched import (DispatchTable, base_pass, finalize,
                         make_switchless_dispatch, snapshot_dispatch)
from repro.core.state import SimState, init_state
from repro.scenarios import perturb
from repro.scenarios.spec import ScenarioKnobs

FLEET_AXIS = "data"   # the mesh axis the scenario lanes shard over


def fleet_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D ('data',) mesh over the first ``n_devices`` (default: all)."""
    n = jax.device_count() if n_devices is None else n_devices
    if n < 1:
        raise ValueError(f"fleet_mesh needs at least 1 device, got {n}")
    if n > jax.device_count():
        raise ValueError(f"--mesh {n} > {jax.device_count()} devices "
                         "(on CPU, fake devices need XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    # Auto axis: lanes never communicate, and the runner slices padding
    # lanes off sharded outputs with plain indexing, which an Explicit
    # axis (make_mesh's default) would reject without an out_sharding
    return jax.make_mesh((n,), (FLEET_AXIS,), axis_types=(AxisType.Auto,))


def shard_over_fleet(tree, mesh: Optional[Mesh]):
    """Place every leaf's leading (lane) axis on the FLEET_AXIS shards.

    The one place the fleet's lane sharding is defined — knobs, batched
    states and restored snapshots all go through here. No-op without a mesh.
    """
    if mesh is None:
        return tree
    sh = NamedSharding(mesh, P(FLEET_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def init_batched_state(cfg: SimConfig, n_scenarios: int,
                       mesh: Optional[Mesh] = None) -> SimState:
    """A (B, ...)-stacked SimState pytree (B identical empty worlds).

    Built with ``broadcast_to`` — a zero-copy view the device program
    materialises lane-sharded — never ``jnp.tile``, which would eagerly
    allocate B full copies before transfer (regression-tested). Under a
    ``mesh`` the leading axis is placed on the FLEET_AXIS shards directly.
    """
    state = init_state(cfg)
    batched = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_scenarios,) + x.shape), state)
    return shard_over_fleet(batched, mesh)


def make_scenario_prelude(cfg: SimConfig, has_storm: bool = True):
    """Single-scenario (unbatched) pre-dispatch transition: window
    perturbation, event application, eviction, storm, injection expiry —
    everything :func:`make_scenario_advance` runs before the scheduler.
    Returns ``(state, injected)``; split out so the switchless fleet path
    can vmap it and then dispatch all lanes in ONE batched pass."""

    def prelude(state: SimState, w: EventWindow,
                knobs: ScenarioKnobs) -> Tuple[SimState, jax.Array]:
        w = perturb.perturb_window(w, knobs, cfg, window=state.window)
        if cfg.inject_slots:
            injected = jnp.sum(w.kind[-cfg.inject_slots:]
                               == jnp.int8(eng.EventKind.ADD_TASK)
                               ).astype(jnp.int32)
        else:
            injected = jnp.int32(0)
        state = eng.apply_node_events(state, w, cfg)
        state = eng.apply_task_events(state, w, cfg)
        if not cfg.incremental_accounting:
            state = eng.recompute_accounting(state, cfg)
        state = eng.evict_invalid(state, cfg)
        if has_storm:
            state = perturb.storm_evict(state, knobs, cfg)
        if cfg.inject_slots:
            state = perturb.expire_injected(state, knobs, cfg)
        if not cfg.incremental_accounting:
            state = eng.recompute_accounting(state, cfg)
        return state, injected

    return prelude


def make_scenario_advance(cfg: SimConfig, scheduler_names: Tuple[str, ...],
                          has_storm: bool = True,
                          table: Optional[DispatchTable] = None):
    """Single-scenario (unbatched) stats-free transition; vmap adds the
    scenario axis.  Returns ``(state, injected)`` — the per-window injected
    SUBMIT count rides the carry so strided stats rows
    (``cfg.stats_stride > 1``) can accumulate it across skipped windows.

    Scheduler dispatch exploits the shared structure of repro.sched:
    every scheduler is `base_pass` (constraint matching + pending top-k) ->
    per-scheduler *proposal* -> `finalize` (capacity-checked assignment).
    Only the cheap proposal goes through ``lax.switch`` — the expensive
    shared passes run once per lane regardless of how many schedulers the
    fleet mixes (a vmapped switch executes every branch, so keeping the
    branches thin matters). This is the fleet's *fallback* dispatch: fleets
    whose schedulers all registered table forms go through the switchless
    grouped path instead (see :func:`run_scenarios` / ``sched.table``).

    The proposal rows come from ``table`` — an immutable
    ``snapshot_dispatch`` of the registry taken when the fleet was built
    (or here, if the caller didn't snapshot) — NOT from the live registry
    views, so plugins registered after fleet construction cannot reorder or
    retarget a compiled fleet's scheduler indices.

    ``has_storm=False`` (a *static* promise from the runner that no lane
    sets ``evict_storm_frac > 0``) drops the storm pass from the compiled
    program entirely — at storm_frac == 0 it is a bitwise identity, but it
    still costs an O(max_tasks) hash sweep per lane per window (plus, under
    incremental accounting, two masked segment-sum debit passes).
    """
    if table is None:
        table = snapshot_dispatch(scheduler_names)
    proposers = table.proposers
    dyn_table = jnp.asarray(table.dynamic)
    prelude = make_scenario_prelude(cfg, has_storm)

    def dispatch(state: SimState, rng: jax.Array, idx: jax.Array) -> SimState:
        pend_idx, valid, base_ok, scores = base_pass(state, cfg)
        with trace.scope(trace.PROPOSE):
            if len(proposers) == 1:  # no switch: keeps lane 0 trivial
                pref = proposers[0](state, cfg, rng, pend_idx, valid,
                                    base_ok, scores)
            else:
                pref = jax.lax.switch(
                    idx,
                    [lambda s, r, pi, v, bo, sc, fn=fn:
                     fn(s, cfg, r, pi, v, bo, sc) for fn in proposers],
                    state, rng, pend_idx, valid, base_ok, scores)
        dyn = table.dynamic[0] if len(proposers) == 1 else dyn_table[idx]
        return finalize(state, cfg, pend_idx, valid, base_ok, pref,
                        dynamic_bestfit=dyn)

    def advance(state: SimState, w: EventWindow, rng: jax.Array,
                knobs: ScenarioKnobs) -> Tuple[SimState, jax.Array]:
        state, injected = prelude(state, w, knobs)
        state = dispatch(state, rng, knobs.sched_idx)
        if not cfg.incremental_accounting:
            state = eng.recompute_accounting(state, cfg)
        return state._replace(window=state.window + 1), injected

    return advance


def make_scenario_step(cfg: SimConfig, scheduler_names: Tuple[str, ...],
                       has_storm: bool = True,
                       table: Optional[DispatchTable] = None):
    """Single-scenario (unbatched) step (advance + stats row); vmap adds the
    scenario axis.  See :func:`make_scenario_advance` for the transition
    semantics — this wrapper exists for unit tests and the stride-1 mental
    model; ``run_scenarios`` composes the advance and the (vmapped) stats
    emission itself so strided runs skip the stats work entirely."""
    advance = make_scenario_advance(cfg, scheduler_names, has_storm, table)

    def step(state: SimState, w: EventWindow, rng: jax.Array,
             knobs: ScenarioKnobs
             ) -> Tuple[SimState, Dict[str, jax.Array]]:
        state, injected = advance(state, w, rng, knobs)
        stats = stats_mod.window_stats(state, cfg)
        stats["injected_arrivals"] = injected
        return state, stats

    return step


def dispatch_mode(cfg: SimConfig, table: DispatchTable,
                  lane_scheds) -> str:
    """The scheduler dispatch a fleet launch compiles: ``"table"``
    (switchless) where the launch has the per-lane scheduler assignment as
    a STATIC tuple (``lane_scheds``, from ScenarioFleet) and every
    scheduler in the table has a table form, unless
    ``cfg.sched_dispatch='switch'`` forces the fallback; ``"switch"``
    (the vmapped ``lax.switch``) otherwise. Never raises."""
    able = lane_scheds is not None and table.switchless
    return "table" if able and cfg.sched_dispatch != "switch" else "switch"


def _want_switchless(cfg: SimConfig, table: DispatchTable,
                     lane_scheds) -> bool:
    """Resolve ``cfg.sched_dispatch`` against what this launch can do
    (:func:`dispatch_mode`). A mesh-sharded launch counts as able when it
    has the fleet's whole lane map: each chip then dispatches its own
    lanes switchlessly. 'auto' falls back to switch when the map or a
    table form is missing; 'table' raises instead of silently degrading."""
    if (cfg.sched_dispatch == "table"
            and dispatch_mode(cfg, table, lane_scheds) != "table"):
        opaque = [n for n, f in zip(table.names, table.forms) if f is None]
        if opaque:
            raise ValueError(
                f"cfg.sched_dispatch='table' but schedulers {opaque} have "
                "no table form — register_scheduler(..., table_form=...) "
                "them or drop to 'auto'/'switch'")
        raise ValueError(
            "cfg.sched_dispatch='table' but no static lane assignment "
            "(lane_scheds) was provided (the serving warm path has none and "
            "dispatches with lax.switch) — pass the fleet's per-lane "
            "scheduler map or use 'auto'/'switch'")
    return dispatch_mode(cfg, table, lane_scheds) == "table"


def _switchless_advance(cfg: SimConfig, has_storm: bool, sched_B):
    """The switchless (B, ...)-lane advance: the vmapped prelude, ONE
    batched scheduler pass ``sched_B(state_B, key) -> state_B`` (base pass,
    proposals, commit), then the vmapped accounting recompute."""
    vpre = jax.vmap(make_scenario_prelude(cfg, has_storm),
                    in_axes=(0, None, 0))
    vrec = jax.vmap(lambda s: eng.recompute_accounting(s, cfg))

    def vadv(state_B, w, key, kn):
        state_B, injected = vpre(state_B, w, kn)
        state_B = sched_B(state_B, key)
        if not cfg.incremental_accounting:
            state_B = vrec(state_B)
        return state_B._replace(window=state_B.window + 1), injected

    return vadv


def run_scenarios(state: SimState, windows: EventWindow, knobs: ScenarioKnobs,
                  cfg: SimConfig, scheduler_names: Tuple[str, ...],
                  seed: int = 0, has_storm: bool = True,
                  table: Optional[DispatchTable] = None,
                  lane_scheds: Optional[Tuple[int, ...]] = None
                  ) -> Tuple[SimState, Dict[str, jax.Array]]:
    """Scan the vmapped step over stacked windows.

    state: (B, ...) stacked SimState; windows: (W, ...) stacked EventWindow
    (shared across scenarios); knobs: (B,) ScenarioKnobs.
    Returns the advanced (B, ...) state and a stats dict of (W, B, ...)
    arrays. RNG keys are split exactly as in ``engine.run_windows`` and
    shared across scenarios (common random numbers — the right thing for
    paired what-if comparisons). ``has_storm=False`` statically drops the
    eviction-storm pass (only valid when every lane's storm_frac is 0).

    Scheduler dispatch: with ``lane_scheds`` (the fleet's static per-lane
    scheduler indices into ``scheduler_names``) and a fully table-formed
    registry snapshot, the per-window advance is *switchless* — the lanes
    run a vmapped prelude, then ONE grouped scheduling pass that evaluates
    each distinct proposal family only over the lanes that use it (under
    ``cfg.use_kernels``, fused into the placement-commit kernel). Otherwise
    (opaque plugin in the mix, no static lane map, or
    ``cfg.sched_dispatch='switch'``) every lane dispatches through the
    classic vmapped ``lax.switch``. Both paths produce bitwise-identical
    lane trajectories; ``lane_scheds`` MUST agree with ``knobs.sched_idx``
    (ScenarioFleet builds both from the same spec list).

    With ``cfg.stats_stride == k > 1`` the scan emits one (B, ...) stats
    row per k windows — same cadence and tail semantics as
    ``engine.run_windows``, with the per-window ``injected_arrivals`` count
    accumulated across each chunk so amplification lanes lose no events.
    """
    if table is None:
        table = snapshot_dispatch(scheduler_names)
    if _want_switchless(cfg, table, lane_scheds):
        vadv = _switchless_advance(
            cfg, has_storm, make_switchless_dispatch(cfg, table, lane_scheds))
    else:
        advance = make_scenario_advance(cfg, scheduler_names, has_storm,
                                        table)
        vadv = jax.vmap(advance, in_axes=(0, None, None, 0))
    return _scan_fleet(state, windows, knobs, cfg, seed, vadv)


def _scan_fleet(state: SimState, windows: EventWindow, knobs: ScenarioKnobs,
                cfg: SimConfig, seed: int, vadv
                ) -> Tuple[SimState, Dict[str, jax.Array]]:
    """Scan the (B, ...)-lane advance ``vadv`` over the windows, with the
    stats rows (``cfg.stats_stride``) and RNG keys of :func:`run_scenarios`."""
    vstats = jax.vmap(lambda s: stats_mod.window_stats(s, cfg))

    def rows_for(s, injected):
        stats = vstats(s)
        stats["injected_arrivals"] = injected
        return stats

    W = windows.kind.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), W)
    stride = cfg.stats_stride

    if stride == 1 or W == 0:     # W == 0: the empty scan handles it cleanly
        def body(s, xs):
            w, k = xs
            s, injected = vadv(s, w, k, knobs)
            return s, rows_for(s, injected)

        return jax.lax.scan(body, state, (windows, keys))

    B = jax.tree.leaves(state)[0].shape[0]

    def chunk(s, xs):
        def inner(carry, x2):
            s2, acc = carry
            w, k = x2
            s2, injected = vadv(s2, w, k, knobs)
            return (s2, acc + injected), None

        (s, injected), _ = jax.lax.scan(inner, (s, jnp.zeros(B, jnp.int32)),
                                        xs)
        return s, rows_for(s, injected)

    return eng.scan_strided(chunk, state, (windows, keys), W, stride)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "scheduler_names", "has_storm",
                                    "table", "lane_scheds"),
                   donate_argnames=("state",))
def run_scenarios_jit(state: SimState, windows: EventWindow,
                      knobs: ScenarioKnobs, cfg: SimConfig,
                      scheduler_names: Tuple[str, ...], seed: int = 0,
                      has_storm: bool = True,
                      table: Optional[DispatchTable] = None,
                      lane_scheds: Optional[Tuple[int, ...]] = None):
    """Donating fleet entry point: the (B, max_tasks, ...) tables of
    ``state`` back the output lanes instead of being double-buffered —
    thread the returned state; do not reuse the argument."""
    return run_scenarios(state, windows, knobs, cfg, scheduler_names, seed,
                         has_storm, table, lane_scheds)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def resync_fleet_jit(state: SimState, cfg: SimConfig) -> SimState:
    """Per-lane full recompute of both accounting tallies — the fleet
    driver's periodic drift resync under incremental accounting."""
    return jax.vmap(lambda s: eng.recompute_accounting(s, cfg))(state)


def chip_layouts(lane_scheds: Tuple[int, ...], n_chips: int
                 ) -> Tuple[Tuple[Tuple[int, ...], ...], Tuple[int, ...]]:
    """Cut a fleet's static lane map into the chips' local maps.

    Chip ``c`` holds lanes ``c*L .. (c+1)*L - 1`` (``L = B / n_chips``, the
    block ``P(FLEET_AXIS)`` gives it). Returns the distinct local maps, in
    order of first use, and each chip's index into them."""
    L = len(lane_scheds) // n_chips
    local = [tuple(lane_scheds[c * L:(c + 1) * L]) for c in range(n_chips)]
    layouts = tuple(dict.fromkeys(local))
    return layouts, tuple(layouts.index(t) for t in local)


def run_scenarios_sharded(state: SimState, windows: EventWindow,
                          knobs: ScenarioKnobs, cfg: SimConfig,
                          scheduler_names: Tuple[str, ...], mesh: Mesh,
                          seed: int = 0, has_storm: bool = True,
                          table: Optional[DispatchTable] = None,
                          lane_scheds: Optional[Tuple[int, ...]] = None
                          ) -> Tuple[SimState, Dict[str, jax.Array]]:
    """``run_scenarios`` with the scenario axis split over a device mesh.

    state/knobs are sharded over FLEET_AXIS (B must divide by the mesh
    size — ScenarioFleet pads specs up); windows are replicated to every
    device; the (W, B, ...) stats gather back along axis 1. Each shard runs
    the plain vmapped program on its B/n local lanes with the same RNG key
    schedule, so per-lane results match the single-device path exactly.

    Scheduler dispatch follows :func:`run_scenarios`' rule, per chip. With
    ``lane_scheds`` (the whole fleet's static map, pad lanes included) and
    a fully table-formed registry, the map is cut into the chips' local
    maps (:func:`chip_layouts`) and each distinct one becomes its own
    switchless dispatch. The shard body picks its chip's dispatch with a
    ``lax.switch`` on the shard index: that index is not batched, so the
    switch lowers to a conditional that runs one branch. Only the
    scheduler pass sits in it; the prelude, the recompute and the stats
    row stay outside. Where every chip has the same local map there is no
    switch at all. Without the map, with an opaque plugin or under
    ``cfg.sched_dispatch='switch'``, every lane dispatches through the
    vmapped ``lax.switch`` fallback. Lane trajectories are
    bitwise-identical either way.
    """
    B = jax.tree.leaves(state)[0].shape[0]
    n_dev = mesh.shape[FLEET_AXIS]
    if B % n_dev:
        raise ValueError(f"B={B} lanes not divisible by the {n_dev}-device "
                         f"'{FLEET_AXIS}' mesh axis — pad the spec list")
    if table is None:
        table = snapshot_dispatch(scheduler_names)
    layouts, branch_of_chip = ((None,), None) if lane_scheds is None else \
        chip_layouts(lane_scheds, n_dev)

    if len(layouts) > 1 and _want_switchless(cfg, table, lane_scheds):
        branches = [make_switchless_dispatch(cfg, table, local)
                    for local in layouts]
        branch = jnp.asarray(branch_of_chip, jnp.int32)

        def sched_B(state_B, key):
            return jax.lax.switch(branch[jax.lax.axis_index(FLEET_AXIS)],
                                  branches, state_B, key)

        vadv = _switchless_advance(cfg, has_storm, sched_B)

        def body(s, w, k):
            return _scan_fleet(s, w, k, cfg, seed, vadv)
    else:
        def body(s, w, k):
            return run_scenarios(s, w, k, cfg, scheduler_names, seed,
                                 has_storm, table, layouts[0])

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(FLEET_AXIS), P(), P(FLEET_AXIS)),
                       out_specs=(P(FLEET_AXIS), P(None, FLEET_AXIS)),
                       check_vma=False)
    return fn(state, windows, knobs)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "scheduler_names", "mesh",
                                    "has_storm", "table", "lane_scheds"),
                   donate_argnames=("state",))
def run_scenarios_sharded_jit(state: SimState, windows: EventWindow,
                              knobs: ScenarioKnobs, cfg: SimConfig,
                              scheduler_names: Tuple[str, ...], mesh: Mesh,
                              seed: int = 0, has_storm: bool = True,
                              table: Optional[DispatchTable] = None,
                              lane_scheds: Optional[Tuple[int, ...]] = None):
    return run_scenarios_sharded(state, windows, knobs, cfg, scheduler_names,
                                 mesh, seed, has_storm, table, lane_scheds)
