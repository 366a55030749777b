"""ScenarioFleet — one parsed trace feeding B divergent simulations.

Shares ``core.pipeline.WindowedDriver``'s drive loop (same WindowPrefetcher,
pacing, pause hooks, and per-batch seed derivation as the single-trajectory
Simulation — the lane-0 bit-identity guarantee depends on that) but the
device program advances a (B, ...)-stacked SimState: the host parses and
tensorises each window batch once and every scenario consumes it. Parse cost
is amortised B ways — the paper's §IV "multiple schedulers, one workload"
use case generalised to arbitrary what-if perturbations.

Two scaling paths ride on top of the vmapped program:

* ``mesh=`` shards the scenario axis over a 1-D ``('data',)`` device mesh
  via ``shard_map`` (vmap inside each shard, windows broadcast, per-lane
  stats gathered back; each chip runs the switchless dispatch of its own
  lanes). The spec list is padded up to a multiple of the
  device count with inert identity lanes; padding lanes are invisible in
  stats, reports and snapshots.
* :meth:`from_precompiled` feeds the fleet from a §V-A pre-compiled npz
  (core/precompile.py) — whole sweeps replay with zero parsing.

Headless sweeps can decimate the stats stream (``cfg.stats_stride == k``,
``whatif --stats-stride``): the fleet emits one (B, ...) row per k windows
(per-window injected counts accumulated across each chunk, lane
trajectories bitwise unchanged), ``stats_frame()`` arrays shrink
accordingly, and ``stats_window_indices()`` maps each row back to its
window. Counter and final-value report columns are unaffected (counters
are cumulative and the final window is always reported), but *mean*
columns (``pending_mean``, ``cpu_*_frac_mean`` and their deltas) become
means over the decimated sample — compare sweeps at equal strides.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.config import SimConfig
from repro.core.events import EventWindow
from repro.core.pipeline import WindowedDriver
from repro.core.snapshot import load_snapshot, save_snapshot
from repro.scenarios import batch as batch_mod
from repro.scenarios.report import scenario_report
from repro.scenarios.spec import ScenarioSpec, build_knobs
from repro.sched import snapshot_dispatch


class ScenarioFleet(WindowedDriver):
    """End-to-end batched what-if driver.

    >>> specs = expand_grid(scheduler=["greedy", "first_fit"],
    ...                     node_outage_frac=[0.0, 0.2])
    >>> fleet = ScenarioFleet(cfg, parser.packed_windows(200), specs,
    ...                       mesh=batch.fleet_mesh())   # mesh is optional
    >>> fleet.run()
    >>> print(format_table(fleet.report()))
    """

    def __init__(self, cfg: SimConfig, window_source: Iterator[EventWindow],
                 specs: Sequence[ScenarioSpec], batch_windows: int = 32,
                 seed: Optional[int] = None, mesh: Optional[Mesh] = None):
        super().__init__(cfg, window_source, batch_windows, seed)
        self.specs = list(specs)
        if not cfg.inject_slots:
            amped = [s.name for s in self.specs if s.arrival_rate > 1.0]
            if amped:
                raise ValueError(
                    f"scenarios {amped} have arrival_rate > 1 but "
                    "cfg.inject_slots == 0: amplification synthesises SUBMIT "
                    "events into the reserved slot pool, so the windows must "
                    "be packed with inject_slots > 0")
        self.mesh = mesh
        lanes = list(self.specs)
        if mesh is not None:
            n_dev = mesh.shape[batch_mod.FLEET_AXIS]
            # pad to a lane count the mesh divides; padding lanes reuse the
            # first spec's scheduler so the lax.switch table doesn't grow
            for i in range((-len(lanes)) % n_dev):
                lanes.append(ScenarioSpec(name=f"_pad{i}",
                                          scheduler=lanes[0].scheduler))
        self._lane_specs = lanes
        # static promise to the compiler: storm-free fleets drop the
        # eviction-storm pass (and its accounting debits) entirely
        self._has_storm = any(s.evict_storm_frac > 0.0 for s in lanes)
        self.knobs, self.scheduler_names = build_knobs(lanes)
        # Dispatch contract, frozen NOW: the registry rows this fleet's
        # scheduler indices point at. Plugins registered after construction
        # cannot retarget them (regression-tested). The static per-lane
        # scheduler map, pad lanes included, enables switchless dispatch:
        # under a mesh each chip gets the dispatch of its own lanes
        # (``batch.run_scenarios_sharded``).
        self.dispatch_table = snapshot_dispatch(self.scheduler_names)
        self._lane_scheds = tuple(
            self.scheduler_names.index(s.scheduler) for s in lanes)
        # what the window program compiles: "table" or "switch", and the
        # number of distinct chip lane maps (1 without a mesh)
        self.sched_dispatch = batch_mod.dispatch_mode(
            cfg, self.dispatch_table, self._lane_scheds)
        self.n_layouts = 1 if mesh is None else len(batch_mod.chip_layouts(
            self._lane_scheds, mesh.shape[batch_mod.FLEET_AXIS])[0])
        self.knobs = batch_mod.shard_over_fleet(self.knobs, mesh)
        self.state = batch_mod.init_batched_state(cfg, len(lanes), mesh)

    @classmethod
    def from_precompiled(cls, cfg: SimConfig, path: str,
                         specs: Sequence[ScenarioSpec],
                         batch_windows: int = 32, seed: Optional[int] = None,
                         mesh: Optional[Mesh] = None,
                         n_windows: Optional[int] = None,
                         start_window: int = 0) -> "ScenarioFleet":
        """A fleet fed straight from a pre-compiled npz (zero parsing).

        The npz must have been written by ``precompile_trace`` under a
        shape-compatible config (same window geometry and slot-pool
        reservation) — validated against the npz's embedded metadata.
        ``n_windows`` truncates the replay; ``start_window`` skips into the
        stack (chunked stacks only decompress the covered range) — pair it
        with :meth:`restore` of a snapshot taken at that window to resume a
        fleet mid-trace.
        """
        from repro.core.precompile import replay_windows, validate_replay
        validate_replay(path, cfg)
        return cls(cfg,
                   replay_windows(path, batch=batch_windows,
                                  n_windows=n_windows,
                                  start_window=start_window),
                   specs, batch_windows=batch_windows, seed=seed, mesh=mesh)

    @property
    def n_scenarios(self) -> int:
        return len(self.specs)

    @property
    def n_lanes(self) -> int:
        """Device-side lane count: n_scenarios plus any mesh padding."""
        return len(self._lane_specs)

    @property
    def names(self) -> List[str]:
        return [s.name for s in self.specs]

    def _advance(self, batch: EventWindow, seed: int):
        if self.mesh is not None:
            self.state, stats = batch_mod.run_scenarios_sharded_jit(
                self.state, batch, self.knobs, self.cfg,
                self.scheduler_names, self.mesh, seed,
                has_storm=self._has_storm, table=self.dispatch_table,
                lane_scheds=self._lane_scheds)
        else:
            self.state, stats = batch_mod.run_scenarios_jit(
                self.state, batch, self.knobs, self.cfg,
                self.scheduler_names, seed, has_storm=self._has_storm,
                table=self.dispatch_table, lane_scheds=self._lane_scheds)
        if self.n_lanes != self.n_scenarios:
            stats = jax.tree.map(lambda x: x[:, :self.n_scenarios], stats)
        return stats

    def _resync(self):
        return batch_mod.resync_fleet_jit(self.state, self.cfg)

    def _dispatch_args(self) -> dict:
        return {"sched": self.sched_dispatch, "layouts": self.n_layouts}

    def report(self, baseline: int = 0) -> dict:
        return scenario_report(self.names, self.stats_frame(),
                               [s.scheduler for s in self.specs],
                               baseline=baseline)

    # --- pause/snapshot/resume (paper §IV, batched) ---

    def save(self, path: str):
        """Snapshot the fleet: real (B, ...) lanes + scenario metadata (mesh
        padding lanes are sliced off, so snapshots are mesh-portable). The
        full per-lane specs ride in ``extra`` so a later consumer (the
        what-if service's fork-point store) can map a spec back to its
        lane."""
        import dataclasses
        state = jax.tree.map(lambda x: x[:self.n_scenarios], self.state)
        save_snapshot(path, state, self.cfg, self.windows_done,
                      extra={"scenario_names": self.names,
                             "schedulers": [s.scheduler for s in self.specs],
                             "specs": [dataclasses.asdict(s)
                                       for s in self.specs]})

    def restore(self, path: str):
        """Resume a fleet mid-trace from a batched snapshot.

        Feed the fleet a window source starting at the snapshot's window
        (``from_precompiled(..., start_window=snapshot_window)``) and the
        resumed run is bitwise identical to the uninterrupted one — the
        per-batch RNG seeds key off ``windows_done`` and the resync cadence
        is re-phased to the from-zero schedule (both tested).
        """
        state, cfg, windows_done, _extra = load_snapshot(path)
        lead = jax.tree.leaves(state)[0]
        if lead.shape[0] != self.n_scenarios:
            raise ValueError(
                f"snapshot holds {lead.shape[0]} scenarios, fleet has "
                f"{self.n_scenarios}")
        if cfg != self.cfg:
            raise ValueError("snapshot config differs from fleet config")
        if self.n_lanes != self.n_scenarios:
            pad = batch_mod.init_batched_state(
                self.cfg, self.n_lanes - self.n_scenarios)
            state = jax.tree.map(
                lambda s, p: jnp.concatenate([s, p], 0), state, pad)
        self.state = batch_mod.shard_over_fleet(state, self.mesh)
        self.windows_done = windows_done
        from repro.core.pipeline import restored_resync_phase
        self._since_resync = restored_resync_phase(
            windows_done, self.prefetcher.batch,
            self.cfg.resync_windows if self.cfg.incremental_accounting else 0)
