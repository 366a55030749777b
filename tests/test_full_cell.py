"""Full-cell node-dimension smoke: the paper's 12,500-node cell, for real.

``configs/agocs_full_cell`` carries the month-scale ingestion geometry
(max_nodes=12,500, max_tasks=262,144, E=8,192); this suite proves the
node-dimension paths actually *hold* at that width on one host:

* the fleet cannot even be added in one window (12,500 > E) — node ADDs
  stream across windows;
* ``evict_invalid`` gathers and the node-dim window-stats reductions run
  at N=12,500;
* the tiled ``sched_pass`` commit streams score/pref blocks over node
  tiles (``commit_tile_n``) instead of materialising a (P, 12500) pref
  tensor per lane, bitwise-equal to the untiled reference;
* a switchless two-lane fleet advances at full width.

Everything here is ``slow``-marked: shapes are the paper's, iteration
counts are smoke-sized (interpret-mode Pallas unrolls its grid at trace
time, so the kernel runs keep sched_batch small and node tiles large).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SimConfig
from repro.configs.agocs_full_cell import CONFIG as FULL_CELL
from repro.core import engine as eng
from repro.core.events import EventKind, HostEvent, pack_window, stack_windows
from repro.core.state import init_state, validate_invariants

pytestmark = pytest.mark.slow

N_FULL = FULL_CELL.max_nodes                 # 12,500
E = FULL_CELL.max_events_per_window          # 8,192 < N_FULL


def _cfg(**over) -> SimConfig:
    # paper-width node/task tables; smoke-sized scheduling batch so the
    # interpret-mode kernel's unrolled grid stays compilable
    base = dict(sched_batch=16, buffer_windows=4, buffer_max_events=65_536)
    base.update(over)
    return dataclasses.replace(FULL_CELL, **base)


def _windows(cfg, n_tasks=1_024, seed=0):
    """4 windows: the 12.5K-node fleet split over two ADD windows (the cap
    forces it), a task wave, then node removals that strand placed tasks."""
    r = np.random.default_rng(seed)

    def node(i):
        return HostEvent(0, EventKind.ADD_NODE, i,
                         a=(float(r.uniform(0.4, 1.0)),
                            float(r.uniform(0.4, 1.0)), 1.0))

    assert N_FULL > E, "full cell must overflow one window's event budget"
    w0 = [node(i) for i in range(E)]
    w1 = [node(i) for i in range(E, N_FULL)]
    w2 = [HostEvent(2, EventKind.ADD_TASK, t,
                    a=(float(r.uniform(0.002, 0.02)),
                       float(r.uniform(0.002, 0.02)), 0.0),
                    prio=int(r.integers(0, 12)))
          for t in range(n_tasks)]
    # remove a slice of the fleet; any tasks placed there get evicted
    w3 = [HostEvent(3, EventKind.REMOVE_NODE, i) for i in range(0, 2_000)]
    return jax.tree.map(jnp.asarray, stack_windows(
        [pack_window(cfg, evs, i) for i, evs in ((0, w0), (1, w1),
                                                 (2, w2), (3, w3))]))


def test_full_cell_engine_paths_at_n12500():
    """Reference engine end-to-end at paper width: streamed node ADDs,
    placements, node-dim stats reductions, evict_invalid after a 2,000-node
    removal — invariants clean throughout."""
    cfg = _cfg(sched_batch=256)
    windows = _windows(cfg)
    state, stats = eng.run_windows_jit(init_state(cfg), windows, cfg,
                                       "greedy", 0)
    state = jax.tree.map(np.asarray, state)
    assert int(state.node_active.sum()) == N_FULL - 2_000
    placed = int(stats["placements"][-1])
    assert placed > 0
    assert int(stats["evictions"][-1]) > 0          # stranded by REMOVE_NODE
    assert validate_invariants(state, cfg) == {}


def test_full_cell_tiled_commit_matches_untiled():
    """cfg.commit_tile_n streams the commit over node tiles at N=12,500;
    the running cross-tile argmax must not move one placement vs the
    whole-N reference path."""
    windows = _windows(_cfg())
    finals = {}
    for name, over in (
            ("ref", dict()),
            ("tiled_kernel", dict(use_kernels=True, commit_tile_n=8_192))):
        cfg = _cfg(**over)
        s, st = eng.run_windows_jit(init_state(cfg), windows, cfg,
                                    "greedy", 0)
        finals[name] = jax.tree.map(np.asarray, s)
        assert int(st["placements"][-1]) > 0
    a, b = finals["ref"], finals["tiled_kernel"]
    np.testing.assert_array_equal(a.task_node, b.task_node)
    np.testing.assert_array_equal(a.task_state, b.task_state)
    np.testing.assert_array_equal(a.node_reserved, b.node_reserved)


def test_full_cell_sched_pass_streams_node_tiles():
    """ops-level: the streaming sched_pass at the full 12,500-node width
    (padded to 16,384 = 2 x 8,192 tiles) is bitwise-equal to the whole-N
    composed reference."""
    from repro.kernels.placement_commit.ops import FAM_SCORES, sched_pass
    P, N, R = 16, N_FULL, 3
    r = np.random.default_rng(7)
    scores = jnp.asarray(r.normal(size=(P, N)).astype(np.float32))
    req = jnp.asarray((r.integers(1, 8, size=(P, R)) / 256.0
                       ).astype(np.float32))
    ok = jnp.asarray(r.random(size=(P, N)) < 0.7)
    valid = jnp.ones((P,), bool)
    total = jnp.asarray((r.integers(64, 256, size=(N, R)) / 64.0
                         ).astype(np.float32))
    denom = jnp.maximum(total, 1e-6)
    res0 = jnp.zeros((N, R), jnp.float32)
    ref = sched_pass(scores, req, ok, valid, total, denom, res0,
                     use_kernel=False, return_tally=True)
    got = sched_pass(scores, req, ok, valid, total, denom, res0,
                     family=FAM_SCORES, use_kernel=True,
                     tile_n=8_192, return_tally=True)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_full_cell_switchless_fleet_smoke():
    """Two-lane switchless fleet (greedy + round_robin) at paper width."""
    from repro.scenarios import ScenarioSpec, build_knobs
    from repro.scenarios import batch as batch_mod
    from repro.sched import snapshot_dispatch
    cfg = _cfg(sched_batch=128, sched_dispatch="table")
    specs = [ScenarioSpec(name="g"),
             ScenarioSpec(name="rr", scheduler="round_robin")]
    knobs, names = build_knobs(specs)
    table = snapshot_dispatch(names)
    lane_scheds = tuple(names.index(s.scheduler) for s in specs)
    state, stats = batch_mod.run_scenarios(
        batch_mod.init_batched_state(cfg, 2), _windows(cfg), knobs, cfg,
        names, 0, False, table, lane_scheds)
    placed = np.asarray(stats["placements"])[-1]
    assert (placed > 0).all()
    for b in range(2):
        lane = jax.tree.map(lambda x, b=b: np.asarray(x[b]), state)
        assert validate_invariants(lane, cfg) == {}, specs[b].name
