"""Switchless scheduler dispatch: proposal-table vs vmapped lax.switch.

The fleet's dispatch contract: every distinct proposal family is evaluated
once over its own lane sub-batch and merged back by static lane order, and
the result is *bitwise identical* to the vmapped ``lax.switch`` fallback —
lane for lane, across every builtin (dynamic-bestfit lanes included),
runtime-registered table-form plugins, storms and arrival amplification.
Opaque plugins (no table form) keep the switch path; ``sched_dispatch ==
"table"`` demands switchless and must error on them. The dispatch table is
snapshotted at fleet build, so registry mutations after construction can
never retarget a live fleet's scheduler indices.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import REDUCED_SIM
from repro.core.events import EventKind, HostEvent, pack_window, stack_windows
from repro.kernels.placement_commit.kernel import (FAM_EXTERNAL,
                                                   FAM_NODE_ORDER,
                                                   FAM_SCORES)
from repro.sched import (context_from_state, register_scheduler,
                         snapshot_dispatch, unregister_scheduler, TableForm)
from repro.scenarios import ScenarioSpec, build_knobs
from repro.scenarios import batch as batch_mod

CFG = dataclasses.replace(REDUCED_SIM, inject_slots=16, inject_task_slots=64)

BUILTINS = ("greedy", "first_fit", "round_robin", "random",
            "simulated_annealing", "tabu_search", "genetic")


def _windows(cfg, n_nodes=16, n_tasks=96, n_windows=4, seed=0):
    r = np.random.default_rng(seed)
    ws = [pack_window(cfg, [HostEvent(0, EventKind.ADD_NODE, i,
                                      a=(float(r.uniform(0.4, 1.0)),
                                         float(r.uniform(0.4, 1.0)), 1.0))
                            for i in range(n_nodes)], 0)]
    t = 0
    for w in range(1, n_windows):
        evs = []
        for _ in range(n_tasks // (n_windows - 1)):
            evs.append(HostEvent(w, EventKind.ADD_TASK, t,
                                 a=(float(r.uniform(0.02, 0.2)),
                                    float(r.uniform(0.02, 0.2)), 0.0),
                                 prio=int(r.integers(0, 12))))
            t += 1
        ws.append(pack_window(cfg, evs, w))
    return jax.tree.map(jnp.asarray, stack_windows(ws))


def _assert_bitwise(a_tree, b_tree, label):
    for f in a_tree._fields:
        a, b = np.asarray(getattr(a_tree, f)), np.asarray(getattr(b_tree, f))
        if a.dtype.kind == "f":
            eq = (a == b) | (np.isnan(a) & np.isnan(b))
        else:
            eq = a == b
        assert eq.all(), f"{label}: field {f} diverged at {(~eq).sum()} elts"


def _run_both(cfg, specs, seed=3, n_windows=4):
    """One fleet through the switch path and the switchless path."""
    knobs, names = build_knobs(specs)
    table = snapshot_dispatch(names)
    lane_scheds = tuple(names.index(s.scheduler) for s in specs)
    windows = _windows(cfg, n_windows=n_windows)
    has_storm = any(s.evict_storm_frac > 0.0 for s in specs)
    out = {}
    for mode, ls in (("switch", None), ("table", lane_scheds)):
        state = batch_mod.init_batched_state(cfg, len(specs))
        out[mode] = batch_mod.run_scenarios(state, windows, knobs, cfg,
                                            names, seed, has_storm, table, ls)
    return out


# --- bitwise switch-vs-switchless, the full builtin mix ----------------------

MIXED_SPECS = tuple(
    [ScenarioSpec(name=f"b-{s}", scheduler=s) for s in BUILTINS]
    + [ScenarioSpec(name="storm", scheduler="greedy", evict_storm_frac=0.2),
       ScenarioSpec(name="amp", scheduler="round_robin", arrival_rate=2.0)])


@pytest.mark.parametrize("use_kernels", [False, True])
def test_switchless_bitwise_all_builtins_storm_injection(use_kernels):
    """Lane-for-lane bitwise identity on a 9-lane fleet covering every
    builtin (greedy = dynamic-bestfit), an eviction-storm lane and an
    amplified lane injecting cloned SUBMITs — in both the jnp reference
    and the fused sched_pass kernel configuration."""
    cfg = dataclasses.replace(CFG, use_kernels=use_kernels)
    out = _run_both(cfg, MIXED_SPECS)
    s_sw, st_sw = out["switch"]
    s_tb, st_tb = out["table"]
    _assert_bitwise(s_sw, s_tb, f"kernels={use_kernels}")
    for k in st_sw:
        np.testing.assert_array_equal(np.asarray(st_sw[k]),
                                      np.asarray(st_tb[k]), err_msg=k)
    placed = np.asarray(st_sw["placements"])[-1]
    assert (placed > 0).all()
    injected = np.asarray(st_sw["injected_arrivals"]).sum(0)
    assert injected[-1] > 0 and (injected[:-1] == 0).all()


def test_switchless_matches_with_commit_tiling():
    """Streaming the commit over node tiles (commit_tile_n < max_nodes)
    must not move a single placement."""
    base = _run_both(CFG, MIXED_SPECS)
    tiled_cfg = dataclasses.replace(CFG, use_kernels=True,
                                    commit_tile_n=16, commit_tile_p=8)
    tiled = _run_both(tiled_cfg, MIXED_SPECS)
    _assert_bitwise(base["switch"][0], tiled["table"][0], "tiled-vs-switch")


# --- runtime-registered plugins ----------------------------------------------

def _tf_pack_left(cfg, ctx, rng, params):
    return jnp.broadcast_to(ctx.node_reserved.sum(-1)[None, :],
                            ctx.base_ok.shape)


def _propose_pack_left(state, cfg, rng, idx, valid, base_ok, scores):
    ctx = context_from_state(state, idx, valid, base_ok, scores)
    return _tf_pack_left(cfg, ctx, rng, ())


def _propose_pack_right(state, cfg, rng, idx, valid, base_ok, scores):
    return jnp.broadcast_to(-state.node_reserved.sum(-1)[None, :],
                            base_ok.shape)


@pytest.fixture
def table_plugin():
    name = "_t_pack_left"
    register_scheduler(name, _propose_pack_left,
                       table_form=TableForm(_tf_pack_left))
    yield name
    unregister_scheduler(name)


@pytest.fixture
def opaque_plugin():
    name = "_t_opaque"
    register_scheduler(name, _propose_pack_left)
    yield name
    unregister_scheduler(name)


def test_table_form_plugin_rides_switchless(table_plugin):
    specs = [ScenarioSpec(name="g"),
             ScenarioSpec(name="p", scheduler=table_plugin),
             ScenarioSpec(name="rr", scheduler="round_robin")]
    _, names = build_knobs(specs)
    assert snapshot_dispatch(names).switchless
    out = _run_both(CFG, specs)
    _assert_bitwise(out["switch"][0], out["table"][0], "plugin")
    # consolidation genuinely differs from greedy best-fit-decreasing
    assert not np.array_equal(np.asarray(out["table"][0].task_node[0]),
                              np.asarray(out["table"][0].task_node[1]))


def test_opaque_plugin_falls_back_to_switch(opaque_plugin):
    """No table form -> table not switchless; 'auto' silently keeps the
    lax.switch path (and still runs), 'table' refuses by name."""
    specs = [ScenarioSpec(name="g"),
             ScenarioSpec(name="p", scheduler=opaque_plugin)]
    knobs, names = build_knobs(specs)
    table = snapshot_dispatch(names)
    assert not table.switchless
    windows = _windows(CFG)
    lane_scheds = tuple(names.index(s.scheduler) for s in specs)
    state = batch_mod.init_batched_state(CFG, len(specs))
    s_auto, _ = batch_mod.run_scenarios(state, windows, knobs, CFG, names,
                                        0, False, table, lane_scheds)
    assert int(s_auto.placements.sum()) > 0
    strict = dataclasses.replace(CFG, sched_dispatch="table")
    with pytest.raises(ValueError, match=opaque_plugin):
        batch_mod.run_scenarios(batch_mod.init_batched_state(strict, 2),
                                windows, knobs, strict, names, 0, False,
                                table, lane_scheds)


def test_dispatch_mode_table_requires_lane_assignment():
    specs = [ScenarioSpec(name="g")]
    knobs, names = build_knobs(specs)
    strict = dataclasses.replace(CFG, sched_dispatch="table")
    with pytest.raises(ValueError, match="lane"):
        batch_mod.run_scenarios(batch_mod.init_batched_state(strict, 1),
                                _windows(strict), knobs, strict, names, 0,
                                False, snapshot_dispatch(names), None)


def test_forced_switch_mode_is_honoured(table_plugin):
    """sched_dispatch='switch' runs the fallback even when every lane is
    table-form; results still match the switchless path bitwise."""
    specs = [ScenarioSpec(name="g"),
             ScenarioSpec(name="p", scheduler=table_plugin)]
    knobs, names = build_knobs(specs)
    table = snapshot_dispatch(names)
    ls = tuple(names.index(s.scheduler) for s in specs)
    windows = _windows(CFG)
    forced = dataclasses.replace(CFG, sched_dispatch="switch")
    s_f, _ = batch_mod.run_scenarios(batch_mod.init_batched_state(forced, 2),
                                     windows, knobs, forced, names, 0, False,
                                     table, ls)
    s_t, _ = batch_mod.run_scenarios(batch_mod.init_batched_state(CFG, 2),
                                     windows, knobs, CFG, names, 0, False,
                                     table, ls)
    _assert_bitwise(s_f, s_t, "forced-switch")


# --- snapshot freeze (registry mutation after fleet build) -------------------

def test_fleet_dispatch_frozen_at_construction(table_plugin):
    """A plugin re-registered (or newly registered) AFTER ScenarioFleet
    construction cannot retarget an existing fleet's scheduler indices:
    the fleet keeps dispatching to the snapshotted proposer."""
    from repro.scenarios import ScenarioFleet
    specs = [ScenarioSpec(name="g"),
             ScenarioSpec(name="p", scheduler=table_plugin)]
    cfg = CFG

    def mk_fleet():
        ws = _windows(cfg)
        source = (jax.tree.map(lambda x, w=w: x[w], ws) for w in range(4))
        return ScenarioFleet(cfg, source, specs, batch_windows=4, seed=0)

    control = mk_fleet()
    control.run()

    fleet = mk_fleet()
    frozen = fleet.dispatch_table
    # mutate the registry out from under the live fleet
    register_scheduler(table_plugin, _propose_pack_right,
                       table_form=TableForm(_tf_pack_left), overwrite=True)
    register_scheduler("_t_late", _propose_pack_right)
    try:
        assert fleet.dispatch_table is frozen
        assert frozen.proposers[frozen.names.index(table_plugin)] \
            is _propose_pack_left
        # a fresh snapshot DOES see the mutation — only live fleets don't
        fresh = snapshot_dispatch(frozen.names)
        assert fresh.proposers[fresh.names.index(table_plugin)] \
            is _propose_pack_right
        fleet.run()
        for a, b in zip(jax.tree.leaves(fleet.state),
                        jax.tree.leaves(control.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        unregister_scheduler("_t_late")
        register_scheduler(table_plugin, _propose_pack_left,
                           table_form=TableForm(_tf_pack_left),
                           overwrite=True)


# --- fused sched_pass kernel vs composed reference ---------------------------

def _rand_operands(P, N, R=3, seed=0):
    r = np.random.default_rng(seed)
    scores = jnp.asarray(r.normal(size=(P, N)).astype(np.float32))
    req = jnp.asarray((r.integers(1, 16, size=(P, R)) / 64.0
                       ).astype(np.float32))
    ok = jnp.asarray(r.random(size=(P, N)) < 0.8)
    valid = jnp.asarray(r.random(size=P) < 0.9)
    total = jnp.asarray((r.integers(32, 128, size=(N, R)) / 64.0
                         ).astype(np.float32))
    denom = jnp.maximum(total, 1e-6)
    res0 = jnp.asarray((r.integers(0, 16, size=(N, R)) / 64.0
                        ).astype(np.float32))
    return scores, req, ok, valid, total, denom, res0


@pytest.mark.parametrize("P,N", [(37, 53), (16, 64), (5, 7)])
@pytest.mark.parametrize("family_start", [("scores", 0), ("node_order", 7)])
@pytest.mark.parametrize("dyn", [False, True])
def test_sched_pass_kernel_matches_ref_nondivisible(P, N, family_start, dyn):
    """Fused kernel vs composed propose->finalize reference at shapes that
    force padding tiles in both P and N."""
    from repro.kernels.placement_commit.ops import (FAM_NODE_ORDER,
                                                    FAM_SCORES, sched_pass)
    fam = FAM_SCORES if family_start[0] == "scores" else FAM_NODE_ORDER
    start = family_start[1]
    ops = _rand_operands(P, N)
    ref = sched_pass(*ops, dynamic_bestfit=dyn, family=fam, start=start,
                     use_kernel=False, return_tally=True)
    for tile_p, tile_n in ((16, None), (16, 16), (8, 32)):
        got = sched_pass(*ops, dynamic_bestfit=dyn, family=fam, start=start,
                         use_kernel=True, tile_p=tile_p,
                         tile_n=tile_n, return_tally=True)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"fam={fam} dyn={dyn} tiles=({tile_p},{tile_n})")


def test_sched_pass_streaming_tiles_match_whole_n():
    """The cross-tile running-argmax carry (strict > adopt rule) preserves
    first-index tie-breaks: streaming with tile_n < N is bitwise equal to
    the whole-N pass, ties and all-invalid rows included."""
    from repro.kernels.placement_commit.ops import FAM_SCORES, sched_pass
    P, N = 24, 48
    ops = list(_rand_operands(P, N, seed=1))
    # force score ties across tile boundaries + a fully-blocked row
    scores = np.asarray(ops[0]).copy()
    scores[3, :] = 0.25
    scores[7, ::5] = 1.5
    ops[0] = jnp.asarray(scores)
    ok = np.asarray(ops[2]).copy()
    ok[11, :] = False
    ops[2] = jnp.asarray(ok)
    ref = sched_pass(*ops, family=FAM_SCORES, use_kernel=False,
                     return_tally=True)
    for tile_n in (8, 16, 24):
        got = sched_pass(*ops, family=FAM_SCORES, use_kernel=True,
                         tile_n=tile_n, return_tally=True)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"tile_n={tile_n}")


# --- config validation -------------------------------------------------------

def test_sched_dispatch_config_validation():
    with pytest.raises(ValueError, match="sched_dispatch"):
        dataclasses.replace(REDUCED_SIM, sched_dispatch="bogus")
    with pytest.raises(ValueError, match="commit_tile"):
        dataclasses.replace(REDUCED_SIM, commit_tile_n=-1)


# --- which commit a platform lowers to ---------------------------------------

def _commit_program_text(cfg, fleet: bool) -> str:
    """The CPU program of one commit pass: ``finalize`` on one lane, or a
    4-lane switchless fleet of mixed families."""
    from repro.core.state import init_state
    from repro.sched.commit import finalize
    from repro.sched.table import make_switchless_dispatch
    if fleet:
        names = ("greedy", "simulated_annealing", "round_robin", "first_fit")
        dispatch = make_switchless_dispatch(cfg, snapshot_dispatch(names),
                                            (0, 1, 2, 3))
        state = batch_mod.init_batched_state(cfg, 4)
        return jax.jit(dispatch).lower(
            state, jax.random.PRNGKey(0)).compile().as_text()
    P, N = cfg.sched_batch, cfg.max_nodes

    def fn(state, idx, valid, pref):
        return finalize(state, cfg, idx, valid, jnp.isfinite(pref), pref,
                        dynamic_bestfit=True)

    return jax.jit(fn).lower(
        init_state(cfg), jnp.arange(P, dtype=jnp.int32),
        jnp.ones((P,), bool), jnp.zeros((P, N))).compile().as_text()


@pytest.mark.parametrize("fleet", [False, True])
@pytest.mark.parametrize("use_kernels", [False, True])
def test_cpu_commit_is_reference_unless_kernels_asked(fleet, use_kernels):
    """Lowered for the CPU, the commit holds no Mosaic call either way: the
    jnp reference's scan unless ``use_kernels`` asks for the interpreted
    kernel, whose ops carry its name (the TPU side is
    tests/test_tpu_compile.py)."""
    cfg = dataclasses.replace(REDUCED_SIM, use_kernels=use_kernels)
    text = _commit_program_text(cfg, fleet)
    assert "tpu_custom_call" not in text
    ops = re.findall(r'op_name="([^"]*pass\.commit[^"]*)"', text)
    interpreted = any("/placement_commit/" in op for op in ops)
    ref_loop = any("/while" in op and "/placement_commit/" not in op
                   for op in ops)
    assert interpreted == use_kernels
    assert ref_loop != use_kernels


# --- the fused fleet kernel, lane for lane against the reference -------------

CELL_MIX = ((FAM_SCORES, True), (FAM_EXTERNAL, False),
            (FAM_NODE_ORDER, False), (FAM_NODE_ORDER, False),
            (FAM_SCORES, True), (FAM_EXTERNAL, False),
            (FAM_NODE_ORDER, False))


def _fleet_operands(B, P, N, n_ext, seed):
    """Random fleet commit operands; row 3 of every lane is all -inf (no
    feasible node), ~10% of nodes are dead (capacity folded to -1)."""
    r = np.random.default_rng(seed)
    scores = r.normal(size=(B, P, N)).astype(np.float32)
    scores[r.random((B, P, N)) < 0.2] = -np.inf
    scores[:, min(3, P - 1)] = -np.inf
    req = (r.integers(1, 16, size=(B, P, 3)) / 64.0).astype(np.float32)
    valid = r.random((B, P)) < 0.9
    node_total = (r.integers(32, 128, size=(B, N, 3)) / 64.0
                  ).astype(np.float32)
    total = np.where(r.random((B, N, 1)) < 0.1, -1.0, node_total
                     ).astype(np.float32)
    denom = np.maximum(node_total, 1e-6)
    res0 = (r.integers(0, 16, size=(B, N, 3)) / 64.0).astype(np.float32)
    start = r.integers(0, N, size=B).astype(np.int32)
    ext = r.normal(size=(max(n_ext, 1), P, N)).astype(np.float32)
    return [jnp.asarray(x) for x in (scores, req, valid, total, denom, res0,
                                     start, ext)]


@pytest.mark.parametrize("B,P,N,tile_p,tile_n", [
    (1, 37, 200, 8, None),      # one lane; P not a whole number of blocks
    (7, 37, 200, 8, None),      # the cell's seven lanes
    (7, 16, 130, None, None),   # whole batch in one step, N off 128
    (7, 13, 200, 8, 64),        # streamed node tiles, padded P and N
    (1, 13, 130, None, 48),     # streamed, one lane
])
@pytest.mark.parametrize("derive_ok", [True, False])
def test_fleet_kernel_matches_ref_lane_for_lane(B, P, N, tile_p, tile_n,
                                                derive_ok):
    """``sched_commit_fleet`` (interpreted) against ``placement_commit_ref``
    lane by lane: all three families in one call ('both' mode for B = 7),
    an all-infeasible row, dead nodes, the fit mask derived in the kernel
    from ``isfinite(scores)`` or handed in."""
    from repro.kernels.placement_commit.ops import sched_commit_fleet
    from repro.kernels.placement_commit.ref import (placement_commit_ref,
                                                    sched_pref_ref)
    mix = CELL_MIX[:B]
    fam = tuple(f for f, _ in mix)
    dynamic = tuple(d for _, d in mix)
    ext_row, n_ext = [], 0
    for f in fam:
        ext_row.append(n_ext if f == FAM_EXTERNAL else 0)
        n_ext += f == FAM_EXTERNAL
    scores, req, valid, total, denom, res0, start, ext = _fleet_operands(
        B, P, N, n_ext, seed=B * 1000 + P * 10 + N)
    ok = jnp.isfinite(scores)
    node_of, tally = sched_commit_fleet(
        scores, None if derive_ok else ok, req, valid, total, denom, res0,
        start, fam=fam, dynamic=dynamic, ext=ext if n_ext else None,
        ext_row=tuple(ext_row), tile_p=tile_p, tile_n=tile_n)
    for b in range(B):
        pref = sched_pref_ref(scores[b], start[b], fam[b], ext[ext_row[b]])
        want = placement_commit_ref(pref, req[b], ok[b], valid[b], total[b],
                                    denom[b], res0[b], dynamic[b])
        np.testing.assert_array_equal(np.asarray(node_of[b]),
                                      np.asarray(want[0]), err_msg=f"{b}")
        np.testing.assert_array_equal(np.asarray(tally[b]),
                                      np.asarray(want[1]), err_msg=f"{b}")
        assert int(node_of[b, min(3, P - 1)]) == -1
