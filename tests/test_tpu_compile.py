"""Compile the main path's Pallas kernels for a TPU v5e at cell-A widths.

The interpreter that runs the kernels in the rest of the suite cannot see
what the chip's compiler (Mosaic) refuses: block shapes off the (8, 128)
tile, primitives it cannot lower, VMEM overruns. Here each kernel entry is
lowered for a *described* v5e chip — the TPU compiler is installed, no chip
is attached — and compiled; ``repro.kernels.by_platform`` picks the
compiled kernel because the call lowers for a TPU, and the test checks that
the program really holds a Mosaic kernel (``tpu_custom_call``).

Widths are cell A's (``configs/agocs_cell_a.py``): P = sched_batch = 1,024
pending tasks, N = 12,500 nodes, T = 262,144 task slots.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, so describing it while a test
worker imports this file would break the other workers.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_sim_config

CELL_A = get_sim_config("agocs_cell_a")
P, N, T = CELL_A.sched_batch, CELL_A.max_nodes, CELL_A.max_tasks
R, C, K = CELL_A.n_resources, CELL_A.max_constraints, CELL_A.n_attr_slots
U = CELL_A.n_usage_stats


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as the fleet's ('data',) mesh."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.scenarios.batch import FLEET_AXIS
    return Mesh(np.array(topo.devices[:4]), (FLEET_AXIS,),
                axis_types=(AxisType.Auto,))


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _node_shapes(n_lanes=None):
    lead = () if n_lanes is None else (n_lanes,)
    return [(lead + (N, R), jnp.float32)] * 3


def test_constraint_match_compiles(one_chip):
    from repro.kernels.constraint_match.ops import constraint_match

    def fn(req, cons, total, reserved, attrs, active):
        return constraint_match(req, cons, total, reserved, attrs, active,
                                use_kernel=True)

    _compile(fn, one_chip, ((P, R), jnp.float32), ((P, C, 3), jnp.int32),
             ((N, R), jnp.float32), ((N, R), jnp.float32),
             ((N, K), jnp.int32), ((N,), jnp.bool_))


@pytest.mark.parametrize("n_lanes", [None, 4])
def test_window_stats_compiles(one_chip, n_lanes):
    from repro.kernels.window_stats.ops import window_reductions

    def one(state, usage, prio, active, total, resv, used):
        return window_reductions(state, usage, prio, active, total, resv,
                                 used, use_kernel=True)

    fn = one if n_lanes is None else jax.vmap(one)
    lead = () if n_lanes is None else (n_lanes,)
    _compile(fn, one_chip, (lead + (T,), jnp.int8),
             (lead + (T, U), jnp.float32), (lead + (T,), jnp.int32),
             (lead + (N,), jnp.bool_), *_node_shapes(n_lanes))


@pytest.mark.parametrize("stream_n", [None, 8_192])
def test_placement_commit_compiles(one_chip, stream_n):
    from repro.kernels.placement_commit.ops import placement_commit

    def fn(pref, req, ok, valid, total, denom, res0):
        return placement_commit(pref, req, ok, valid, total, denom, res0,
                                True, use_kernel=True, stream_n=stream_n,
                                return_tally=True)

    _compile(fn, one_chip, ((P, N), jnp.float32), ((P, R), jnp.float32),
             ((P, N), jnp.bool_), ((P,), jnp.bool_), *_node_shapes())


def test_sched_pass_streamed_compiles(one_chip):
    from repro.kernels.placement_commit.ops import FAM_NODE_ORDER, sched_pass

    def fn(scores, req, ok, valid, total, denom, res0, start):
        return sched_pass(scores, req, ok, valid, total, denom, res0,
                          family=FAM_NODE_ORDER, start=start,
                          use_kernel=True, tile_n=8_192, return_tally=True)

    _compile(fn, one_chip, ((P, N), jnp.float32), ((P, R), jnp.float32),
             ((P, N), jnp.bool_), ((P,), jnp.bool_), *_node_shapes(),
             ((), jnp.int32))


def test_sched_commit_fleet_mixed_compiles(one_chip):
    """The switchless fleet's fused pass over cell A's four-scheduler mix:
    greedy (scores, dynamic), simulated annealing (external), round-robin
    and first-fit (node order)."""
    from repro.kernels.placement_commit.ops import (FAM_EXTERNAL,
                                                    FAM_NODE_ORDER,
                                                    FAM_SCORES,
                                                    sched_commit_fleet)
    B = 4
    fam = (FAM_SCORES, FAM_EXTERNAL, FAM_NODE_ORDER, FAM_NODE_ORDER)

    def fn(scores, ok, req, valid, total, denom, res0, start, ext):
        return sched_commit_fleet(scores, ok, req, valid, total, denom, res0,
                                  start, fam=fam,
                                  dynamic=(True, False, False, False),
                                  ext=ext, ext_row=(0, 0, 0, 0))

    _compile(fn, one_chip, ((B, P, N), jnp.float32),
             ((B, P, N), jnp.bool_), ((B, P, R), jnp.float32),
             ((B, P), jnp.bool_), *_node_shapes(B), ((B,), jnp.int32),
             ((1, P, N), jnp.float32))


def test_segment_usage_compiles(one_chip):
    from repro.kernels.segment_usage.ops import segment_usage

    def fn(task_node, values, mask):
        return segment_usage(task_node, values, mask, N, use_kernel=True)

    _compile(fn, one_chip, ((T,), jnp.int32), ((T, R), jnp.float32),
             ((T,), jnp.bool_))


# --- the commit's dispatch: the kernel on a TPU whatever use_kernels says --

COMMIT_LOOP = re.compile(r'op_name="[^"]*pass\.commit[^"]*/while')


def _state_shapes(cfg, sharding, lanes=None):
    from repro.core.state import init_state
    lead = () if lanes is None else (lanes,)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                       sharding=sharding),
        jax.eval_shape(lambda: init_state(cfg)))


@pytest.mark.parametrize("mask", ["base_ok", "isfinite"])
def test_finalize_default_config_commits_in_kernel(one_chip, mask):
    """``finalize`` at cell-A widths with the default ``use_kernels=False``,
    lowered for a TPU: the commit is the Mosaic kernel, no scan loop is
    left under ``pass.commit`` — with an explicit feasibility mask and
    with the mask derived from the preference (the greedy call)."""
    from repro.sched.commit import finalize
    assert not CELL_A.use_kernels

    def fn(state, idx, valid, base_ok, pref):
        return finalize(state, CELL_A, idx, valid,
                        base_ok if mask == "base_ok" else None, pref,
                        dynamic_bestfit=True)

    args = [_state_shapes(CELL_A, one_chip)] + [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in (((P,), jnp.int32), ((P,), jnp.bool_),
                     ((P, N), jnp.bool_), ((P, N), jnp.float32))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "placement_commit" in text
    assert not COMMIT_LOOP.search(text)


def _small_fleet():
    """A 4-lane switchless fleet of mixed families at small widths:
    greedy (scores, dynamic), simulated annealing (external), round robin
    and first fit (node order)."""
    from repro.config import REDUCED_SIM
    from repro.sched import snapshot_dispatch
    from repro.sched.table import make_switchless_dispatch
    names = ("greedy", "simulated_annealing", "round_robin", "first_fit")
    table = snapshot_dispatch(names)
    assert table.switchless and not REDUCED_SIM.use_kernels
    return REDUCED_SIM, make_switchless_dispatch(REDUCED_SIM, table,
                                                 (0, 1, 2, 3))


def test_switchless_fleet_default_config_commits_in_kernel(one_chip):
    cfg, dispatch = _small_fleet()
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(dispatch).lower(_state_shapes(cfg, one_chip, 4),
                                   rng).compile().as_text()
    assert "tpu_custom_call" in text
    assert "placement_commit" in text
    assert not COMMIT_LOOP.search(text)


def test_sharded_fleet_dispatch_per_chip_compiles(four_chips):
    """The mesh-sharded mixed fleet, lowered for four v5e chips with its
    lane map: 8 lanes, 2 a chip, two distinct chip maps (greedy + annealing,
    round robin + first fit). The chip's dispatch is one conditional whose
    branches each hold the commit kernel; no branch is vmapped away."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.config import REDUCED_SIM
    from repro.core.events import empty_window
    from repro.scenarios import ScenarioFleet, ScenarioSpec
    from repro.scenarios import batch as batch_mod
    names = ("greedy", "simulated_annealing", "round_robin", "first_fit")
    specs = [ScenarioSpec(name=f"l{i}", scheduler=names[i % 4])
             for i in range(8)]
    probe = ScenarioFleet(REDUCED_SIM, iter(()), specs)
    lane = NamedSharding(four_chips, PartitionSpec(batch_mod.FLEET_AXIS))
    every = NamedSharding(four_chips, PartitionSpec())

    def shapes(tree, sharding, lead=()):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            lead + x.shape, x.dtype, sharding=sharding), tree)

    text = batch_mod.run_scenarios_sharded_jit.lower(
        _state_shapes(REDUCED_SIM, lane, 8),
        shapes(empty_window(REDUCED_SIM), every, (4,)),
        shapes(probe.knobs, lane), REDUCED_SIM, probe.scheduler_names,
        four_chips, 0, has_storm=False, table=probe.dispatch_table,
        lane_scheds=probe._lane_scheds).compile().as_text()
    assert text.count("conditional(") == 1
    assert text.count("tpu_custom_call") >= 2
