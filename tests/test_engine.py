"""Engine behaviour: event application, node churn eviction, accounting,
and hypothesis-driven invariant properties over random event streams."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.config import REDUCED_SIM
from repro.core import engine as eng
from repro.core.ordered import ordered_scatter_add, tree_sum
from repro.core.events import (EventKind, HostEvent, REMOVE_REASON_EVICT,
                               pack_window, stack_windows)
from repro.sched import get_scheduler
from repro.core.state import (TASK_PENDING, TASK_RUNNING, init_state,
                              validate_invariants)

CFG = REDUCED_SIM
KEY = jax.random.PRNGKey(0)


def _node(slot, cpu=1.0, mem=1.0, t=0):
    return HostEvent(t, EventKind.ADD_NODE, slot, a=(cpu, mem, 1.0))


def _task(slot, cpu=0.1, mem=0.1, t=1, prio=0):
    return HostEvent(t, EventKind.ADD_TASK, slot, a=(cpu, mem, 0.0), prio=prio)


def _run(events_per_window, scheduler="greedy"):
    ws = [pack_window(CFG, evs, i) for i, evs in enumerate(events_per_window)]
    state = init_state(CFG)
    state, stats = eng.run_windows(state, jax.tree.map(jnp.asarray,
                                                       stack_windows(ws)),
                                   CFG, get_scheduler(scheduler))
    return state, stats


def test_add_node_and_task_places():
    state, stats = _run([[_node(0), _node(1)], [_task(0)], []])
    assert int(stats["n_running"][-1]) == 1
    assert int(stats["placements"][-1]) == 1
    assert validate_invariants(state, CFG) == {}


def test_remove_task_frees_capacity():
    evs = [[_node(0, cpu=0.2)], [_task(0, cpu=0.15)],
           [HostEvent(0, EventKind.REMOVE_TASK, 0, a=(0.0, 0, 0))],
           [_task(1, cpu=0.15)], []]
    state, stats = _run(evs)
    assert int(stats["n_running"][-1]) == 1
    assert int(stats["completions"][-1]) == 1


def test_capacity_blocks_placement():
    # two tasks that each need 60% of the single node: only one fits
    state, stats = _run([[_node(0, cpu=1.0)],
                         [_task(0, cpu=0.6), _task(1, cpu=0.6)], []])
    assert int(stats["n_running"][-1]) == 1
    assert int(stats["n_pending"][-1]) == 1
    assert validate_invariants(state, CFG) == {}


def test_node_removal_evicts_to_pending():
    evs = [[_node(0), _node(1, cpu=0.01, mem=0.01)], [_task(0, cpu=0.5)],
           [HostEvent(0, EventKind.REMOVE_NODE, 0)], []]
    state, stats = _run(evs)
    assert int(stats["evictions"][-1]) >= 1
    # task went back to pending (node 1 too small to re-place)
    assert int(stats["n_pending"][-1]) == 1
    assert validate_invariants(state, CFG) == {}


def test_evict_reason_counted():
    evs = [[_node(0)], [_task(0)],
           [HostEvent(0, EventKind.REMOVE_TASK, 0,
                      a=(float(REMOVE_REASON_EVICT), 0, 0))], []]
    _, stats = _run(evs)
    assert int(stats["evictions"][-1]) == 1
    assert int(stats["completions"][-1]) == 0


def test_usage_accounting_flows_to_nodes():
    evs = [[_node(0)], [_task(0, cpu=0.4)],
           [HostEvent(0, EventKind.UPDATE_TASK_USED, 0,
                      u=(0.05, 0.02, 0.03, 0.0, 0.0, 0.01, 1.5, 0.03))], []]
    state, stats = _run(evs)
    assert np.isclose(float(state.node_used[0, 0]), 0.05)
    assert np.isclose(float(state.node_reserved[0, 0]), 0.4)
    over = float(stats["overestimate_frac"][-1][0])
    assert 0.8 < over < 0.9          # 0.05/0.4 used -> 87.5% overestimated


def test_constraints_block_node():
    # task requires attr0 == 3; only node 1 has it
    n0 = _node(0)
    n1 = _node(1)
    a1 = HostEvent(0, EventKind.ADD_NODE_ATTR, 1, attr_idx=0, attr_val=3)
    t = HostEvent(1, EventKind.ADD_TASK, 0, a=(0.1, 0.1, 0.0),
                  constraints=[(0, 1, 3)])   # OP_EQ
    state, stats = _run([[n0, n1, a1], [t], []])
    assert int(state.task_node[0]) == 1
    assert validate_invariants(state, CFG) == {}


def test_attr_removal_respected_for_new_tasks():
    n = _node(0)
    a = HostEvent(0, EventKind.ADD_NODE_ATTR, 0, attr_idx=2, attr_val=1)
    rm = HostEvent(0, EventKind.REMOVE_NODE_ATTR, 0, attr_idx=2)
    t = HostEvent(1, EventKind.ADD_TASK, 0, a=(0.1, 0.1, 0.0),
                  constraints=[(2, 1, 1)])
    state, stats = _run([[n, a], [rm], [t], []])
    assert int(stats["n_pending"][-1]) == 1   # constraint now unsatisfiable


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_no_overcommit_random_streams(seed):
    """Random event soup -> engine invariants always hold."""
    r = np.random.default_rng(seed)
    windows = []
    for w in range(6):
        evs = []
        for _ in range(r.integers(0, 20)):
            kind = r.choice([1, 1, 1, 3, 5, 6, 6, 8, 10])
            slot = int(r.integers(0, 16))
            if kind == 6:
                evs.append(_node(slot, cpu=float(r.uniform(0.1, 1))))
            elif kind == 10:
                evs.append(HostEvent(0, EventKind.REMOVE_NODE, slot))
            elif kind == 1:
                cons = ([(int(r.integers(0, 4)), int(r.integers(1, 5)),
                          int(r.integers(0, 3)))] if r.random() < 0.3 else None)
                evs.append(HostEvent(1, EventKind.ADD_TASK, slot,
                                     a=(float(r.uniform(0, 0.5)),
                                        float(r.uniform(0, 0.5)), 0.0),
                                     prio=int(r.integers(0, 11)),
                                     constraints=cons))
            elif kind == 5:
                evs.append(HostEvent(2, EventKind.REMOVE_TASK, slot,
                                     a=(0.0, 0, 0)))
            elif kind == 3:
                evs.append(HostEvent(2, EventKind.UPDATE_TASK_USED, slot,
                                     u=tuple(r.uniform(0, 0.2, 8))))
            elif kind == 8:
                evs.append(HostEvent(0, EventKind.ADD_NODE_ATTR, slot,
                                     attr_idx=int(r.integers(0, 4)),
                                     attr_val=int(r.integers(0, 3))))
        windows.append(evs)
    state, _ = _run(windows)
    assert validate_invariants(state, CFG) == {}


@pytest.mark.parametrize("seed", range(3))
def test_ordered_scatter_add_sums_each_index_once(seed):
    """Each index takes one add, ``acc + sum(its rows)``: exact on
    grid-aligned values, masked and out-of-range rows dropped, and the
    result depends only on each index's own rows in row order (any
    interleaving of the indices gives the same bits)."""
    r = np.random.default_rng(seed)
    N, E, R = 7, 300, 3
    idx = r.integers(-1, N + 1, E).astype(np.int32)
    mask = r.random(E) < 0.8
    keep = mask & (idx >= 0) & (idx < N)

    def run(acc, idx, mask, vals):
        return np.asarray(ordered_scatter_add(
            jnp.asarray(acc), jnp.asarray(idx), jnp.asarray(mask),
            jnp.asarray(vals)))

    # multiples of 2^-10: every partial sum is exact, whatever the order
    acc = (r.integers(0, 1024, (N, R)) / 1024).astype(np.float32)
    vals = (r.integers(-512, 512, (E, R)) / 1024).astype(np.float32)
    want = acc.astype(np.float64)
    np.add.at(want, idx[keep], vals[keep])
    np.testing.assert_array_equal(run(acc, idx, mask, vals), want)

    # values whose sums round: one add per index on top of its row sum,
    # and only the order within an index matters
    acc = r.random((N, R)).astype(np.float32)
    vals = (r.random((E, R)) * 1e-3 - 3e-4).astype(np.float32)
    got = run(acc, idx, mask, vals)
    np.testing.assert_array_equal(
        got, acc + run(np.zeros_like(acc), idx, mask, vals))
    want = acc.astype(np.float64)
    np.add.at(want, idx[keep], vals[keep])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # a random interleaving of the indices; each index's rows stay in order
    tag = np.where(keep, idx, N)
    shuffled = r.permutation(E)
    tag_at = tag[shuffled]
    for k in np.unique(tag):
        shuffled[tag_at == k] = np.nonzero(tag == k)[0]
    np.testing.assert_array_equal(
        run(acc, idx[shuffled], mask[shuffled], vals[shuffled]), got)


def test_tree_sum_is_a_fixed_pairwise_tree():
    """tree_sum adds neighbours level by level (zero-padded to a power of
    two) — bitwise the same tree written out in numpy."""
    r = np.random.default_rng(0)
    for n in (1, 2, 5, 8, 13):
        x = r.random((n, 3)).astype(np.float32)
        lvl = np.concatenate([x, np.zeros((8 * 2 - n, 3), np.float32)])
        lvl = lvl[:1 << max(0, (n - 1).bit_length())]
        while len(lvl) > 1:
            lvl = lvl[0::2] + lvl[1::2]
        np.testing.assert_array_equal(np.asarray(tree_sum(jnp.asarray(x))),
                                      lvl[0])


def test_resync_tallies_bitwise_under_use_kernels():
    """The accounting resync sets tallies that later fits compare against,
    so with ``use_kernels`` it must give the reference's bits: many tasks
    per node and values that round."""
    import dataclasses
    r = np.random.default_rng(0)
    T, N = CFG.max_tasks, CFG.max_nodes
    state = init_state(CFG)
    state = state._replace(
        task_state=jnp.asarray(np.where(r.random(T) < 0.7, TASK_RUNNING,
                                        TASK_PENDING).astype(np.int8)),
        # a quarter of the nodes: dozens of tasks each
        task_node=jnp.asarray(r.integers(-1, N // 4, T).astype(np.int32)),
        task_req=jnp.asarray(r.random(state.task_req.shape, np.float32)
                             * 0.03),
        task_usage=jnp.asarray(r.random(state.task_usage.shape, np.float32)
                               * 0.01))
    placed = np.asarray(state.task_node)
    assert np.bincount(placed[placed >= 0]).max() > 20
    ref = eng.recompute_accounting(state, CFG)
    ker = eng.recompute_accounting(
        state, dataclasses.replace(CFG, use_kernels=True))
    for a, b in ((ker.node_reserved, ref.node_reserved),
                 (ker.node_used, ref.node_used)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
