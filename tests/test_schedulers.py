"""Scheduler suite: every scheduler respects capacity + constraints, honours
priority, and the meta-heuristics (SA/GA) are deterministic under a fixed key."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import REDUCED_SIM
from repro.core import engine as eng
from repro.core.events import EventKind, HostEvent, pack_window, stack_windows
from repro.sched import SCHEDULERS, get_scheduler
from repro.core.state import TASK_RUNNING, init_state, validate_invariants

CFG = REDUCED_SIM


def _mk_state(n_nodes=8, n_tasks=24, seed=0, with_constraints=True):
    r = np.random.default_rng(seed)
    evs0 = [HostEvent(0, EventKind.ADD_NODE, i,
                      a=(float(r.uniform(0.4, 1.0)),
                         float(r.uniform(0.4, 1.0)), 1.0))
            for i in range(n_nodes)]
    evs0 += [HostEvent(0, EventKind.ADD_NODE_ATTR, i, attr_idx=0,
                       attr_val=int(r.integers(0, 3))) for i in range(n_nodes)]
    evs1 = []
    for t in range(n_tasks):
        cons = ([(0, 1, int(r.integers(0, 3)))]
                if with_constraints and r.random() < 0.4 else None)
        evs1.append(HostEvent(1, EventKind.ADD_TASK, t,
                              a=(float(r.uniform(0.02, 0.3)),
                                 float(r.uniform(0.02, 0.3)), 0.0),
                              prio=int(r.integers(0, 12)), constraints=cons))
    ws = [pack_window(CFG, evs0, 0), pack_window(CFG, evs1, 1)]
    return jax.tree.map(jnp.asarray, stack_windows(ws))


@pytest.mark.parametrize("name", sorted(SCHEDULERS))
def test_scheduler_invariants(name):
    windows = _mk_state()
    state = init_state(CFG)
    state, stats = eng.run_windows(state, windows, CFG, get_scheduler(name))
    assert validate_invariants(state, CFG) == {}, name
    assert int(stats["placements"][-1]) > 0, f"{name} placed nothing"


@pytest.mark.parametrize("name", ["simulated_annealing", "genetic", "random"])
def test_stochastic_schedulers_deterministic_under_key(name):
    windows = _mk_state()
    outs = []
    for _ in range(2):
        state = init_state(CFG)
        state, stats = eng.run_windows(state, windows, CFG,
                                       get_scheduler(name), seed=42)
        outs.append(np.asarray(state.task_node))
    assert np.array_equal(outs[0], outs[1])


def test_priority_order_respected():
    """When capacity suffices for only one task, the high-priority one wins."""
    evs0 = [HostEvent(0, EventKind.ADD_NODE, 0, a=(0.5, 0.5, 1.0))]
    evs1 = [HostEvent(1, EventKind.ADD_TASK, 0, a=(0.4, 0.1, 0.0), prio=1),
            HostEvent(1, EventKind.ADD_TASK, 1, a=(0.4, 0.1, 0.0), prio=9)]
    ws = jax.tree.map(jnp.asarray, stack_windows(
        [pack_window(CFG, evs0, 0), pack_window(CFG, evs1, 1)]))
    state = init_state(CFG)
    state, _ = eng.run_windows(state, ws, CFG, get_scheduler("greedy"))
    assert int(state.task_state[1]) == TASK_RUNNING     # prio 9 placed
    assert int(state.task_node[0]) == -1                # prio 1 waits


def test_best_fit_prefers_tight_node():
    evs0 = [HostEvent(0, EventKind.ADD_NODE, 0, a=(1.0, 1.0, 1.0)),
            HostEvent(0, EventKind.ADD_NODE, 1, a=(0.15, 0.15, 1.0))]
    evs1 = [HostEvent(1, EventKind.ADD_TASK, 0, a=(0.1, 0.1, 0.0))]
    ws = jax.tree.map(jnp.asarray, stack_windows(
        [pack_window(CFG, evs0, 0), pack_window(CFG, evs1, 1)]))
    state = init_state(CFG)
    state, _ = eng.run_windows(state, ws, CFG, get_scheduler("greedy"))
    assert int(state.task_node[0]) == 1                 # tighter node


def test_first_fit_prefers_low_index():
    evs0 = [HostEvent(0, EventKind.ADD_NODE, i, a=(1.0, 1.0, 1.0))
            for i in range(4)]
    evs1 = [HostEvent(1, EventKind.ADD_TASK, 0, a=(0.1, 0.1, 0.0))]
    ws = jax.tree.map(jnp.asarray, stack_windows(
        [pack_window(CFG, evs0, 0), pack_window(CFG, evs1, 1)]))
    state = init_state(CFG)
    state, _ = eng.run_windows(state, ws, CFG, get_scheduler("first_fit"))
    assert int(state.task_node[0]) == 0


def test_vmapped_scheduler_replicas():
    """The paper's use case: N schedulers consume one workload concurrently —
    here via vmap over PRNG keys (random scheduler -> different placements,
    same invariants)."""
    windows = _mk_state(with_constraints=False)
    state = init_state(CFG)

    def run_one(seed):
        s, stats = eng.run_windows(state, windows, CFG,
                                   get_scheduler("random"), seed=seed)
        return s.task_node, stats["placements"][-1]

    nodes, placements = jax.vmap(run_one)(jnp.arange(4))
    assert placements.shape == (4,)
    assert (placements > 0).all()
    # different seeds -> not all identical placements
    assert not bool(jnp.all(nodes[0] == nodes[1]))


def test_pending_batch_order_is_fully_determined():
    """Pending slots by priority descending, equal priorities in slot
    order, then non-pending slots in slot order — no tie left for top_k
    to break (the TPU breaks ties differently from the CPU)."""
    from repro.core.state import TASK_PENDING
    from repro.sched.base import pending_batch
    r = np.random.default_rng(0)
    T, P = CFG.max_tasks, CFG.sched_batch
    st = init_state(CFG)
    task_state = r.choice([0, TASK_PENDING, TASK_RUNNING], T).astype(np.int8)
    prio = r.integers(0, 3, T).astype(np.int32)          # many ties
    st = st._replace(task_state=jnp.asarray(task_state),
                     task_prio=jnp.asarray(prio))
    idx, valid = pending_batch(st, CFG)
    pend = task_state == TASK_PENDING
    slots = np.arange(T)
    want = np.lexsort((slots, -np.where(pend, prio + 1, 0)))[:P]
    np.testing.assert_array_equal(np.asarray(idx), want)
    np.testing.assert_array_equal(np.asarray(valid), pend[want])
