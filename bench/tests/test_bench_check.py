"""The comparison that decides ``correct`` fails when the timed path is
broken underneath a whole run (the chip check skipped, tiny cells on the
CPU), and when the bfloat16 control stands in the program's place; a
family's own leaves reach its reference."""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run


def _run(root, workload, **kw):
    return run.run_cell(workload, 2**31 + 5, 0.5, False, root=root,
                        require_tpu=False, compile_cache=False,
                        t_start=time.time(), **kw)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def unchanged(orig):
    """A step that returns the state it was given."""
    def step(state, *args, **kw):
        keep = _copy(state)
        _, stats = orig(state, *args, **kw)
        return keep, stats
    return step


def half_left_out(orig):
    """Half of the batch left out: the first half of the windows of a
    trajectory's launch, or the first half of a fleet's lanes."""
    def step(state, windows, *args, **kw):
        lanes = np.ndim(state.window)
        if not lanes:
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], windows)
            return orig(state, half, *args, **kw)
        keep = _copy(state)
        new, stats = orig(state, windows, *args, **kw)
        B = keep.window.shape[0]
        on = jnp.arange(B) < B // 2
        return jax.tree.map(
            lambda n, k: jnp.where(on.reshape((B,) + (1,) * (n.ndim - 1)),
                                   n, k), new, keep), stats
    return step


def altered(orig):
    """One answer altered where it is produced: task slot 0's node."""
    def step(state, *args, **kw):
        new, stats = orig(state, *args, **kw)
        return new._replace(task_node=new.task_node.at[..., 0].add(1)), stats
    return step


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered])
@pytest.mark.parametrize("workload,target", [
    ("tiny.single", "repro.core.engine.run_windows_jit"),
    ("tiny.fleet", "repro.scenarios.batch.run_scenarios_jit")])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault,
                                          workload, target):
    import importlib
    mod_name, attr = target.rsplit(".", 1)
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, fault(getattr(mod, attr)))
    out = _run(tiny_root, workload)
    assert out["correct"] is False
    assert out["failed"] >= 1
    over = [k for k, v in out["checks"].items() if v["value"] > v["limit"]]
    assert over


@pytest.mark.parametrize("workload", ["tiny.single", "tiny.fleet"])
def test_control_is_not_correct(tiny_root, workload):
    out = _run(tiny_root, workload, control=True)
    assert out["correct"] is False
    assert out["checks"]["decisions_differ"]["value"] > 0


def test_drift_reads_a_bad_tally():
    T, N = 64, 8
    s = {"task_state": np.full((1, T), 2, np.int8),
         "task_node": np.arange(T).reshape(1, T) % N,
         "task_req": np.full((1, T, 3), 0.01, np.float32),
         "task_usage": np.zeros((1, T, 8), np.float32),
         "node_total": np.ones((1, N, 3), np.float32),
         "node_active": np.ones((1, N), bool)}
    s["node_reserved"] = np.full((1, N, 3), 0.08, np.float32)
    s["node_used"] = np.zeros((1, N, 3), np.float32)
    assert check.drift(s) < 1e-6
    s["node_reserved"][0, 3, 1] += 1e-3
    assert check.drift(s) == pytest.approx(1e-3, rel=1e-3)


def test_drift_reads_a_node_outside_the_table():
    """A running task on no node of the table: a whole capacity, not a
    crash of the check."""
    T, N = 16, 8
    s = {"task_state": np.full((1, T), 2, np.int8),
         "task_node": np.arange(T).reshape(1, T) % N,
         "task_req": np.full((1, T, 3), 0.01, np.float32),
         "task_usage": np.zeros((1, T, 8), np.float32),
         "node_total": np.ones((1, N, 3), np.float32),
         "node_active": np.ones((1, N), bool)}
    s["node_reserved"] = np.full((1, N, 3), 0.02, np.float32)
    s["node_used"] = np.zeros((1, N, 3), np.float32)
    assert check.drift(s) < 1e-6
    s["task_node"][0, 0] = N
    assert check.drift(s) == 1.0


def test_family_leaves_reach_its_reference():
    """A leaf a family lists beyond the shared ones is read from the state
    by name and handed to the family's reference with the configuration's
    numbers, in the precision asked for."""
    from repro.config import SimConfig
    seen = []

    def run_batch(state, windows, seed, lane, *, dims, dtype):
        seen.append((sorted(state), lane, dims["max_nodes"], dtype))
        return state, {}

    family = types.SimpleNamespace(STATE_FIELDS=("gpu_free",),
                                   run_batch=run_batch)
    state = {f: np.zeros((2, 3)) for f in check.state_fields(family)}
    assert check.state_fields(family)[-1] == "gpu_free"
    lanes = [{"scheduler": "greedy"}, {"scheduler": "first_fit"}]
    outs = check.reference_lanes(family, SimConfig(max_nodes=3), lanes,
                                 state, {"kind": np.zeros((4, 5))}, 7,
                                 dtype=jnp.bfloat16)
    assert len(outs) == 2
    assert [s[1] for s in seen] == lanes
    for keys, _, nodes, dtype in seen:
        assert "gpu_free" in keys and "node_used" in keys
        assert nodes == 3 and dtype == jnp.bfloat16
