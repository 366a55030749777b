"""The harness's mesh path: a cell on four chips runs one process over four
devices, its fleet's lanes sharded over them (four fake CPU devices, in a
subprocess, as ``tests/test_scenarios_sharded.py`` makes them); its
comparison holds every lane, and fails for the bfloat16 control and for
each fault of the sharded timed path; and a cell that asks for more chips
than JAX sees exits 2."""
import functools
import json
import os
import subprocess
import sys
import textwrap

import pytest

import run
from conftest import BENCH, REPO, make_tiny_root

SCHEDULERS = ("greedy", "simulated_annealing", "round_robin", "first_fit")
LANES = 8       # two a chip: 4 schedulers by 0% and 10% outage
FAULTS = ("unchanged", "half_left_out", "altered", "exchange_left_out")


def add_four_chip_cell(root: str):
    """A tiny ``mixed_fleet_28``: lane i runs scheduler i % 4 at
    ``node_outage_frac`` 0.1 * (i // 4), the lanes in order over 4 chips."""
    traffic = os.path.join(root, "bench", "traffic")
    with open(os.path.join(traffic, "tiny_fleet.json")) as f:
        t = json.load(f)
    t.update(name="tiny_fleet_8", lanes=[
        {"scheduler": SCHEDULERS[i % 4], "node_outage_frac": 0.1 * (i // 4)}
        for i in range(LANES)])
    with open(os.path.join(traffic, "tiny_fleet_8.json"), "w") as f:
        json.dump(t, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.fleet.4chip", "config":
                               "tiny_gcd", "traffic": "tiny_fleet_8",
                               "chips": 4, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)


SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
    import jax
    import jax.numpy as jnp
    import numpy as np
    import run
    import test_bench_check as faults
    from repro.scenarios import batch as batch_mod

    restored = []
    plain = run.Runner.restore

    def restore(self, drv, snap, windows_done):
        plain(self, drv, snap, windows_done)
        want = batch_mod.shard_over_fleet(
            jax.tree.map(np.asarray, drv.state), self.mesh)
        restored.append(all(
            x.sharding == w.sharding and len(x.sharding.device_set) == 4
            for x, w in zip(jax.tree.leaves(drv.state),
                            jax.tree.leaves(want))))

    def exchange_left_out(orig):
        # the window batch reaches the first chip's lanes only: the others
        # advance over empty windows
        def step(state, windows, knobs, cfg, names, mesh, *a, **kw):
            keep = faults._copy(state)
            new, stats = orig(state, windows, knobs, cfg, names, mesh,
                              *a, **kw)
            idle = jax.tree.map(jnp.zeros_like, windows)
            lone, lone_stats = orig(keep, idle, knobs, cfg, names, mesh,
                                    *a, **kw)
            B = new.window.shape[0]
            on = jnp.arange(B) < B // mesh.devices.size
            pick = lambda a, b, ax: jnp.where(
                on.reshape((1,) * ax + (B,) + (1,) * (a.ndim - ax - 1)),
                a, b)
            return (jax.tree.map(lambda a, b: pick(a, b, 0), new, lone),
                    jax.tree.map(lambda a, b: pick(a, b, 1), stats,
                                 lone_stats))
        return step

    def cell(**kw):
        return run.run_cell("tiny.fleet.4chip", 2**31 + 11, 0.5, False,
                            root={root!r}, compile_cache=False,
                            t_start=time.time(), **kw)

    out = {{}}
    # the chip's checks on the CPU's devices: a compile inside the window
    # fails the run, as it does on the chip
    info = run.device_info
    run.device_info = lambda jax, chips, tpu, peaks: info(jax, chips, False,
                                                          peaks)
    run.Runner.restore = restore
    out["program"] = cell(require_tpu=True)
    out["restored_lane_sharded"] = restored
    run.Runner.restore = plain
    out["control"] = cell(require_tpu=False, control=True)
    sharded = batch_mod.run_scenarios_sharded_jit
    for name in {faults!r}:
        make = (exchange_left_out if name == "exchange_left_out"
                else getattr(faults, name))
        batch_mod.run_scenarios_sharded_jit = make(sharded)
        out[name] = cell(require_tpu=False)
    batch_mod.run_scenarios_sharded_jit = sharded
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_chip_runs(tmp_path_factory):
    """One process over four fake devices: the program's run, the
    control's, and one run with each fault."""
    root = make_tiny_root(tmp_path_factory.mktemp("tiny"))
    add_four_chip_cell(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(
            bench=BENCH, tests=os.path.dirname(os.path.abspath(__file__)),
            src=os.path.join(REPO, "src"), root=root, faults=FAULTS)],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_cell_runs_on_four_devices(four_chip_runs):
    prog = four_chip_runs["program"]
    assert prog["device"]["count"] == 4
    assert prog["correct"], prog["checks"]
    assert prog["failed"] == 0 and prog["attempted"] % LANES == 0
    assert all(v["value"] == 0 for k, v in prog["checks"].items()
               if k.endswith("_differ"))
    # every restore (warm-up and each trajectory) keeps the lane sharding
    got = four_chip_runs["restored_lane_sharded"]
    assert got and all(got)


def test_four_chip_control_is_not_correct(four_chip_runs):
    ctrl = four_chip_runs["control"]
    assert ctrl["correct"] is False
    assert ctrl["checks"]["decisions_differ"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_sharded_path_is_not_correct(four_chip_runs, fault):
    out = four_chip_runs[fault]
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert [k for k, v in out["checks"].items() if v["value"] > v["limit"]]


def test_four_chip_cell_exits_2_on_fewer_devices(tiny_root, monkeypatch,
                                                 capsys):
    """JAX sees one device here: the cell fails before any set-up, with no
    result line."""
    add_four_chip_cell(tiny_root)
    monkeypatch.setattr(run, "run_cell", functools.partial(
        run.run_cell, root=tiny_root, require_tpu=False,
        compile_cache=False))
    rc = run.main(["--workload", "tiny.fleet.4chip", "--seed", "1",
                   "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "the cell needs 4 chips, JAX sees 1" in captured.err
