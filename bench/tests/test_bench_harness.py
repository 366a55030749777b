"""The harness finds every cell's files by name, runs a cell end to end
at a tiny size on the CPU (chip check off), takes a new cell, or a new
deployment family, from new files alone, and refuses to run without a TPU,
without the program, or without a family it can load."""
import functools
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

import run
from conftest import BENCH, REPO


def _run(root, workload, **kw):
    kw.setdefault("seconds", 0.5)
    return run.run_cell(workload, 2**31 + 99, kw.pop("seconds"), False,
                        root=root, require_tpu=False, compile_cache=False,
                        t_start=time.time(), **kw)


@pytest.mark.parametrize("workload", [w["name"] for w in json.load(
    open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]])
def test_every_cell_resolves_by_name(workload):
    from repro.scenarios import ScenarioSpec
    cell = run.resolve(REPO, workload)
    cfg = run.sim_config(cell.config)
    assert cfg.max_nodes == cell.config["sim"]["max_nodes"]
    specs = [ScenarioSpec(name=str(i), **ln)
             for i, ln in enumerate(run.lane_specs(cell.traffic))]
    assert specs
    assert cell.traffic["steady_windows"] % cell.traffic["batch_windows"] \
        == 0
    assert {m["name"] for m in cell.end_to_end} >= {"sim_speed", "setup_s"}
    for m in cell.per_layer:
        assert callable(run.load_layer(REPO, m["name"]).read)
    assert set(cell.limits) == {"events_differ", "decisions_differ",
                                "tallies_differ", "stats_differ",
                                "stats_gap", "tally_drift"}


def test_benchmark_file_keys():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))


@pytest.mark.parametrize("workload", ["tiny.single", "tiny.fleet"])
def test_tiny_cell_runs_correct(tiny_root, workload):
    out = _run(tiny_root, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"sim_speed", "setup_s"}
    assert out["metrics"]["sim_speed"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert not os.path.exists(os.path.join(tiny_root, "bench", ".cache",
                                           workload + ".npz"))


def test_new_cell_needs_only_new_files(tiny_root):
    """A new traffic file and a new BENCHMARK.json entry make a cell; no
    existing file changes."""
    traffic = os.path.join(tiny_root, "bench", "traffic")
    with open(os.path.join(traffic, "tiny_single.json")) as f:
        t = json.load(f)
    t.update(name="two_lanes", lanes=[{"scheduler": "first_fit"},
                                      {"scheduler": "greedy",
                                       "capacity_scale": 0.7}])
    with open(os.path.join(traffic, "two_lanes.json"), "w") as f:
        json.dump(t, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.two_lanes", "config":
                               "tiny_gcd", "traffic": "two_lanes",
                               "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    out = _run(tiny_root, "tiny.two_lanes")
    assert out["correct"], out["checks"]
    assert out["attempted"] % 2 == 0


TOY_FAMILY = textwrap.dedent('''
    """A toy family: GCD's generator and reference, and one more number,
    ``used_gap``, the widest gap of the usage tally ``node_used`` from the
    reference's (the shared numbers hold that leaf only against a recompute
    from the program's own task table)."""
    import importlib.util
    import os

    import numpy as np

    _spec = importlib.util.spec_from_file_location(
        "toy_gcd", os.path.join(os.path.dirname(__file__), "gcd.py"))
    gcd = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(gcd)
    horizon, rows, run_batch = gcd.horizon, gcd.rows, gcd.run_batch

    STATE_FIELDS = ("node_used",)
    NUMBERS = ("used_gap",)


    def numbers(after, rows, ref_after, ref_rows):
        gap = np.abs(after["node_used"].astype(np.float64)
                     - ref_after["node_used"])
        return {"used_gap": float(gap.max(initial=0.0))}
''')
TOY_MACHINES = 40       # of the tiny deployment's 64 node rows


def _files(root) -> dict:
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            path = os.path.join(d, n)
            if "__pycache__" not in path:
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = f.read()
    return out


def add_toy_family_cell(root, family="toy", source=TOY_FAMILY,
                        limits=True):
    """New files only: ``families/<family>.py``, a deployment of it (the
    tiny one on 40 machines), a two-lane traffic mix, the cell's limits,
    and one more entry of each list in BENCHMARK.json."""
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "configs", "tiny_gcd.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny_toy", family=family,
                cluster=dict(conf["cluster"], n_machines=TOY_MACHINES))
    if family is None:
        del conf["family"]
    with open(os.path.join(bench_dir, "traffic", "tiny_single.json")) as f:
        traffic = json.load(f)
    traffic.update(name="tiny_toy", lanes=[
        {"scheduler": "greedy"},
        {"scheduler": "first_fit", "node_outage_frac": 0.2}])
    new = {"configs/tiny_toy.json": json.dumps(conf),
           "traffic/tiny_toy.json": json.dumps(traffic),
           "families/toy.py": source}
    if limits:
        new["limits/tiny.toy.json"] = json.dumps({"used_gap": 1e-4})
    for rel, text in new.items():
        assert not os.path.exists(os.path.join(bench_dir, rel))
        with open(os.path.join(bench_dir, rel), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_toy", "source": "test", "file":
                             "bench/configs/tiny_toy.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.toy", "config": "tiny_toy",
                               "traffic": "tiny_toy", "chips": 1,
                               "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)


def used_planted(orig):
    """A fault in the toy family's leaf alone: usage on the last node row,
    which no machine of the toy deployment fills (so no shared number,
    which reads live nodes, sees it)."""
    def step(state, *args, **kw):
        new, stats = orig(state, *args, **kw)
        return new._replace(
            node_used=new.node_used.at[..., -1, :].add(0.5)), stats
    return step


@pytest.mark.parametrize("fault", [None, used_planted])
def test_new_family_needs_only_new_files(tiny_root, monkeypatch, fault):
    """A family, a deployment of it, a traffic mix and the cell's limits
    come as new files; beside BENCHMARK.json's new entries no file
    changes. Its number is in the verdict and the result line, and a fault
    in its leaf alone reads not correct."""
    before = _files(tiny_root)
    add_toy_family_cell(tiny_root)
    after = _files(tiny_root)
    assert {k: v for k, v in after.items() if k in before
            and k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    if fault:
        from repro.scenarios import batch
        monkeypatch.setattr(batch, "run_scenarios_jit",
                            fault(batch.run_scenarios_jit))
    out = _run(tiny_root, "tiny.toy")
    checks = out["checks"]
    assert list(checks)[-1] == "used_gap"
    assert checks["used_gap"]["limit"] == 1e-4
    if fault is None:
        assert out["correct"], checks
        assert out["attempted"] % 2 == 0
    else:
        assert out["correct"] is False and out["failed"] >= 1
        assert checks["used_gap"]["value"] >= 0.5
        assert all(v["value"] <= v["limit"] for k, v in checks.items()
                   if k != "used_gap")


@pytest.mark.parametrize("variant,said", [
    (dict(family=None), "names no family"),
    (dict(family="nosuch"), "'nosuch', which has no bench/families/"),
    (dict(family="../families/toy"), "which has no bench/families/"),
    (dict(limits=False), "no limit for ['used_gap']"),
    (dict(source=TOY_FAMILY.replace('STATE_FIELDS = ("node_used",)',
                                    'STATE_FIELDS = ("gpu_milli",)')),
     "the program's state has no ['gpu_milli']"),
    (dict(source=TOY_FAMILY.replace("def numbers(", "def _numbers(")),
     "has no ['numbers']")])
def test_family_at_fault_exits_2(tiny_root, monkeypatch, capsys, variant,
                                 said):
    """A deployment with no family, or one with no module, a number with
    no limit, or a family leaf the program's state lacks: exit code 2 with
    the name, and no result line."""
    add_toy_family_cell(tiny_root, **variant)
    monkeypatch.setattr(run, "run_cell", functools.partial(
        run.run_cell, root=tiny_root, require_tpu=False,
        compile_cache=False))
    rc = run.main(["--workload", "tiny.toy", "--seed", "1", "--seconds",
                   "0.5"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert said in captured.err


def test_layer_reader_sees_the_deployment(tiny_root, monkeypatch):
    """A per-layer reader gets the deployment file as ``ctx["config"]``, so
    that a family's reader can count a kernel's work from its sizes. (A CPU
    trace has no TPU plane to reduce: the reduction is stood in for.)"""
    import xplane
    with open(os.path.join(tiny_root, "bench", "layers",
                           "toy.machines.py"), "w") as f:
        f.write('def read(ctx):\n'
                '    return ctx["config"]["cluster"]["n_machines"]\n')
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"] = [{"name": "toy.machines", "unit": "nodes",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "sim_speed"}]
    with open(path, "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(xplane, "reduce_dir", lambda d: {
        "busy_s": 1.0, "window_s": 1.0, "top_ops": [], "top_gaps": []})
    out = run.run_cell("tiny.single", 2**31 + 99, 0.5, True, root=tiny_root,
                       require_tpu=False, compile_cache=False,
                       t_start=time.time())
    assert out["correct"], out["checks"]
    assert out["metrics"] == {"toy.machines": {"value": 64,
                                               "unit": "nodes"}}


def test_cell_on_four_chips_is_refused(tiny_root):
    """JAX sees one device here: a four-chip cell fails rather than timing
    its fleet on fewer chips under its name."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append(dict(bench["workloads"][1], name="tiny.4chip",
                                   chips=4))
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(run.BenchError, match="4 chips"):
        _run(tiny_root, "tiny.4chip")


def test_missing_program_hook_fails_loudly(monkeypatch):
    """The private names of the program the harness relies on are checked,
    so a renamed hook fails the run instead of dropping its spans."""
    from repro.core import pipeline
    run.spanned_class(pipeline.Simulation, run.Spans())
    monkeypatch.delattr(pipeline.WindowedDriver, "_resync")
    monkeypatch.delattr(pipeline.Simulation, "_resync")
    with pytest.raises(run.BenchError, match="_resync"):
        run.spanned_class(pipeline.Simulation, run.Spans())
    with pytest.raises(run.BenchError, match="_thread"):
        run.need(object(), "_thread")


def test_no_tpu_exits_nonzero(capsys):
    rc = run.main(["--workload", "cell_a.greedy.backlog", "--seed", "1",
                   "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "cell_a.greedy.backlog", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_compile_counter_counts_the_runs_thread_only():
    import threading
    import jax
    import jax.numpy as jnp
    with run.CompileCounter() as counter:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        mine = counter.n
        other = threading.Thread(target=lambda: jax.jit(lambda x: x - 5)(
            jnp.arange(9.0)).block_until_ready())
        other.start()
        other.join(60)
    assert not other.is_alive()
    assert mine > 0 and counter.n == mine
    jax.jit(lambda x: x * 7)(jnp.arange(5.0)).block_until_ready()
    assert counter.n == mine            # unregistered on exit
