"""The harness finds every cell's files by name, runs a cell end to end
at a tiny size on the CPU (chip check off), takes a new cell from new
files alone, and refuses to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys
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
