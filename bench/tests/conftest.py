"""Fixtures of the benchmark's CPU tests: a checkout-shaped root holding a
tiny GCD-model deployment and cells on it."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SIM = {"max_nodes": 64, "max_tasks": 2048, "max_events_per_window": 256,
            "window_us": 5000000, "n_resources": 3, "n_usage_stats": 8,
            "n_attr_slots": 16, "max_constraints": 6, "sched_batch": 64,
            "resync_windows": 16, "buffer_windows": 64}


def tiny_files() -> dict:
    """{relative path: JSON object} of the tiny root."""
    gcd = json.load(open(os.path.join(BENCH, "configs", "gcd_cell_a.json")))
    gcd.pop("program_config")
    gcd.update(name="tiny_gcd", sim=TINY_SIM)
    gcd["cluster"] = dict(gcd["cluster"], n_machines=64)
    single = json.load(open(os.path.join(BENCH, "traffic",
                                         "greedy_backlog.json")))
    # core/tracegen.py's own tail of tasks per job (Pareto 1.2 capped at
    # 64), so that no window of a tiny cell holds more than its 256 rows
    single.update(name="tiny_single", standing_tasks=900, jobs_per_hour=4000,
                  tasks_per_job_pareto=1.2, max_tasks_per_job=64,
                  batch_windows=8, steady_windows=32)
    fleet = dict(single, name="tiny_fleet", lanes=[
        {"scheduler": "greedy"},
        {"scheduler": "simulated_annealing", "node_outage_frac": 0.2},
        {"scheduler": "round_robin", "capacity_scale": 0.5},
        {"scheduler": "first_fit", "node_outage_frac": 0.1}])
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"] = [
        {"name": "tiny_gcd", "source": "test", "file":
         "bench/configs/tiny_gcd.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.single", "config": "tiny_gcd", "traffic":
         "tiny_single", "chips": 1, "why": "test"},
        {"name": "tiny.fleet", "config": "tiny_gcd", "traffic": "tiny_fleet",
         "chips": 1, "why": "test"}]
    return {"BENCHMARK.json": bench,
            "bench/configs/tiny_gcd.json": gcd,
            "bench/traffic/tiny_single.json": single,
            "bench/traffic/tiny_fleet.json": fleet}


def make_tiny_root(root) -> str:
    """Write the tiny root's files and the benchmark's families, readers
    and limits under the directory ``root``."""
    for rel, obj in tiny_files().items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    for d in ("families", "layers", "limits"):
        shutil.copytree(os.path.join(BENCH, d), root / "bench" / d)
    return str(root)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
