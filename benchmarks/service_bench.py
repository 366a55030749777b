"""What-if serving benchmark: micro-batched warm serving vs cold CLI runs.

Answers the same batch of single-spec what-if questions three ways:

* **cold CLI** — one ``python -m repro.launch.whatif --replay`` subprocess
  per query, sequentially: every query pays interpreter + jax import,
  tracing/compilation, and replay from window 0. This is what "run a
  what-if" costs without the service.
* **warm sequential** — in-process, one B=1 fleet per query after a warmup
  run: compilation amortised, but queries still run one lane at a time.
* **served** — a warm :class:`repro.service.WhatIfServer` with
  ``max_lanes`` lanes; all queries submitted concurrently and coalesced by
  the micro-batcher into vmapped launches.

While timing, every served report row is compared against the direct
in-process fleet run of the same spec (exact equality — the serving
equivalence contract), and the cold CLI rows' counter columns are checked
against the same truth.

A TPU belongs to one process at a time, so the cold CLI children run
first, before this process initialises a JAX backend (checked); the
``run.py`` suite hook, whose process already holds the device, skips them.

Writes ``BENCH_service.json`` (lanes/sec per mode, speedups, latency
percentiles, batch occupancy). ``--quick`` shrinks the workload for the CI
service-smoke job; ``--check`` fails on an equivalence break or if warm
micro-batched serving beats the sequential cold CLI baseline by less than
2x (the committed run shows well over the 3x acceptance bar — the floor
only absorbs machine noise).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import jax
from jax._src import xla_bridge

from repro.config import REDUCED_SIM
from repro.core import tracegen
from repro.core.precompile import precompile_trace, replay_config
from repro.scenarios import ScenarioFleet, ScenarioSpec
from repro.service import WhatIfQuery, WhatIfServer

REPO = pathlib.Path(__file__).resolve().parent.parent
JSON_PATH = REPO / "BENCH_service.json"

SCHEDULERS = ("greedy", "first_fit")
NUM_KEYS = ("placements", "completions", "evictions", "injected",
            "pending_final", "running_final", "nodes_final")


def query_specs(n):
    """n single-spec questions mixing schedulers and capacity scales."""
    return [ScenarioSpec(name=f"q{i}", scheduler=SCHEDULERS[i % 2],
                         capacity_scale=1.0 - 0.05 * (i // 2))
            for i in range(n)]


def direct_rows(cfg, stack, specs, n_windows, batch_windows):
    """Ground truth: one warm in-process B=1 fleet per spec, timed after a
    throwaway warmup run so only the post-compile cost is measured."""
    def one(spec):
        fleet = ScenarioFleet.from_precompiled(
            cfg, stack, [spec], batch_windows=batch_windows,
            n_windows=n_windows)
        fleet.run()
        return fleet.report()["scenarios"][0]

    one(specs[0])                                   # warm the B=1 program
    t0 = time.time()
    rows = [one(s) for s in specs]
    return rows, time.time() - t0


def cold_cli_rows(stack, specs, n_windows, runs):
    """Sequential cold subprocesses, `runs` of them (each pays full
    startup); lanes/sec extrapolates from the measured per-query cost."""
    rows, wall = [], 0.0
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    for spec in specs[:runs]:
        with tempfile.TemporaryDirectory() as td:
            out = os.path.join(td, "r.json")
            cmd = [sys.executable, "-m", "repro.launch.whatif",
                   "--replay", stack, "--windows", str(n_windows),
                   "--schedulers", spec.scheduler,
                   "--capacity", f"{spec.capacity_scale:g}",
                   "--json", out]
            t0 = time.time()
            subprocess.run(cmd, check=True, env=env, cwd=REPO,
                           stdout=subprocess.DEVNULL)
            wall += time.time() - t0
            with open(out) as f:
                rows.append(json.load(f)["scenarios"][0])
    return rows, wall


def served_rows(cfg, stack, specs, n_windows, batch_windows, max_lanes):
    server = WhatIfServer(cfg, stack, schedulers=SCHEDULERS,
                          max_lanes=max_lanes, max_wait_s=0.05,
                          batch_windows=batch_windows)
    server.start(warm=True)                         # compile outside timing
    t0 = time.time()
    tickets = [server.submit(WhatIfQuery(s, n_windows=n_windows))
               for s in specs]
    results = [t.wait(timeout=600) for t in tickets]
    wall = time.time() - t0
    stats = server.stats()
    server.stop()
    bad = [r.error for r in results if not r.ok()]
    if bad:
        raise RuntimeError(f"served queries failed: {bad}")
    return [r.row for r in results], wall, stats


def rows_equal(a, b):
    return all(a[k] == b[k] for k in NUM_KEYS) and \
        abs(a["cpu_used_frac_mean"] - b["cpu_used_frac_mean"]) < 1e-12


def bench(quick: bool, cold_cli: bool = True):
    n_stack = 64 if quick else 128
    n_windows = 32 if quick else 64
    batch_windows = 32
    n_queries = 8
    cold_runs = 2 if quick else 4
    cfg = REDUCED_SIM
    specs = query_specs(n_queries)

    with tempfile.TemporaryDirectory() as d:
        tracegen.generate_trace(d, n_machines=cfg.max_nodes, n_jobs=200,
                                horizon_windows=n_stack, seed=0,
                                usage_period_us=max(cfg.window_us * 4,
                                                    20_000_000))
        stack = os.path.join(d, "stack.npz")
        precompile_trace(cfg, d, stack, n_stack,
                         start_us=tracegen.SHIFT_US - cfg.window_us,
                         shard_windows=batch_windows)
        cfg = replay_config(stack, cfg)

        crows, cold_wall = [], 0.0
        if cold_cli:
            # the children need the device: run them while this process
            # holds none (trace synthesis and precompile are numpy-only)
            if xla_bridge.backends_are_initialized():
                raise RuntimeError("cold CLI runs need a process that has "
                                   "not initialised a JAX backend")
            crows, cold_wall = cold_cli_rows(stack, specs, n_windows,
                                             cold_runs)
        truth, seq_wall = direct_rows(cfg, stack, specs, n_windows,
                                      batch_windows)
        srows, srv_wall, stats = served_rows(cfg, stack, specs, n_windows,
                                             batch_windows,
                                             max_lanes=n_queries)

    served_ok = all(rows_equal(s, t) for s, t in zip(srows, truth))
    # the CLI auto-names its scenario and recomputes deltas vs itself; the
    # counter columns must still match the in-process truth exactly
    cold_ok = all(all(c[k] == t[k] for k in NUM_KEYS)
                  for c, t in zip(crows, truth))

    out = {
        "meta": {"backend": jax.default_backend(), "quick": quick,
                 "n_stack_windows": n_stack, "query_windows": n_windows,
                 "batch_windows": batch_windows, "queries": n_queries,
                 "max_lanes": n_queries, "schedulers": list(SCHEDULERS),
                 "max_nodes": cfg.max_nodes},
        "warm_sequential": {"wall_s": seq_wall,
                            "lanes_per_s": n_queries / seq_wall},
        "served": {"wall_s": srv_wall,
                   "lanes_per_s": n_queries / srv_wall,
                   "lane_windows_per_s": n_queries * n_windows / srv_wall,
                   "batches": stats["batches"],
                   "occupancy": stats["mean_batch_occupancy"],
                   "latency_p50_s": stats["latency_p50_s"],
                   "latency_p90_s": stats["latency_p90_s"],
                   "latency_p99_s": stats["latency_p99_s"]},
        "speedup_vs_warm_sequential": seq_wall / srv_wall,
        "equivalence": {"served_matches_direct": served_ok},
    }
    if cold_cli:
        cold_per_query = cold_wall / cold_runs
        out["cold_cli"] = {"runs": cold_runs, "per_query_s": cold_per_query,
                           "lanes_per_s": 1.0 / cold_per_query}
        out["speedup_vs_cold_cli"] = cold_per_query / (srv_wall / n_queries)
        out["equivalence"]["cold_cli_matches_direct"] = cold_ok
    return out


def run(rows):
    """run.py suite hook — in-process modes only: the suite's process
    already holds the device, so no cold CLI children."""
    out = bench(quick=True, cold_cli=False)
    per_q = out["served"]["wall_s"] / out["meta"]["queries"] * 1e6
    rows.append(("service_served", per_q, out["served"]["lanes_per_s"]))
    rows.append(("service_warm_seq",
                 out["warm_sequential"]["wall_s"]
                 / out["meta"]["queries"] * 1e6,
                 out["warm_sequential"]["lanes_per_s"]))
    rows.append(("service_speedup_vs_seq", 0.0,
                 out["speedup_vs_warm_sequential"]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="fail on equivalence break or < 2x vs cold CLI")
    ap.add_argument("--platform", default=None,
                    choices=("cpu", "tpu"),
                    help="pin the jax backend (recorded in meta.backend)")
    args = ap.parse_args()
    from repro import env
    env.set_platform(args.platform)
    env.enable_compile_cache()
    out = bench(args.quick)
    print(json.dumps(out, indent=1, sort_keys=True))
    if not args.quick:
        JSON_PATH.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
        print(f"-> {JSON_PATH}", file=sys.stderr)
    if args.check:
        eq = out["equivalence"]
        if not (eq["served_matches_direct"] and eq["cold_cli_matches_direct"]):
            raise SystemExit(f"serving equivalence broken: {eq}")
        if out["speedup_vs_cold_cli"] < 2.0:
            raise SystemExit(
                f"served speedup vs cold CLI "
                f"{out['speedup_vs_cold_cli']:.2f}x < 2x floor")
        print("service bench check OK", file=sys.stderr)


if __name__ == "__main__":
    main()
