"""AGOCS simulation driver — the paper's stand-alone simulator server.

  # synthetic GCD-schema trace, 12.5K-node cell scaled down:
  PYTHONPATH=src python -m repro.launch.simulate --nodes 256 --jobs 400 \
      --windows 200 --scheduler greedy

  # from a GCD-format trace directory (real or generated):
  PYTHONPATH=src python -m repro.launch.simulate --trace-dir /data/gcd \
      --windows 1000 --scheduler simulated_annealing

  # §V-A pre-compiled replay:
  ... --precompile /tmp/events.npz
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

import numpy as np

from repro.config import SimConfig, REDUCED_SIM
from repro.configs import get_sim_config
from repro.core import precompile as precompile_mod
from repro.core import trace, tracegen
from repro.core.pipeline import Simulation
from repro.core.snapshot import save_snapshot
from repro.core.state import validate_invariants
from repro import parsers as trace_parsers


def build_cfg(args) -> SimConfig:
    cfg = get_sim_config() if args.cell_a else REDUCED_SIM
    over = {}
    if args.nodes:
        over["max_nodes"] = args.nodes
    if args.tasks:
        over["max_tasks"] = args.tasks
    if args.scheduler:
        over["scheduler"] = args.scheduler
    if args.speed_factor:
        over["speed_factor"] = args.speed_factor
    if args.use_kernels:
        over["use_kernels"] = True
    if args.stats_stride != 1:      # 0/negative hit SimConfig's validator
        over["stats_stride"] = args.stats_stride
    if args.nodes and not args.tasks:
        over["max_tasks"] = max(args.nodes * 16, 512)
    if not args.cell_a:
        over.setdefault("max_events_per_window", 4096)
        over.setdefault("sched_batch", 256)
    return dataclasses.replace(cfg, **over)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--trace-family", default="gcd",
                    help="trace parser family (see --list-families); "
                         "synthetic traces are generated in this schema too")
    ap.add_argument("--list-families", action="store_true",
                    help="print the trace-parser registry and exit")
    ap.add_argument("--cell-a", action="store_true",
                    help="the paper's 12.5K-node Google cell configuration")
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--tasks", type=int, default=None)
    ap.add_argument("--jobs", type=int, default=400)
    ap.add_argument("--windows", type=int, default=200)
    ap.add_argument("--scheduler", default="greedy",
                    help="any repro.sched registry name (plugins included)")
    ap.add_argument("--list-schedulers", action="store_true",
                    help="print the scheduler registry and exit")
    ap.add_argument("--speed-factor", type=float, default=0.0)
    ap.add_argument("--use-kernels", action="store_true",
                    help="Pallas kernels (compiled on a TPU, interpreted "
                         "on the CPU)")
    ap.add_argument("--stats-stride", type=int, default=1,
                    help="emit a stats row every k-th window (headless "
                         "sweeps; skipped windows pay zero stats cost, "
                         "cumulative counters lose nothing)")
    ap.add_argument("--precompile", default=None,
                    help="path: pre-compile events to npz then replay (§V-A)")
    ap.add_argument("--snapshot", default=None,
                    help="write a pausable snapshot here at the end")
    ap.add_argument("--batch-windows", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-dir", default=None,
                    help="write a profiler trace of the run here (open it "
                         "in TensorBoard's profile plugin or Perfetto)")
    ap.add_argument("--platform", default=None,
                    choices=("cpu", "tpu"),
                    help="pin the jax backend (default: auto-detect)")
    args = ap.parse_args(argv)

    from repro import env
    env.set_platform(args.platform)
    env.enable_compile_cache()

    if args.list_schedulers:
        from repro.sched import describe_schedulers
        print(describe_schedulers())
        raise SystemExit(0)
    if args.list_families:
        print(trace_parsers.describe_parsers())
        raise SystemExit(0)
    family = args.trace_family
    parser_cls = trace_parsers.get_parser(family)      # fail fast on typos

    cfg = build_cfg(args)
    tmp = None
    trace_dir = args.trace_dir
    if trace_dir is None:
        tmp = tempfile.TemporaryDirectory()
        trace_dir = tmp.name
        t0 = time.time()
        if family == "openb":
            from repro.parsers.alibaba_openb import generate_openb_trace
            summary = generate_openb_trace(
                trace_dir, n_nodes=cfg.max_nodes, n_pods=args.jobs * 4,
                horizon_s=int(args.windows * cfg.window_us / 1e6),
                seed=args.seed)
        else:
            summary = tracegen.generate_trace(
                trace_dir, n_machines=cfg.max_nodes, n_jobs=args.jobs,
                horizon_windows=args.windows, seed=args.seed,
                usage_period_us=max(cfg.window_us * 4, 20_000_000))
        print(f"generated {family}-schema trace: {summary} "
              f"({time.time()-t0:.1f}s)")

    start_us = trace_parsers.default_start_us(family, cfg)
    t0 = time.time()
    if args.precompile:
        n = precompile_mod.precompile_trace(cfg, trace_dir, args.precompile,
                                            args.windows, start_us=start_us,
                                            family=family)
        print(f"pre-compiled {n} windows -> {args.precompile} "
              f"({time.time()-t0:.1f}s)")
        warn = precompile_mod.overflow_warning(
            precompile_mod.stack_parse_stats(args.precompile))
        if warn:
            print(warn)
        source = precompile_mod.replay_single_windows(args.precompile)
        parser = None
    else:
        parser = parser_cls(cfg, trace_dir)
        source = parser.packed_windows(args.windows, start_us=start_us)

    # the profiler covers the driver from its start: its feed thread
    # begins filling as soon as it is built
    with trace.profile(args.profile_dir):
        sim = Simulation(cfg, source, scheduler=args.scheduler,
                         batch_windows=args.batch_windows, seed=args.seed)
        t0 = time.time()
        state = sim.run()
        wall = time.time() - t0
    sf = sim.stats_frame()
    sim_seconds = sim.windows_done * cfg.window_us / 1e6
    print(f"simulated {sim.windows_done} windows ({sim_seconds:.0f} sim-s) "
          f"in {wall:.2f}s wall -> speed factor {sim_seconds / wall:.1f}x")
    print(json.dumps({
        "scheduler": args.scheduler,
        "n_running_final": int(sf["n_running"][-1]),
        "n_pending_final": int(sf["n_pending"][-1]),
        "placements": int(sf["placements"][-1]),
        "completions": int(sf["completions"][-1]),
        "evictions": int(sf["evictions"][-1]),
        "cpu_reserved_frac": float(sf["reserved_frac"][-1][0]),
        "cpu_used_frac": float(sf["used_frac"][-1][0]),
        "overestimate_frac": float(sf["overestimate_frac"][-1][0]),
        "util_balance_var": float(sf["util_balance_var"][-1]),
    }, indent=1))
    problems = validate_invariants(state, cfg)
    print("invariants:", problems or "OK")
    if parser is not None:
        print("parser:", parser.stats)
        warn = precompile_mod.overflow_warning(parser.stats)
        if warn:
            print(warn)
    if args.snapshot:
        save_snapshot(args.snapshot, state, cfg, sim.windows_done)
        print(f"snapshot -> {args.snapshot}")
    if tmp:
        tmp.cleanup()
    return sf


if __name__ == "__main__":
    main()
