#!/usr/bin/env python3
"""Bring-up smoke run: the cell-A simulator on one TPU chip, end to end.

Drives the system's normal entry points once, in one process, at the
paper's cell A (``configs/agocs_cell_a.py``: 12,500 nodes, 262,144 task
slots, 8,192 events per window, sched_batch 1,024):

  trace   a seeded GCD-schema cell-A slice (``tracegen.generate_paper_
          scale_trace``) that opens on the cell's standing workload of
          ~140K tasks, so the schedulers work through a deep backlog ->
          the ``gcd`` parser of the registry -> ``precompile_trace`` into
          a chunked stack under ``.chip_smoke/``;
  a       one greedy trajectory on the reference path (``Simulation``, as
          ``launch/simulate`` runs it), then ``validate_invariants``;
  b       the same trajectory with ``use_kernels=True`` (compiled Pallas
          kernels): every decision bitwise equal to a. The placement commit
          is the compiled kernel on a TPU whatever ``use_kernels`` says, so
          this a/b comparison no longer covers it: the commit's on-chip
          check is ``bench/reference.py`` (the benchmark's ``correct``),
          and phase c's chip-versus-CPU comparison, whose CPU side runs
          the jnp reference commit;
  c       chip versus CPU in this process: the first windows of a again on
          ``jax.devices("cpu")[0]``; and a fleet with one lane per built-in
          scheduler, run on the chip to the end, whose last windows run
          again on the CPU from the chip's snapshot of the loaded cluster
          (an accounting resync included) — decisions bitwise equal, float
          stats within ``FLOAT_RTOL``;
  d       a mixed-scheduler ``ScenarioFleet.from_precompiled`` fleet, its
          lane count B sized from the compiled program's
          ``memory_analysis()`` against the device's ``bytes_limit``; lane 0
          (greedy, no perturbation) bitwise equal to a;
  e       a ``WhatIfServer`` answering queries from window 0 and from a fork
          point, each equal to the matching lane of d.

Each phase prints one line: windows, placements, completions, evictions,
compile and run seconds apart, and device memory. The run fails (non-zero
exit, no result line) on any error, any inequality, or when JAX finds no
TPU. The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

  python3 chip_smoke.py             # phases a-e, one chip
  python3 chip_smoke.py --chips 4   # only the four-chip sharded fleet
                                    # against the same lanes on one chip

These are one-off wall-clock readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, ".chip_smoke")     # trace, stack, snapshot

WINDOWS = 128            # trace slice and trajectory length
CPU_WINDOWS = 16         # phase c replays this many windows on the CPU
BATCH = 16               # windows per device launch, every phase alike
FORK_EVERY = 32          # phase e fork-point cadence
SEED = 0
FLEET_MIX = ("greedy", "simulated_annealing", "round_robin", "first_fit")
SERVED = ("greedy", "round_robin", "first_fit")
MAX_FLEET_LANES = 16
# chip versus CPU, kernel versus reference: float columns are sums and
# quotients whose rounding order may differ between backends; decisions
# and every integer column must still agree bitwise
FLOAT_RTOL = 1e-4
FLOAT_ATOL = 1e-6
DECISION_FIELDS = ("task_state", "task_node", "placements", "completions",
                   "evictions", "overflow_drops")


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(msg, flush=True)


def _gb(n) -> str:
    return f"{n / 2**30:.2f}GB"


def _mem_line(dev, compiled=None) -> str:
    parts = []
    if compiled is not None:
        ma = compiled.memory_analysis()
        parts.append(f"args={_gb(ma.argument_size_in_bytes)} "
                     f"temp={_gb(ma.temp_size_in_bytes)}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        parts.append(f"peak_in_use={_gb(stats['peak_bytes_in_use'])}")
    return " ".join(parts)


def _program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def _host(tree):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


def _decisions(state) -> dict:
    s = _host(state)
    return {k: getattr(s, k) for k in DECISION_FIELDS}


def occupancy(s) -> str:
    """Running tasks and the most on one node, per lane of a host state."""
    import numpy as np
    from repro.core.state import TASK_RUNNING
    state = np.atleast_2d(s.task_state)
    node = np.atleast_2d(s.task_node)
    run, most = [], []
    for st, nd in zip(state, node):
        placed = nd[(st == TASK_RUNNING) & (nd >= 0)]
        run.append(int(placed.size))
        most.append(int(np.bincount(placed).max(initial=0)))
    if len(run) == 1:
        return f"running={run[0]} max_tasks_per_node={most[0]}"
    return f"running={run} max_tasks_per_node={most}"


def check_decisions(got: dict, want: dict, what: str):
    import numpy as np
    bad = [f"{k} ({int(np.sum(got[k] != want[k]))} differ)"
           for k in DECISION_FIELDS if not np.array_equal(got[k], want[k])]
    if bad:
        raise SmokeFailure(f"{what}: decisions differ in {bad}")


def check_frames(got: dict, want: dict, what: str) -> float:
    """Integer stats columns bitwise, float columns within FLOAT_RTOL /
    FLOAT_ATOL; returns the largest relative float difference."""
    import numpy as np
    if got.keys() != want.keys():
        raise SmokeFailure(f"{what}: stats keys differ")
    worst = 0.0
    # integer columns (counts, populations) first: they localise a decision
    # difference to its first window
    for k in sorted(want, key=lambda k: np.asarray(want[k]).dtype.kind
                    not in "iub"):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.shape != b.shape:
            raise SmokeFailure(f"{what}: {k} shape {a.shape} != {b.shape}")
        if a.dtype.kind in "iub":
            if not np.array_equal(a, b):
                rows = np.nonzero((a != b).reshape(len(a), -1).any(-1))[0]
                raise SmokeFailure(f"{what}: integer column {k} differs "
                                   f"from row {rows[0]} on ({len(rows)} "
                                   "rows)")
            continue
        if not np.allclose(a, b, rtol=FLOAT_RTOL, atol=FLOAT_ATOL):
            off = ~np.isclose(a, b, rtol=FLOAT_RTOL, atol=FLOAT_ATOL)
            first = np.argwhere(off)[0]
            raise SmokeFailure(
                f"{what}: float column {k} outside rtol={FLOAT_RTOL} "
                f"atol={FLOAT_ATOL} at {tuple(int(i) for i in first)}: "
                f"{a[tuple(first)]!r} vs {b[tuple(first)]!r} "
                f"({int(off.sum())} elements)")
        den = np.maximum(np.abs(b), FLOAT_ATOL)
        worst = max(worst, float(np.max(np.abs(a - b) / den, initial=0.0)))
    return worst


def check_row(got: dict, want: dict, what: str):
    import numpy as np
    if got.keys() != want.keys():
        raise SmokeFailure(f"{what}: report keys differ")
    for k, v in want.items():
        g = got[k]
        if isinstance(v, float):
            if not np.isclose(g, v, rtol=FLOAT_RTOL, atol=FLOAT_ATOL):
                raise SmokeFailure(f"{what}: report {k} {g!r} != {v!r}")
        elif g != v:
            raise SmokeFailure(f"{what}: report {k} {g!r} != {v!r}")


# ---------------------------------------------------------------------------
# set-up: trace -> parser registry -> precompiled chunked stack
# ---------------------------------------------------------------------------

def build_stack(n_windows: int, standing_tasks: int):
    from repro import parsers
    from repro.configs import get_sim_config
    from repro.core import tracegen
    from repro.core.precompile import (overflow_warning, precompile_trace,
                                       stack_parse_stats, validate_replay)
    cfg = get_sim_config("agocs_cell_a")
    trace_dir = os.path.join(OUT_DIR, "trace")
    stack = os.path.join(OUT_DIR, "cell_a.npz")
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.time()
    summary = tracegen.generate_paper_scale_trace(
        trace_dir, horizon_windows=n_windows, seed=SEED,
        standing_tasks=standing_tasks)
    parsers.get_parser("gcd")                       # the registry entry
    n = precompile_trace(cfg, trace_dir, stack, n_windows,
                         start_us=parsers.default_start_us("gcd", cfg),
                         shard_windows=BATCH, family="gcd")
    validate_replay(stack, cfg, verify=True)
    warn = overflow_warning(stack_parse_stats(stack))
    if warn:
        raise SmokeFailure(warn)
    log(f"[setup] cell A: nodes={cfg.max_nodes} task_slots={cfg.max_tasks} "
        f"events/window={cfg.max_events_per_window} "
        f"sched_batch={cfg.sched_batch}; trace machines="
        f"{summary.n_machines} jobs={summary.n_jobs} tasks={summary.n_tasks} "
        f"-> {n} windows precompiled in {time.time() - t0:.1f}s")
    return cfg, stack


def window_batch_shape(stack):
    import jax
    from repro.core.precompile import load_window_range
    batch = load_window_range(stack, 0, BATCH)
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        batch)


# ---------------------------------------------------------------------------
# phases a-c: single trajectory, kernels, chip versus CPU
# ---------------------------------------------------------------------------

def trajectory(cfg, stack, dev, tag, n_windows, split=None):
    """Run greedy through ``Simulation`` over ``n_windows`` of the stack.
    Returns (final state, stats frame, state after ``split`` windows)."""
    from repro.core import engine
    from repro.core.pipeline import Simulation
    from repro.core.precompile import replay_windows
    from repro.core.state import init_state

    t0 = time.time()
    state0 = init_state(cfg)
    compiled = engine.run_windows_jit.lower(
        state0, window_batch_shape(stack), cfg, "greedy", SEED).compile()
    # the periodic accounting resync is a program of its own: compile it
    # here too, or its first call would count as run time
    engine.resync_accounting_jit.lower(state0, cfg).compile()
    del state0
    compile_s = time.time() - t0
    sim = Simulation(cfg, replay_windows(stack, batch=BATCH,
                                         n_windows=n_windows),
                     scheduler="greedy", batch_windows=BATCH, seed=SEED)
    t0 = time.time()
    early = None
    if split:
        sim.run(max_windows=split)
        early = _decisions(sim.state)
    state = sim.run()
    run_s = time.time() - t0
    s = _host(state)
    if sim.windows_done != n_windows:
        raise SmokeFailure(f"{tag}: ran {sim.windows_done} of {n_windows} "
                           "windows")
    log(f"[{tag}] windows={sim.windows_done} placements={int(s.placements)} "
        f"completions={int(s.completions)} evictions={int(s.evictions)} "
        f"{occupancy(s)} compile_s={compile_s:.1f} "
        f"run_s={run_s:.2f} resyncs={sim.resyncs_done} "
        f"{_mem_line(dev, compiled)}")
    return state, sim.stats_frame(), early


def phase_abc(cfg, stack, tpu):
    import jax
    from repro.core.state import validate_invariants

    state_a, frame_a, early_a = trajectory(cfg, stack, tpu, "a reference",
                                           WINDOWS, split=CPU_WINDOWS)
    problems = validate_invariants(state_a, cfg)
    if problems:
        raise SmokeFailure(f"a: invariants violated: {problems}")
    log("[a] invariants OK")
    dec_a = _decisions(state_a)
    del state_a

    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    state_b, frame_b, _ = trajectory(cfg_k, stack, tpu, "b kernels", WINDOWS)
    check_decisions(_decisions(state_b), dec_a, "b kernels vs a")
    worst = check_frames(frame_b, frame_a, "b kernels vs a")
    problems = validate_invariants(state_b, cfg)
    if problems:
        raise SmokeFailure(f"b: invariants violated: {problems}")
    log(f"[b] kernel decisions bitwise equal to a; float stats max rel diff "
        f"{worst:.3g} (rtol {FLOAT_RTOL:g})")
    del state_b

    cpu = jax.devices("cpu")[0]
    jax.config.update("jax_default_device", cpu)
    try:
        state_c, frame_c, _ = trajectory(cfg, stack, cpu, "c cpu",
                                         CPU_WINDOWS)
    finally:
        jax.config.update("jax_default_device", None)
    check_decisions(_decisions(state_c), early_a, "c cpu vs a")
    worst = check_frames(frame_c, {k: v[:CPU_WINDOWS]
                                   for k, v in frame_a.items()},
                         "c cpu vs a")
    log(f"[c] greedy: chip decisions bitwise equal to the CPU over "
        f"{CPU_WINDOWS} windows; float stats max rel diff {worst:.3g} "
        f"(rtol {FLOAT_RTOL:g})")
    del state_c
    gc.collect()
    return dec_a, frame_a


def fleet_run(cfg, stack, specs, dev, n_windows, mesh=None, start=0,
              snapshot=None, save_at=None):
    """A ``ScenarioFleet.from_precompiled`` run to window ``n_windows``;
    with ``start`` it resumes from ``snapshot``, with ``save_at`` it writes
    ``snapshot`` at that window. Returns (host state, stats frame, wall
    seconds, number of devices holding the lanes)."""
    from repro.scenarios import ScenarioFleet
    fleet = ScenarioFleet.from_precompiled(
        cfg, stack, specs, batch_windows=BATCH, seed=SEED,
        n_windows=n_windows - start, start_window=start, mesh=mesh)
    if start:
        fleet.restore(snapshot)
    t0 = time.time()
    if save_at:
        fleet.run(max_windows=save_at)
        fleet.save(snapshot)
    fleet.run()
    run_s = time.time() - t0
    if fleet.windows_done != n_windows:
        raise SmokeFailure(f"fleet ran to window {fleet.windows_done}, not "
                           f"{n_windows}")
    import jax
    n_dev = len(jax.tree.leaves(fleet.state)[0].sharding.device_set)
    state = _host(fleet.state)
    frame = fleet.stats_frame()
    return state, frame, run_s, n_dev


def lane(state, i: int) -> dict:
    return {k: getattr(state, k)[i] for k in DECISION_FIELDS}


def phase_c_schedulers(cfg, stack, tpu):
    """Every built-in scheduler, one lane each: the chip runs the whole
    trace, the CPU its last CPU_WINDOWS windows from the chip's snapshot,
    once the standing workload has loaded the cluster."""
    import numpy as np
    import jax
    from repro.core.snapshot import load_snapshot
    from repro.core.state import TASK_PENDING
    from repro.scenarios import ScenarioSpec
    from repro.sched import SCHEDULERS
    names = tuple(sorted(SCHEDULERS))
    specs = [ScenarioSpec(name=n, scheduler=n) for n in names]
    split = WINDOWS - CPU_WINDOWS
    snap = os.path.join(OUT_DIR, "c_fleet_snapshot.npz")
    s_tpu, f_tpu, t_tpu, _ = fleet_run(cfg, stack, specs, tpu, WINDOWS,
                                       snapshot=snap, save_at=split)
    at_split = occupancy(_host(load_snapshot(snap).state))
    cpu = jax.devices("cpu")[0]
    jax.config.update("jax_default_device", cpu)
    try:
        s_cpu, f_cpu, t_cpu, _ = fleet_run(cfg, stack, specs, cpu, WINDOWS,
                                           start=split, snapshot=snap)
    finally:
        jax.config.update("jax_default_device", None)
        os.remove(snap)
    f_tpu = {k: v[split:] for k, v in f_tpu.items()}
    for i, n in enumerate(names):
        check_frames({k: v[:, i] for k, v in f_tpu.items()},
                     {k: v[:, i] for k, v in f_cpu.items()},
                     f"c {n} chip vs cpu")
        check_decisions(lane(s_tpu, i), lane(s_cpu, i), f"c {n} chip vs cpu")
    worst = check_frames(f_tpu, f_cpu, "c schedulers chip vs cpu")
    log(f"[c] {len(names)} schedulers {list(names)}: chip decisions bitwise "
        f"equal to the CPU over windows {split}-{WINDOWS}, resumed from the "
        f"chip's snapshot at window {split} ({at_split}); placements "
        f"{[int(x) for x in s_tpu.placements]}; "
        f"{occupancy(s_tpu)}; "
        f"pending={[int(x) for x in np.sum(s_tpu.task_state == TASK_PENDING, axis=1)]}; "
        f"float stats max rel diff {worst:.3g}; chip_s={t_tpu:.1f} (all "
        f"{WINDOWS} windows) cpu_s={t_cpu:.1f} {_mem_line(tpu)}")


# ---------------------------------------------------------------------------
# phase d: fleet sized from memory_analysis()
# ---------------------------------------------------------------------------

def mixed_specs(n_lanes: int):
    """FLEET_MIX repeated; each further group of four lanes adds 10% node
    outage, so no two lanes simulate the same world."""
    from repro.scenarios import ScenarioSpec
    specs = []
    for i in range(n_lanes):
        sched, group = FLEET_MIX[i % 4], i // 4
        specs.append(ScenarioSpec(name=f"{sched}/outage{group * 10}",
                                  scheduler=sched,
                                  node_outage_frac=0.1 * group))
    return specs


def compile_fleet(cfg, stack, specs):
    """AOT-compile the fleet program ``ScenarioFleet`` will launch for
    ``specs`` (same static arguments, so the run reuses the compile)."""
    from repro.scenarios import ScenarioFleet
    from repro.scenarios import batch as batch_mod
    probe = ScenarioFleet(cfg, iter(()), specs, batch_windows=BATCH,
                          seed=SEED)
    t0 = time.time()
    compiled = batch_mod.run_scenarios_jit.lower(
        probe.state, window_batch_shape(stack), probe.knobs, cfg,
        probe.scheduler_names, SEED, has_storm=probe._has_storm,
        table=probe.dispatch_table,
        lane_scheds=probe._lane_scheds).compile()
    return compiled, time.time() - t0


def phase_d(cfg, stack, tpu, dec_a):
    stats = tpu.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        raise SmokeFailure("device reports no bytes_limit")
    # room for what is resident now plus the prefetch queue of windows
    budget = int(0.9 * limit) - stats.get("bytes_in_use", 0) - (1 << 30)
    compiled, c_s = compile_fleet(cfg, stack, mixed_specs(4))
    need4 = _program_bytes(compiled)
    if need4 > budget:
        raise SmokeFailure(f"d: a 4-lane fleet needs {_gb(need4)} > budget "
                           f"{_gb(budget)}")
    # lanes are independent, so the program grows about linearly in B:
    # guess from the 4-lane program, then step down until one fits
    B = max(4, min(MAX_FLEET_LANES, int(budget // (need4 / 4))))
    tried = [(4, need4)]
    while B > 4:
        compiled, c_s = compile_fleet(cfg, stack, mixed_specs(B))
        need = _program_bytes(compiled)
        tried.append((B, need))
        if need <= budget:
            break
        B -= 1
    if B == 4 and len(tried) > 1:
        compiled, c_s = compile_fleet(cfg, stack, mixed_specs(4))
    log(f"[d] fleet size from memory_analysis: bytes_limit={_gb(limit)} "
        f"budget={_gb(budget)} tried "
        + ", ".join(f"B={b}:{_gb(n)}" for b, n in tried) + f" -> B={B}")
    specs = mixed_specs(B)
    state, frame, run_s, _ = fleet_run(cfg, stack, specs, tpu, WINDOWS)
    check_decisions(lane(state, 0), dec_a, "d lane 0 vs a")
    log(f"[d] fleet B={B} {[s.name for s in specs]} windows={WINDOWS} "
        f"placements={[int(x) for x in state.placements]} "
        f"completions={[int(x) for x in state.completions]} "
        f"evictions={[int(x) for x in state.evictions]} "
        f"compile_s={c_s:.1f} run_s={run_s:.2f} "
        f"lane_windows/s={B * WINDOWS / run_s:.1f} "
        f"{_mem_line(tpu, compiled)}; lane 0 decisions bitwise equal to a")
    return specs, frame


# ---------------------------------------------------------------------------
# phase e: the what-if service
# ---------------------------------------------------------------------------

def phase_e(cfg, stack, tpu, fleet_specs, fleet_frame):
    from repro.scenarios import ScenarioSpec, scenario_report
    from repro.service import WhatIfQuery, WhatIfServer

    def fleet_lane(sched):
        return next(i for i, s in enumerate(fleet_specs)
                    if s.scheduler == sched and s.node_outage_frac == 0.0)

    server = WhatIfServer(cfg, stack, schedulers=SERVED, max_lanes=4,
                          batch_windows=BATCH, seed=SEED)
    t0 = time.time()
    server.start(warm=True)
    warm_s = time.time() - t0
    try:
        t0 = time.time()
        forks = server.build_fork_points(fleet_specs, every=FORK_EVERY,
                                         n_windows=WINDOWS)
        trunk_s = time.time() - t0
        fork_w = forks[0]
        queries = [WhatIfQuery(ScenarioSpec(name=f"e/{s}", scheduler=s),
                               n_windows=WINDOWS, seed=SEED) for s in SERVED]
        queries.append(WhatIfQuery(
            ScenarioSpec(name="e/greedy@fork", scheduler="greedy"),
            n_windows=WINDOWS - fork_w, start_window=fork_w, seed=SEED))
        t0 = time.time()
        tickets = [server.submit(q) for q in queries]
        results = [t.wait(timeout=900) for t in tickets]
        serve_s = time.time() - t0
        for q, r in zip(queries, results):
            if not r.ok():
                raise SmokeFailure(f"e: {q.spec.name} failed: {r.code} "
                                   f"{r.error}")
            i = fleet_lane(q.spec.scheduler)
            lo, hi = q.start_window, q.start_window + q.n_windows
            direct = {k: v[lo:hi, i:i + 1] for k, v in fleet_frame.items()}
            check_frames({k: v[:, None] for k, v in r.frame.items()}, direct,
                         f"e {q.spec.name} frame")
            want = scenario_report([q.spec.name], direct,
                                   [q.spec.scheduler])["scenarios"][0]
            check_row(r.row, want, f"e {q.spec.name}")
        m = server.stats()
    finally:
        server.stop()
    log(f"[e] served {len(queries)} queries "
        f"({', '.join(f'{q.spec.name}@{q.start_window}+{q.n_windows}' for q in queries)}) "
        f"each equal to its direct fleet lane; fork points {forks}; "
        f"batches={m['batches']} p50={m['latency_p50_s']:.2f}s "
        f"p90={m['latency_p90_s']:.2f}s warm_s={warm_s:.1f} "
        f"trunk_s={trunk_s:.1f} serve_s={serve_s:.1f} {_mem_line(tpu)}")


# ---------------------------------------------------------------------------
# four chips: sharded fleet against the same lanes on one chip
# ---------------------------------------------------------------------------

def phase_four_chips(cfg, stack, devs):
    from repro.scenarios import fleet_mesh
    n_windows = 64
    specs = mixed_specs(4)
    mesh = fleet_mesh(4)
    sh_state, sh_frame, sh_s, sh_dev = fleet_run(cfg, stack, specs, devs[0],
                                                 n_windows, mesh=mesh)
    one_state, one_frame, one_s, one_dev = fleet_run(cfg, stack, specs,
                                                     devs[0], n_windows)
    if sh_dev != 4 or one_dev != 1:
        raise SmokeFailure(f"lanes on {sh_dev} devices sharded and "
                           f"{one_dev} unsharded, want 4 and 1")
    for i, s in enumerate(specs):
        check_decisions(lane(sh_state, i), lane(one_state, i),
                        f"4-chip lane {i} ({s.name}) vs one chip")
    worst = check_frames(sh_frame, one_frame, "4-chip vs one chip")
    log(f"[4chip] sharded fleet over {mesh.devices.size} devices "
        f"({[s.name for s in specs]}, one lane per device) vs the same "
        f"lanes vmapped on one chip: decisions bitwise equal per lane; "
        f"placements={[int(x) for x in sh_state.placements]}; float stats "
        f"max rel diff {worst:.3g}; windows={n_windows}; wall with compile "
        f"sharded_s={sh_s:.1f} one_chip_s={one_s:.1f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded four-chip fleet against "
                         "the same lanes on one chip")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import env
        from repro.core import tracegen
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 1
    cache_dir = env.enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform} "
              f"devices); this check runs only on a TPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"devices", file=sys.stderr)
        return 2
    tpu = devs[0]
    log(f"device: {tpu.platform} {tpu.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache_dir} ({cached} "
        "entries at start)")
    t_start = time.time()
    try:
        if args.chips == 4:
            # no standing workload: its host synthesis would hold four
            # chips for a minute, and the comparison is lane for lane
            cfg, stack = build_stack(64, standing_tasks=0)
            phase_four_chips(cfg, stack, devs)
        else:
            cfg, stack = build_stack(WINDOWS,
                                     tracegen.PAPER_CONCURRENT_TASKS)
            dec_a, _ = phase_abc(cfg, stack, tpu)
            phase_c_schedulers(cfg, stack, tpu)
            gc.collect()
            specs, frame = phase_d(cfg, stack, tpu, dec_a)
            gc.collect()
            phase_e(cfg, stack, tpu, specs, frame)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.time() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": tpu.platform, "kind": tpu.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
