"""Engine hot-loop benchmark: the optimised path vs the PR-3-era baseline.

Measures windows/sec through the jitted window scan — single-lane and the
vmapped scenario fleet at B=8 — with the current defaults (incremental
accounting, fused window stats, victim-compacted storm debits, donated
state buffers) against the *full* path: ``incremental_accounting=False``
(three O(max_tasks) segment-sum recomputes per window) plus
``fused_window_stats=False`` (the pre-fusion ~6-pass stats body) — i.e.
the engine as it stood before PRs 4-5. Also:

* verifies equivalence while timing: final placements (``task_node``)
  bit-exact across modes, final accounting + stats allclose;
* breaks the stats path down: unfused body vs fused jnp reference vs the
  Pallas window-stats kernel, rows bitwise-compared across all three;
* measures stats decimation: ``stats_stride=8`` headless sweeps (single
  and fleet), final state bit-exact vs stride 1;
* measures the storm-lane debit: victim-compacted scatter (default cap)
  vs the legacy whole-table masked segment-sum;
* times the host-side staging path: the WindowPrefetcher's preallocated
  buffer ring vs the per-batch ``np.stack`` it replaced;
* reports end-to-end driver throughput (async stats + device-resident
  batches) for the single-trajectory Simulation.

The trace is synthetic and *grid-aligned* (every resource a multiple of
1/128) so float sums are exact and the bit-exactness bar is meaningful.

Writes ``BENCH_engine.json`` at the repo root. ``--quick`` shrinks shapes
for the CI perf-smoke job; ``--check`` compares the measured speedups
(single, fleet, storm fleet) against the committed baseline and fails on a
>20% regression (speedup ratios are machine-independent, unlike absolute
windows/sec) or any equivalence break. Acceptance bar: >= 2.5x single-lane
and >= 2x storm-fleet vs the full path on CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SimConfig
from repro.core import engine as eng
from repro.core import pipeline as pipe
from repro.core.events import (EventKind, HostEvent, REMOVE_REASON_EVICT,
                               pack_window, stack_windows)
from repro.core.state import init_state
from repro.scenarios import batch as batch_mod
from repro.scenarios.spec import ScenarioSpec, build_knobs

REPO = pathlib.Path(__file__).resolve().parent.parent
JSON_PATH = REPO / "BENCH_engine.json"

FLEET_B = 8
# every knob exact-arithmetic so the cross-mode comparison stays bit-exact.
# The headline fleet is storm-free — the common case, which ScenarioFleet
# compiles with has_storm=False so the whole storm pass is dropped; the
# storm variant (per-window masked debit passes) is reported separately.
FLEET_SPECS = [
    ScenarioSpec(name="base"),
    ScenarioSpec(name="ff", scheduler="first_fit"),
    ScenarioSpec(name="rr", scheduler="round_robin"),
    ScenarioSpec(name="outage", node_outage_frac=0.25),
    ScenarioSpec(name="half-cap", capacity_scale=0.5),
    ScenarioSpec(name="thin", arrival_rate=0.5),
    ScenarioSpec(name="surge", priority_surge_frac=0.5),
    ScenarioSpec(name="usage", scheduler="first_fit", usage_scale=2.0),
]
STORM_SPECS = FLEET_SPECS[:6] + [
    ScenarioSpec(name="storm", evict_storm_frac=0.25),
    ScenarioSpec(name="ff-storm", scheduler="first_fit",
                 evict_storm_frac=0.125),
]
# dispatch benchmark fleet: mixed cheap heuristics + an expensive
# metaheuristic. Under vmapped lax.switch EVERY lane pays for the SA
# branch (vmap executes all switch branches on all lanes); switchless
# proposal-table dispatch runs SA only over its two lanes
SCHED_DISPATCH_SPECS = [
    ScenarioSpec(name="g0"),
    ScenarioSpec(name="sa0", scheduler="simulated_annealing"),
    ScenarioSpec(name="rr0", scheduler="round_robin"),
    ScenarioSpec(name="g1"),
    ScenarioSpec(name="sa1", scheduler="simulated_annealing"),
    ScenarioSpec(name="rr1", scheduler="round_robin"),
    ScenarioSpec(name="g2"),
    ScenarioSpec(name="rr2", scheduler="round_robin"),
]


def make_cfg(quick: bool) -> SimConfig:
    # max_tasks dominates deliberately: the optimised path's win is the
    # removal of O(max_tasks) work (accounting recomputes, the unfused
    # stats passes, the storm debit sweep), and the paper cell runs 262K
    # task slots — small tables would hide the effect behind the
    # (mode-independent) commit scan + constraint match cost
    if quick:
        return SimConfig(max_nodes=64, max_tasks=32_768,
                         max_events_per_window=512, sched_batch=64,
                         n_attr_slots=8, max_constraints=4)
    return SimConfig(max_nodes=128, max_tasks=65_536,
                     max_events_per_window=1_024, sched_batch=128,
                     n_attr_slots=8, max_constraints=4)


def _grid(r, lo, hi, q=128):
    return float(r.integers(lo, hi)) / q


def build_windows(cfg: SimConfig, n_windows: int, seed: int = 0):
    """Synthetic grid-aligned workload: node fleet up front plus churn,
    steady task arrivals/removals/usage samples sized to the cell."""
    r = np.random.default_rng(seed)
    evs = [[] for _ in range(n_windows)]
    for m in range(cfg.max_nodes):
        evs[0].append(HostEvent(0, EventKind.ADD_NODE, m,
                                a=(_grid(r, 96, 256), _grid(r, 96, 256),
                                   _grid(r, 96, 256))))
    per_window = max(cfg.max_events_per_window // 4, 32)
    slots = cfg.max_tasks
    live = []
    next_slot = 0
    for w in range(1, n_windows):
        for _ in range(per_window):
            kind = r.random()
            if kind < 0.55 or not live:
                s = next_slot % slots
                next_slot += 1
                live.append(s)
                evs[w].append(HostEvent(
                    1, EventKind.ADD_TASK, s,
                    a=(_grid(r, 1, 24), _grid(r, 1, 24), _grid(r, 0, 8)),
                    prio=int(r.integers(0, 12))))
            elif kind < 0.75:
                s = live.pop(int(r.integers(0, len(live))))
                reason = float(REMOVE_REASON_EVICT) if r.random() < .2 else 0.
                evs[w].append(HostEvent(2, EventKind.REMOVE_TASK, s,
                                        a=(reason, 0, 0)))
            elif kind < 0.95:
                s = live[int(r.integers(0, len(live)))]
                evs[w].append(HostEvent(
                    2, EventKind.UPDATE_TASK_USED, s,
                    u=tuple(_grid(r, 0, 16) for _ in range(8))))
            else:
                m = int(r.integers(0, cfg.max_nodes))
                evs[w].append(HostEvent(0, EventKind.UPDATE_NODE_RESOURCES, m,
                                        a=(_grid(r, 64, 256),
                                           _grid(r, 64, 256),
                                           _grid(r, 64, 256))))
    return [pack_window(cfg, e, i) for i, e in enumerate(evs)]


def _wall(fn, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_single(cfg_inc, cfg_full, windows, reps):
    """Single-lane scan: windows/sec per mode + cross-mode equivalence."""
    W = windows.kind.shape[0]
    finals = {}
    out = {}
    for name, cfg in (("incremental", cfg_inc), ("full", cfg_full)):
        def run():
            s, st = eng.run_windows_jit(init_state(cfg), windows, cfg,
                                        "greedy", 0)
            jax.block_until_ready(s)
            return s, st
        s, st = run()                       # compile + equivalence capture
        finals[name] = (jax.tree.map(np.asarray, s),
                        jax.tree.map(np.asarray, st))
        out[f"windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["speedup"] = (out["windows_per_sec_incremental"]
                      / out["windows_per_sec_full"])
    si, sf = finals["incremental"][0], finals["full"][0]
    out["placements_bitexact"] = bool(
        np.array_equal(si.task_node, sf.task_node)
        and np.array_equal(si.task_state, sf.task_state))
    out["accounting_allclose"] = bool(
        np.allclose(si.node_reserved, sf.node_reserved, atol=1e-4)
        and np.allclose(si.node_used, sf.node_used, atol=1e-4))
    out["stats_allclose"] = bool(all(
        np.allclose(finals["incremental"][1][k], finals["full"][1][k],
                    atol=1e-4)
        for k in finals["full"][1]))
    return out


def bench_fleet(cfg_inc, cfg_full, windows, reps, specs):
    """Vmapped fleet at B=8, mixed schedulers; has_storm derived from the
    specs exactly as ScenarioFleet does."""
    W = windows.kind.shape[0]
    has_storm = any(s.evict_storm_frac > 0.0 for s in specs)
    knobs, sched_names = build_knobs(specs)
    finals = {}
    out = {"has_storm": has_storm}
    for name, cfg in (("incremental", cfg_inc), ("full", cfg_full)):
        def run():
            s, st = batch_mod.run_scenarios_jit(
                batch_mod.init_batched_state(cfg, FLEET_B), windows, knobs,
                cfg, sched_names, 0, has_storm=has_storm)
            jax.block_until_ready(s)
            return s, st
        s, st = run()
        finals[name] = jax.tree.map(np.asarray, s)
        out[f"windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["speedup"] = (out["windows_per_sec_incremental"]
                      / out["windows_per_sec_full"])
    si, sf = finals["incremental"], finals["full"]
    out["placements_bitexact"] = bool(
        np.array_equal(si.task_node, sf.task_node)
        and np.array_equal(si.task_state, sf.task_state))
    out["accounting_allclose"] = bool(
        np.allclose(si.node_reserved, sf.node_reserved, atol=1e-4)
        and np.allclose(si.node_used, sf.node_used, atol=1e-4))
    return out


def bench_stats_path(cfg, windows, reps):
    """Stats-path breakdown at the engine level (single lane, incremental
    accounting throughout): unfused body vs fused jnp reference vs the
    Pallas window-stats kernel (interpret mode on CPU; the kernel config
    also kernelises the commit/constraint passes — noted in the key).
    Rows are bitwise-compared across all three paths."""
    W = windows.kind.shape[0]
    variants = {
        "unfused": dataclasses.replace(cfg, fused_window_stats=False),
        "fused_ref": cfg,
        "fused_kernel_all_kernels": dataclasses.replace(cfg,
                                                        use_kernels=True),
    }
    rows = {}
    out = {}
    for name, c in variants.items():
        def run():
            s, st = eng.run_windows_jit(init_state(c), windows, c,
                                        "greedy", 0)
            jax.block_until_ready(s)
            return st
        rows[name] = jax.tree.map(np.asarray, run())
        out[f"windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["fused_speedup_vs_unfused"] = (out["windows_per_sec_fused_ref"]
                                       / out["windows_per_sec_unfused"])
    out["rows_bitwise"] = bool(all(
        np.array_equal(rows[v][k], rows["unfused"][k])
        for v in ("fused_ref", "fused_kernel_all_kernels")
        for k in rows["unfused"]))
    return out


def bench_stride(cfg_inc, windows, reps, specs):
    """Stats decimation: stride-8 headless sweeps vs stride 1 (single lane
    + fleet B=8), final states bit-exact by construction of the stride."""
    W = windows.kind.shape[0]
    cfg8 = dataclasses.replace(cfg_inc, stats_stride=8)
    out = {"stride": 8}

    finals = {}
    for name, cfg in (("stride1", cfg_inc), ("stride8", cfg8)):
        def run():
            s, st = eng.run_windows_jit(init_state(cfg), windows, cfg,
                                        "greedy", 0)
            jax.block_until_ready(s)
            return s
        finals[name] = jax.tree.map(np.asarray, run())
        out[f"single_windows_per_sec_{name}"] = W / _wall(lambda: run(),
                                                          reps)
    out["single_speedup"] = (out["single_windows_per_sec_stride8"]
                             / out["single_windows_per_sec_stride1"])
    out["single_state_bitexact"] = bool(all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(finals["stride1"]),
                        jax.tree.leaves(finals["stride8"]))))

    has_storm = any(s.evict_storm_frac > 0.0 for s in specs)
    knobs, sched_names = build_knobs(specs)
    for name, cfg in (("stride1", cfg_inc), ("stride8", cfg8)):
        def run():
            s, st = batch_mod.run_scenarios_jit(
                batch_mod.init_batched_state(cfg, FLEET_B), windows, knobs,
                cfg, sched_names, 0, has_storm=has_storm)
            jax.block_until_ready(s)
        run()
        out[f"fleet_windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["fleet_speedup"] = (out["fleet_windows_per_sec_stride8"]
                            / out["fleet_windows_per_sec_stride1"])
    return out


def bench_storm_compaction(cfg_inc, windows, reps, specs):
    """Storm-lane debit: victim-compacted O(V) scatter (default cap) vs the
    legacy whole-table masked segment-sum (cap >= max_tasks). The cap never
    bites at these shapes, so the two fleets are bit-identical."""
    W = windows.kind.shape[0]
    knobs, sched_names = build_knobs(specs)
    variants = {
        "compacted": cfg_inc,
        "masked_segment_sum": dataclasses.replace(
            cfg_inc, storm_max_victims=cfg_inc.max_tasks),
    }
    finals = {}
    out = {"victim_cap": cfg_inc.resolved_storm_max_victims}
    for name, cfg in variants.items():
        def run():
            s, st = batch_mod.run_scenarios_jit(
                batch_mod.init_batched_state(cfg, FLEET_B), windows, knobs,
                cfg, sched_names, 0, has_storm=True)
            jax.block_until_ready(s)
            return s
        finals[name] = jax.tree.map(np.asarray, run())
        out[f"windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["speedup"] = (out["windows_per_sec_compacted"]
                      / out["windows_per_sec_masked_segment_sum"])
    out["states_bitexact"] = bool(all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(finals["compacted"]),
                        jax.tree.leaves(finals["masked_segment_sum"]))))
    return out


def bench_sched_dispatch(quick, reps):
    """Scheduler dispatch strategy on a mixed greedy+SA+round_robin B=8
    fleet at a *scheduling-bound* shape (small task table, large
    sched_batch, so proposal cost dominates the window):

    * ``switch`` — the vmapped ``lax.switch`` fallback. vmap lowers a
      switch to "run every branch on every lane, select", so all 8 lanes
      pay for the 64-step simulated-annealing body;
    * ``switchless`` — proposal-table dispatch: each distinct proposal
      family is evaluated once over its own lane sub-batch (SA runs on 2
      lanes, not 8) and results are merged back by static lane order;
    * ``fused_kernel`` — switchless with ``use_kernels=True``: table-form
      built-ins commit through the fused ``sched_pass`` Pallas kernel
      (interpret mode on CPU — timing informational there; the row exists
      to pin bitwise equivalence of the kernel path at bench shapes).

    Final fleet states are bitwise-compared across all three."""
    from repro.sched import snapshot_dispatch
    if quick:
        cfg = SimConfig(max_nodes=64, max_tasks=4_096,
                        max_events_per_window=256, sched_batch=128,
                        n_attr_slots=8, max_constraints=4)
        W = 24
    else:
        cfg = SimConfig(max_nodes=128, max_tasks=8_192,
                        max_events_per_window=512, sched_batch=256,
                        n_attr_slots=8, max_constraints=4)
        W = 48
    windows = jax.tree.map(jnp.asarray, stack_windows(build_windows(cfg, W)))
    specs = SCHED_DISPATCH_SPECS
    B = len(specs)
    knobs, sched_names = build_knobs(specs)
    table = snapshot_dispatch(sched_names)
    lanes = tuple(sched_names.index(s.scheduler) for s in specs)
    variants = {
        "switch": (dataclasses.replace(cfg, sched_dispatch="switch"), None),
        "switchless": (dataclasses.replace(cfg, sched_dispatch="table"),
                       lanes),
        "fused_kernel": (dataclasses.replace(cfg, sched_dispatch="table",
                                             use_kernels=True), lanes),
    }
    finals = {}
    out = {"fleet_B": B, "max_nodes": cfg.max_nodes,
           "sched_batch": cfg.sched_batch, "windows": W,
           "schedulers": sorted(set(s.scheduler for s in specs))}
    for name, (c, ls) in variants.items():
        def run():
            s, st = batch_mod.run_scenarios_jit(
                batch_mod.init_batched_state(c, B), windows, knobs, c,
                sched_names, 0, has_storm=False, table=table,
                lane_scheds=ls)
            jax.block_until_ready(s)
            return s
        finals[name] = jax.tree.map(np.asarray, run())
        out[f"windows_per_sec_{name}"] = W / _wall(lambda: run(), reps)
    out["speedup_switchless"] = (out["windows_per_sec_switchless"]
                                 / out["windows_per_sec_switch"])
    out["speedup_fused_kernel"] = (out["windows_per_sec_fused_kernel"]
                                   / out["windows_per_sec_switch"])
    sw = jax.tree.leaves(finals["switch"])
    for name in ("switchless", "fused_kernel"):
        out[f"{name}_bitexact"] = bool(all(
            np.array_equal(a, b, equal_nan=a.dtype.kind == "f")
            for a, b in zip(sw, jax.tree.leaves(finals[name]))))
    return out


def bench_staging(cfg, window_list, reps):
    """Host-side restacking: preallocated staging ring vs np.stack."""
    batch = 32
    groups = [window_list[i:i + batch]
              for i in range(0, len(window_list) - batch + 1, batch)]
    if not groups:
        groups = [window_list]
        batch = len(window_list)
    pool = pipe._StagingPool(window_list[0], batch)

    def with_stack():
        for g in groups:
            stack_windows(g)

    def with_pool():
        for g in groups:
            pool.stack(g)

    with_stack(), with_pool()
    t_stack = _wall(with_stack, reps)
    t_pool = _wall(with_pool, reps)
    return {"np_stack_ms_per_batch": t_stack * 1e3 / len(groups),
            "staging_ring_ms_per_batch": t_pool * 1e3 / len(groups),
            "speedup": t_stack / max(t_pool, 1e-12)}


def bench_driver(cfg, window_list, reps):
    """End-to-end Simulation driver (prefetch thread + async stats)."""
    W = len(window_list)

    def run():
        sim = pipe.Simulation(cfg, iter(window_list), batch_windows=32)
        sim.run()
        return sim

    run()
    return {"windows_per_sec_e2e": W / _wall(lambda: run(), reps),
            "async_stats": True}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for the CI perf-smoke job")
    ap.add_argument("--check", action="store_true",
                    help="fail if speedups regress >20%% vs the committed "
                         "baseline (or equivalence breaks)")
    ap.add_argument("--windows", type=int, default=None)
    ap.add_argument("--out", default=str(JSON_PATH))
    ap.add_argument("--platform", default=None,
                    choices=("cpu", "tpu"),
                    help="pin the jax backend (recorded under meta.backend "
                         "so runs from different platforms never get "
                         "compared silently)")
    args = ap.parse_args(argv)

    from repro import env
    env.set_platform(args.platform)
    env.enable_compile_cache()

    cfg_inc = make_cfg(args.quick)
    # the "full" baseline is the PR-3-era engine: full segment-sum
    # recomputes AND the unfused ~6-pass stats body
    cfg_full = dataclasses.replace(cfg_inc, incremental_accounting=False,
                                   fused_window_stats=False)
    W = args.windows or (64 if args.quick else 128)
    reps = 3

    # snapshot the committed baseline BEFORE (possibly) overwriting it
    baseline = None
    if args.check:
        try:
            baseline = json.loads(JSON_PATH.read_text())
        except FileNotFoundError:
            pass

    window_list = build_windows(cfg_inc, W)
    windows = jax.tree.map(jnp.asarray, stack_windows(window_list))

    result = {
        "meta": {"backend": jax.default_backend(),
                 "quick": args.quick, "windows": W,
                 "max_nodes": cfg_inc.max_nodes,
                 "max_tasks": cfg_inc.max_tasks,
                 "sched_batch": cfg_inc.sched_batch,
                 "fleet_B": FLEET_B},
        "single": bench_single(cfg_inc, cfg_full, windows, reps),
        "fleet_B8": bench_fleet(cfg_inc, cfg_full, windows, reps,
                                FLEET_SPECS),
        "fleet_B8_storm": bench_fleet(cfg_inc, cfg_full, windows, reps,
                                      STORM_SPECS),
        "stats_path": bench_stats_path(cfg_inc, windows, reps),
        "sched_dispatch": bench_sched_dispatch(args.quick, reps),
        "stride8": bench_stride(cfg_inc, windows, reps, FLEET_SPECS),
        "storm_compaction": bench_storm_compaction(cfg_inc, windows, reps,
                                                   STORM_SPECS),
        "staging": bench_staging(cfg_inc, window_list, reps),
        "driver": bench_driver(cfg_inc, window_list, reps),
    }

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    for sec in ("single", "fleet_B8", "fleet_B8_storm"):
        r = result[sec]
        print(f"{sec}: {r['windows_per_sec_incremental']:.1f} w/s "
              f"incremental vs {r['windows_per_sec_full']:.1f} w/s full "
              f"-> {r['speedup']:.2f}x  (bitexact={r['placements_bitexact']}"
              f", allclose={r['accounting_allclose']})")
    sp = result["stats_path"]
    print(f"stats_path: {sp['windows_per_sec_unfused']:.1f} w/s unfused, "
          f"{sp['windows_per_sec_fused_ref']:.1f} fused ref, "
          f"{sp['windows_per_sec_fused_kernel_all_kernels']:.1f} kernel "
          f"(rows bitwise={sp['rows_bitwise']})")
    sd = result["sched_dispatch"]
    print(f"sched_dispatch: {sd['windows_per_sec_switch']:.1f} w/s switch, "
          f"{sd['windows_per_sec_switchless']:.1f} switchless "
          f"({sd['speedup_switchless']:.2f}x), "
          f"{sd['windows_per_sec_fused_kernel']:.1f} fused-kernel "
          f"(bitexact: switchless={sd['switchless_bitexact']}, "
          f"kernel={sd['fused_kernel_bitexact']})")
    st8 = result["stride8"]
    print(f"stride8: single {st8['single_speedup']:.2f}x, fleet "
          f"{st8['fleet_speedup']:.2f}x vs stride 1 "
          f"(state bitexact={st8['single_state_bitexact']})")
    sc = result["storm_compaction"]
    print(f"storm_compaction: {sc['speedup']:.2f}x vs masked segment-sum "
          f"(V={sc['victim_cap']}, bitexact={sc['states_bitexact']})")
    print(f"staging: {result['staging']['speedup']:.2f}x vs np.stack; "
          f"driver e2e {result['driver']['windows_per_sec_e2e']:.1f} w/s; "
          f"-> {args.out}")

    ok = True
    for sec in ("single", "fleet_B8", "fleet_B8_storm"):
        if not (result[sec]["placements_bitexact"]
                and result[sec]["accounting_allclose"]):
            print(f"FAIL: {sec} equivalence broken")
            ok = False
    if not result["stats_path"]["rows_bitwise"]:
        print("FAIL: stats rows differ across unfused/fused/kernel paths")
        ok = False
    for name in ("switchless", "fused_kernel"):
        if not result["sched_dispatch"][f"{name}_bitexact"]:
            print(f"FAIL: {name} dispatch diverged from lax.switch")
            ok = False
    if not result["stride8"]["single_state_bitexact"]:
        print("FAIL: stride-8 final state differs from stride 1")
        ok = False
    if not result["storm_compaction"]["states_bitexact"]:
        print("FAIL: compacted storm debit diverged from masked segment-sum")
        ok = False
    if args.check:
        # absolute floor (speedup ratios are machine-independent): the
        # switchless dispatch win must hold, baseline or not
        got_sd = result["sched_dispatch"]["speedup_switchless"]
        if got_sd < 1.2:
            print(f"FAIL: switchless dispatch speedup {got_sd:.2f}x below "
                  "the 1.2x floor")
            ok = False
        else:
            print(f"check sched_dispatch: switchless {got_sd:.2f}x "
                  ">= 1.2x floor OK")
        if baseline is None:
            print(f"note: no committed baseline at {JSON_PATH}; "
                  "skipping regression gate")
        elif baseline.get("meta", {}).get("quick") != args.quick:
            print("note: committed baseline was measured at different "
                  "shapes (quick mismatch); skipping regression gate")
        else:
            for sec in ("single", "fleet_B8", "fleet_B8_storm"):
                got = result[sec]["speedup"]
                want = baseline.get(sec, {}).get("speedup")
                if want is None:
                    print(f"note: no committed {sec} speedup; skipping")
                    continue
                if got < 0.8 * want:
                    print(f"FAIL: {sec} speedup {got:.2f}x regressed >20% "
                          f"vs committed {want:.2f}x")
                    ok = False
                else:
                    print(f"check {sec}: {got:.2f}x vs committed "
                          f"{want:.2f}x OK")
            want8 = baseline.get("stride8", {}).get("single_speedup")
            got8 = result["stride8"]["single_speedup"]
            if want8 is not None and got8 < 0.8 * want8:
                print(f"FAIL: stride-8 speedup {got8:.2f}x regressed >20% "
                      f"vs committed {want8:.2f}x")
                ok = False
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
