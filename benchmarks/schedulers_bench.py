"""Paper §IV use case: up to 5 meta-heuristic schedulers concurrently
consuming ONE workload (MASB). Reports per-scheduler wall time, placements,
and the load-balance objective — plus the vmapped many-replica variant that
the TPU adaptation makes cheap (paper runs 5 at 5x speed; we vmap 16).

Also times the placement-commit finaliser in isolation — the Pallas kernel
(`kernels/placement_commit`, interpret mode on CPU) against the XLA
``fori_loop`` reference it replaced, single-trajectory and vmapped fleet
B=8 — and persists everything to ``BENCH_schedulers.json`` at the repo root
so the perf trajectory is recorded run-over-run. The acceptance bar for the
kernel is >= 1.0x (no regression) on CPU; the structural win (tally resident
on-chip, blocked pref matrix) is aimed at TPU.
"""
from __future__ import annotations

import functools
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SimConfig
from repro.core import engine as eng
from repro.core.events import EventKind, HostEvent, pack_window, stack_windows
from repro.kernels.placement_commit.ops import placement_commit
from repro.kernels.placement_commit.ref import placement_commit_ref
from repro.sched import SCHEDULERS, get_scheduler

from repro.core.state import init_state

CFG = SimConfig(max_nodes=128, max_tasks=4096, max_events_per_window=1024,
                sched_batch=256, n_attr_slots=8, max_constraints=4)
WINDOWS = 16
SCHED_SET = ("greedy", "first_fit", "round_robin", "random",
             "simulated_annealing", "genetic")
FLEET_B = 8
JSON_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_schedulers.json"


def _windows(seed=0):
    r = np.random.default_rng(seed)
    evs = [[] for _ in range(WINDOWS)]
    for i in range(CFG.max_nodes):
        evs[0].append(HostEvent(0, EventKind.ADD_NODE, i, a=(1.0, 1.0, 1.0)))
    for t in range(1200):
        w = int(r.integers(1, WINDOWS - 1))
        evs[w].append(HostEvent(0, EventKind.ADD_TASK, t,
                                a=(float(r.uniform(.01, .15)),
                                   float(r.uniform(.01, .15)), 0.0),
                                prio=int(r.integers(0, 12))))
    ws = [pack_window(CFG, e, i) for i, e in enumerate(evs)]
    return jax.tree.map(jnp.asarray, stack_windows(ws))


def _best_of(fn, *args, reps: int = 10):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _commit_inputs(P, N, R=3, seed=0):
    r = np.random.default_rng(seed)
    pref = jnp.asarray(r.standard_normal((P, N)), jnp.float32)
    req = jnp.asarray(r.uniform(0.0, 0.2, (P, R)), jnp.float32)
    ok = jnp.asarray(r.random((P, N)) > 0.2)
    valid = jnp.ones((P,), bool)
    total = jnp.asarray(r.uniform(0.5, 1.0, (N, R)), jnp.float32)
    denom = jnp.maximum(total, 1e-6)
    res0 = jnp.zeros((N, R), jnp.float32)
    return pref, req, ok, valid, total, denom, res0


def run_commit(csv_rows):
    """Commit-kernel vs fori_loop finaliser, isolated from the engine.

    single: the single-trajectory shape (P=sched_batch, N=max_nodes);
    fleet_B8: the scenario fleet's batched commit — vmap over B=8 lanes with
    per-lane traced dynamic_bestfit flags (the lax.switch dispatch mode).
    The derived column is the speedup (>= 1.0 means the kernel does not
    regress; node_of is bitwise-identical either way, tested).
    """
    P, N = CFG.sched_batch, CFG.max_nodes
    pref, req, ok, valid, total, denom, res0 = _commit_inputs(P, N)

    for dyn, tag in ((True, "bestfit"), (False, "static")):
        f_ref = jax.jit(lambda *a, d=dyn: placement_commit_ref(*a, d)[0])
        f_ker = jax.jit(lambda *a, d=dyn: placement_commit(
            *a, d, use_kernel=True))
        t_ref = _best_of(f_ref, pref, req, ok, valid, total, denom, res0)
        t_ker = _best_of(f_ker, pref, req, ok, valid, total, denom, res0)
        csv_rows.append((f"commit_single_{tag}_fori_wall", t_ref * 1e6,
                         t_ref / t_ker))
        csv_rows.append((f"commit_single_{tag}_kernel_wall", t_ker * 1e6,
                         t_ref / t_ker))

    prefs = jnp.stack([pref + i for i in range(FLEET_B)])
    flags = jnp.asarray([i % 2 == 0 for i in range(FLEET_B)])

    def fleet(commit):
        return jax.jit(jax.vmap(
            lambda p, f: commit(p, req, ok, valid, total, denom, res0, f)))

    # the loop itself, not placement_commit(use_kernel=False), which is
    # the kernel wherever the program lowers for a TPU
    t_ref = _best_of(fleet(lambda *a: placement_commit_ref(*a)[0]), prefs,
                     flags)
    t_ker = _best_of(fleet(functools.partial(placement_commit,
                                             use_kernel=True)), prefs, flags)
    csv_rows.append((f"commit_fleet_B{FLEET_B}_fori_wall", t_ref * 1e6,
                     t_ref / t_ker))
    csv_rows.append((f"commit_fleet_B{FLEET_B}_kernel_wall", t_ker * 1e6,
                     t_ref / t_ker))
    return csv_rows


def _emit_json(csv_rows):
    """Persist this suite's rows so the perf trajectory is recorded."""
    commit = {r[0]: {"us_per_call": r[1], "speedup_vs_fori": r[2]}
              for r in csv_rows if r[0].startswith("commit_")}
    payload = {
        "suite": "schedulers",
        "config": {"max_nodes": CFG.max_nodes, "sched_batch": CFG.sched_batch,
                   "windows": WINDOWS, "fleet_b": FLEET_B,
                   "backend": jax.default_backend()},
        "rows": [{"name": n, "us_per_call": u, "derived": d}
                 for n, u, d in csv_rows],
        "commit_kernel": commit,
        "commit_kernel_no_regression": all(
            v["speedup_vs_fori"] >= 1.0 for v in commit.values()),
    }
    JSON_PATH.write_text(json.dumps(payload, indent=1))
    return payload


def run(csv_rows):
    windows = _windows()
    state0 = init_state(CFG)
    for name in SCHED_SET:
        fn = jax.jit(lambda s, w, n=name: eng.run_windows(
            s, w, CFG, get_scheduler(n)))
        out = fn(state0, windows)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        state, stats = fn(state0, windows)
        jax.block_until_ready(state)
        wall = time.perf_counter() - t0
        csv_rows.append((f"sched_{name}_wall", wall * 1e6 / WINDOWS,
                         float(stats["placements"][-1])))
        csv_rows.append((f"sched_{name}_balance_var", wall * 1e6 / WINDOWS,
                         float(stats["reserved_balance_var"][-1])))

    # many concurrent scheduler replicas on one workload (vmap over seeds)
    def one(seed):
        s, stats = eng.run_windows(state0, windows, CFG,
                                   get_scheduler("random"), seed=seed)
        return stats["placements"][-1]

    vr = jax.jit(jax.vmap(one))
    out = vr(jnp.arange(16))
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = vr(jnp.arange(16))
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    csv_rows.append(("sched_16_replicas_vmap_wall", wall * 1e6 / WINDOWS,
                     float(out.mean())))

    run_commit(csv_rows)
    _emit_json(csv_rows)
    return csv_rows


if __name__ == "__main__":
    rows = []
    run(rows)
    for r in rows:
        print(f"{r[0]},{r[1]:.2f},{r[2]:.6g}")
    commit = {n: d for n, _, d in rows if n.startswith("commit_")}
    worst = min(commit.values())
    print(f"# commit kernel vs fori_loop finaliser: worst speedup "
          f"{worst:.2f}x ({'PASS' if worst >= 1.0 else 'BELOW'} the 1.0x "
          f"no-regression bar); full rows -> {JSON_PATH.name}")
