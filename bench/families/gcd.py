"""The Google ClusterData 2011 v2 family: the generator and reference of a
deployment whose configuration file says ``"family": "gcd"``.

The statistical model, vectorised from ``core/tracegen.py``: machines from
five platform shapes with 1-4 obfuscated attributes and churn; Pareto tasks
per job; lognormal durations and requests; usage a Beta fraction of the
request, sampled every 5 minutes while a task lives; 25% of tasks
constrained; a standing workload the trace opens on. Trace window 0 holds
everything before GCD's 10-minute shift: the machine declarations and the
standing submits. Every task gets its own slot (no slot is reused inside a
run), so a row's slot is its task index; the generator checks that the
slots fit.

Its reference is the shared one (``reference.run_batch``) with a lane's
what-if knobs, and it compares no state beyond the shared leaves.
"""
from __future__ import annotations

import numpy as np

import gen
import reference as ref
from gen import Rows
from repro.config import SimConfig
from repro.core.events import EventKind, OP_EQ, OP_GT, OP_LT, OP_NE

USAGE_PERIOD_WINDOWS = 60             # GCD measurement period: 5 minutes
_GCD_OP = np.array([OP_EQ, OP_NE, OP_LT, OP_GT], np.int32)

STATE_FIELDS = ()
NUMBERS = ()


def _gcd_machines(cfg: SimConfig, cl: dict, rng, horizon: int) -> Rows:
    W = cfg.window_us
    M = int(cl["n_machines"])
    caps = np.asarray(cl["platform_caps"], np.float64)
    cap = caps[rng.integers(0, len(caps), M)]
    one = np.ones((M, 1))
    add = gen.new_rows(M, cfg, t=np.zeros(M, np.int64),
                       kind=EventKind.ADD_NODE, slot=np.arange(M),
                       a=np.hstack([cap, one]))
    # 1-4 distinct attributes per machine, values 1-3
    n_names = int(cl["attr_names"])
    lo, hi = cl["attrs_per_machine"]
    n_attr = rng.integers(lo, hi + 1, M)
    keys = np.argsort(rng.random((M, n_names)), axis=1)
    pick = np.arange(n_names)[None, :] < n_attr[:, None]
    m_idx = np.broadcast_to(np.arange(M)[:, None], pick.shape)[pick]
    a_idx = keys[pick]
    vlo, vhi = cl["attr_values"]
    vals = rng.integers(vlo, vhi + 1, len(m_idx))
    attr = gen.new_rows(len(m_idx), cfg, kind=EventKind.ADD_NODE_ATTR,
                        slot=m_idx, attr_idx=a_idx, attr_val=vals)
    # churn from window 1 on: remove, add back, or a cpu capacity update
    hit = rng.random((horizon - 1, M)) < float(cl["churn_prob"])
    w_idx, m = np.nonzero(hit)
    w_idx = w_idx + 1
    what = rng.integers(0, 3, len(m))
    scale = rng.choice([0.5, 1.0, 2.0], len(m))
    t = w_idx.astype(np.int64) * W
    kind = np.where(what == 0, EventKind.REMOVE_NODE,
                    np.where(what == 1, EventKind.ADD_NODE,
                             EventKind.UPDATE_NODE_RESOURCES))
    a = np.hstack([cap[m], np.ones((len(m), 1))])
    a[:, 0] *= np.where(what == 2, scale, 1.0)
    a[what == 0] = 0.0
    churn = gen.new_rows(len(m), cfg, t=t + (what == 1), kind=kind, slot=m,
                         a=a)
    return gen.cat([add, attr, churn])


def _job_shapes(rng, n_jobs: int, tr: dict):
    n_t = np.minimum(1 + rng.pareto(float(tr["tasks_per_job_pareto"]),
                                     n_jobs).astype(np.int64),
                     int(tr["max_tasks_per_job"]))
    prios, p = zip(*tr["priority_mix"])
    prio = rng.choice(np.asarray(prios, np.int32), n_jobs, p=np.asarray(p))
    return n_t, prio


def _gcd_tasks(cfg: SimConfig, tr: dict, rng, t0, dur_w, prio, job,
               slot0: int, horizon_us: int, first_use=None) -> Rows:
    """Submit, end, update and usage rows of tasks submitted at ``t0`` with
    lifetimes of ``dur_w`` windows (GCD model, vectorised); the first usage
    sample comes one period after the submit, or at ``first_use``."""
    W = cfg.window_us
    n = len(t0)
    slot = slot0 + np.arange(n)
    cpu = np.clip(rng.lognormal(-3.2, 0.8, n), 0.001, 0.5)
    ram = np.clip(rng.lognormal(-3.5, 0.9, n), 0.001, 0.5)
    disk = np.clip(rng.lognormal(-6.0, 1.0, n), 1e-5, 0.2)
    req = np.stack([cpu, ram, disk], 1)
    # constraints: 1-2 (attr, op, value) triples on a quarter of the tasks
    C = cfg.max_constraints
    cons = np.zeros((n, C, 3), np.int32)
    has = rng.random(n) < float(tr["constraint_prob"])
    n_c = np.where(has, rng.integers(1, 3, n), 0)
    for c in range(2):
        on = n_c > c
        cons[on, c, 0] = rng.integers(0, int(tr["constraint_attrs"]), on.sum())
        cons[on, c, 1] = _GCD_OP[rng.integers(0, 4, on.sum())]
        cons[on, c, 2] = rng.integers(0, 4, on.sum())
    add = gen.new_rows(n, cfg, t=t0, kind=EventKind.ADD_TASK, slot=slot,
                       a=req, prio=prio, job=job, cons=cons)
    # end: FINISH mostly; EVICT / FAIL / KILL a minority
    end_kind = rng.choice([4, 2, 3, 5], n, p=[.62, .15, .08, .15])
    t_end = t0 + dur_w * W + rng.integers(0, W, n)
    ends = t_end < horizon_us
    rm_a = np.zeros((n, 3))
    rm_a[:, 0] = np.where(end_kind == 2, 2.0, 0.0)
    rem = gen.take(gen.new_rows(n, cfg, t=t_end, kind=EventKind.REMOVE_TASK,
                                slot=slot, a=rm_a), ends)
    # occasional requirement update while alive (UPDATE_RUNNING)
    up = rng.random(n) < float(tr["update_prob"])
    t_up = t0 + rng.integers(1, np.maximum(dur_w, 2)) * W
    up &= t_up < np.minimum(t_end, horizon_us)
    upd = gen.take(gen.new_rows(n, cfg, t=t_up,
                                kind=EventKind.UPDATE_TASK_REQUIRED,
                                slot=slot, a=req * np.array([1.5, 1.0, 1.0]),
                                prio=prio), up)
    # usage samples every period while alive, a Beta fraction of the request
    frac = np.clip(rng.beta(1.3, 8.0, n), 0.01, 1.0)
    period = USAGE_PERIOD_WINDOWS * W
    first = t0 + period if first_use is None else first_use
    stop = np.minimum(t_end, horizon_us)
    k = np.maximum(0, -(-(stop - first) // period))
    idx = np.repeat(np.arange(n), k)
    nth = np.arange(len(idx)) - np.repeat(np.cumsum(k) - k, k)
    t_u = first[idx] + nth * period
    m = len(idx)
    u_cpu = cpu[idx] * frac[idx] * np.clip(rng.normal(1, .25, m), .05, 2)
    u_ram = ram[idx] * frac[idx]
    u = np.stack([u_cpu, u_ram, u_ram * 1.1, u_ram * 0.15,
                  np.clip(rng.lognormal(-4, 1, m), 0, .5),
                  disk[idx] * frac[idx],
                  np.clip(rng.normal(1.5, .4, m), .5, 4),
                  np.clip(rng.normal(.03, .01, m), .001, .2)], 1)
    use = gen.new_rows(m, cfg, t=t_u, kind=EventKind.UPDATE_TASK_USED,
                       slot=slot[idx], u=u)
    return gen.cat([add, rem, upd, use])


def gcd_rows(cfg: SimConfig, cl: dict, tr: dict, seed: int,
             horizon: int) -> Rows:
    """Every event of ``horizon`` trace windows (window 0 = before the
    shift: machines and the standing workload)."""
    W = cfg.window_us
    horizon_us = horizon * W
    machines = _gcd_machines(cfg, cl, np.random.default_rng([seed, 0]),
                             horizon)
    # standing workload: jobs submitted before the shift, long lifetimes;
    # their first usage sample falls at a uniform phase of the period
    srng = np.random.default_rng([seed, 1])
    n_standing = int(tr["standing_tasks"])
    parts = [machines]
    n_jobs_before = 0
    if n_standing:
        n_t, prio = _job_shapes(srng, n_standing, tr)
        n_t = n_t[:np.searchsorted(np.cumsum(n_t), n_standing) + 1]
        n_t[-1] -= n_t.sum() - n_standing
        n_jobs_before = len(n_t)
        job = np.repeat(np.arange(len(n_t)), n_t).astype(np.int32)
        prio = np.repeat(prio[:len(n_t)], n_t)
        mu, sigma = tr["standing_duration_lognormal"]
        dur = np.maximum(1, srng.lognormal(mu, sigma,
                                           n_standing).astype(np.int64))
        t0 = srng.integers(0, W, n_standing)
        phase = srng.integers(0, USAGE_PERIOD_WINDOWS * W, n_standing)
        parts.append(_gcd_tasks(cfg, tr, srng, t0, dur + 1, prio, job, 0,
                                horizon_us, first_use=W + phase))
    # arrivals: jobs_per_hour over the trace windows after the shift
    arng = np.random.default_rng([seed, 2])
    hours = (horizon - 1) * W / 3.6e9
    n_jobs = max(1, int(round(hours * float(tr["jobs_per_hour"]))))
    n_t, prio = _job_shapes(arng, n_jobs, tr)
    arrive = arng.integers(1, max(horizon - 4, 2), n_jobs)
    t_sub = arrive.astype(np.int64) * W + arng.integers(0, W, n_jobs)
    job = np.repeat(np.arange(n_jobs), n_t).astype(np.int32) + n_jobs_before
    n = int(n_t.sum())
    t0 = np.repeat(t_sub, n_t) + arng.integers(0, 1_000_000, n)
    mu, sigma = tr["duration_lognormal"]
    dur = np.maximum(1, arng.lognormal(mu, sigma, n).astype(np.int64))
    parts.append(_gcd_tasks(cfg, tr, arng, t0, dur, np.repeat(prio, n_t),
                            job, n_standing, horizon_us))
    if n_standing + n > cfg.real_task_slots:
        raise ValueError(f"{n_standing + n} tasks > {cfg.real_task_slots} "
                         "task slots: shorten the segment")
    return gen.cat(parts)


# ---------------------------------------------------------------------------
# the family's interface
# ---------------------------------------------------------------------------

def horizon(cfg: SimConfig, config: dict, tr: dict, batch: int) -> int:
    """Trace windows to generate: window 0 holds every machine with its
    attributes and the standing tasks (5% over their mean count)."""
    cl = config["cluster"]
    n0 = int(cl["n_machines"]) * (1 + sum(cl["attrs_per_machine"]) / 2) \
        + int(tr["standing_tasks"])
    return gen.horizon_for(cfg, tr, batch, n0)


def rows(cfg: SimConfig, config: dict, tr: dict, seed: int,
         horizon: int) -> Rows:
    return gcd_rows(cfg, config["cluster"], tr, seed, horizon)


def run_batch(state, windows, seed, lane: dict, *, dims: dict, dtype):
    """The shared reference over one lane: its scheduler, node outage and
    capacity scale."""
    return ref.run_batch(
        state, windows, seed, np.float32(lane.get("node_outage_frac", 0.0)),
        np.float32(lane.get("capacity_scale", 1.0)),
        salt=int(dims["scenario_salt"]), sched=lane["scheduler"],
        dims=(dims["max_nodes"], dims["sched_batch"], dims["max_tasks"]),
        dtype=dtype)
