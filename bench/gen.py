"""The benchmark's traffic generator: one cell's packed event windows, made
from ``--seed`` by vectorised numpy.

A deployment file (``configs/<name>.json``) fixes the cluster; a traffic
file (``traffic/<name>.json``) fixes the workload model and the segments a
run replays. The statistical model, which writes windows in the program's
``EventWindow`` layout ready for ``core.precompile.precompile_stream``, is
the Google ClusterData 2011 v2 model of ``core/tracegen.py`` (machines
from five platform shapes with 1-4 obfuscated attributes and churn; Pareto
tasks per job; lognormal durations and requests; usage a Beta fraction of
the request, sampled every 5 minutes while a task lives; 25% of tasks
constrained; a standing workload the trace opens on).
Trace window 0 holds everything before GCD's 10-minute shift: the machine
declarations and the standing submits, split into ``events_per_window``-row
chunks as the parser splits an overlong window.

Every task gets its own slot (no slot is reused inside a run), so a row's
slot is its task index; the generator checks that the slots fit.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple

import numpy as np

from repro.config import SimConfig
from repro.core.events import EventKind, EventWindow, OP_EQ, OP_GT, OP_LT, \
    OP_NE

USAGE_PERIOD_WINDOWS = 60             # GCD measurement period: 5 minutes
_GCD_OP = np.array([OP_EQ, OP_NE, OP_LT, OP_GT], np.int32)


class Rows(NamedTuple):
    """Flat event rows of a whole run, in no particular order."""
    t: np.ndarray           # int64 microseconds from the start of window 0
    kind: np.ndarray        # int8
    slot: np.ndarray        # int32
    a: np.ndarray           # (n, R) float32
    u: np.ndarray           # (n, U) float32
    prio: np.ndarray        # int32
    job: np.ndarray         # int32
    cons: np.ndarray        # (n, C, 3) int32
    attr_idx: np.ndarray    # int32
    attr_val: np.ndarray    # int32


class Plan(NamedTuple):
    """Where a run's segments lie in the generated stack."""
    n_windows: int          # windows in the stack
    warm: int               # stack windows [0, warm) run in set-up
    steady: int             # stack windows [warm, warm + steady) are timed
    trace_windows: int      # trace windows covered (split chunks count once)


def _rows(n: int, cfg: SimConfig, **cols) -> Rows:
    R, U, C = cfg.n_resources, cfg.n_usage_stats, cfg.max_constraints
    base = dict(t=np.zeros(n, np.int64), kind=np.zeros(n, np.int8),
                slot=np.zeros(n, np.int32), a=np.zeros((n, R), np.float32),
                u=np.zeros((n, U), np.float32), prio=np.zeros(n, np.int32),
                job=np.zeros(n, np.int32),
                cons=np.zeros((n, C, 3), np.int32),
                attr_idx=np.zeros(n, np.int32),
                attr_val=np.zeros(n, np.int32))
    for k, v in cols.items():
        v = np.asarray(v)
        if base[k].ndim == 2 and v.ndim == 2 and v.shape[1] < base[k].shape[1]:
            base[k][:, :v.shape[1]] = v
        else:
            base[k][...] = v
    return Rows(**base)


def _cat(parts: List[Rows]) -> Rows:
    return Rows(*[np.concatenate([getattr(p, f) for p in parts])
                  for f in Rows._fields])


def _take(r: Rows, m) -> Rows:
    return Rows(*[x[m] for x in r])


# ---------------------------------------------------------------------------
# Google ClusterData 2011 v2 (cell A)
# ---------------------------------------------------------------------------

def _gcd_machines(cfg: SimConfig, cl: dict, rng, horizon: int) -> Rows:
    W = cfg.window_us
    M = int(cl["n_machines"])
    caps = np.asarray(cl["platform_caps"], np.float64)
    cap = caps[rng.integers(0, len(caps), M)]
    one = np.ones((M, 1))
    add = _rows(M, cfg, t=np.zeros(M, np.int64), kind=EventKind.ADD_NODE,
                slot=np.arange(M), a=np.hstack([cap, one]))
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
    attr = _rows(len(m_idx), cfg, kind=EventKind.ADD_NODE_ATTR, slot=m_idx,
                 attr_idx=a_idx, attr_val=vals)
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
    churn = _rows(len(m), cfg, t=t + (what == 1), kind=kind, slot=m, a=a)
    return _cat([add, attr, churn])


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
    add = _rows(n, cfg, t=t0, kind=EventKind.ADD_TASK, slot=slot, a=req,
                prio=prio, job=job, cons=cons)
    # end: FINISH mostly; EVICT / FAIL / KILL a minority
    end_kind = rng.choice([4, 2, 3, 5], n, p=[.62, .15, .08, .15])
    t_end = t0 + dur_w * W + rng.integers(0, W, n)
    ends = t_end < horizon_us
    rm_a = np.zeros((n, 3))
    rm_a[:, 0] = np.where(end_kind == 2, 2.0, 0.0)
    rem = _take(_rows(n, cfg, t=t_end, kind=EventKind.REMOVE_TASK, slot=slot,
                      a=rm_a), ends)
    # occasional requirement update while alive (UPDATE_RUNNING)
    up = rng.random(n) < float(tr["update_prob"])
    t_up = t0 + rng.integers(1, np.maximum(dur_w, 2)) * W
    up &= t_up < np.minimum(t_end, horizon_us)
    upd = _take(_rows(n, cfg, t=t_up, kind=EventKind.UPDATE_TASK_REQUIRED,
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
    use = _rows(m, cfg, t=t_u, kind=EventKind.UPDATE_TASK_USED,
                slot=slot[idx], u=u)
    return _cat([add, rem, upd, use])


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
    return _cat(parts)


# ---------------------------------------------------------------------------
# rows -> packed windows
# ---------------------------------------------------------------------------

def _dedup(r: Rows, W: int, T: int) -> Rows:
    """Within one window a task's lifecycle rows squash last-wins (as the
    parser's packer does): an update that its own removal follows in the
    same window is dropped."""
    win = r.t // W
    key = win * T + r.slot
    upd = r.kind == EventKind.UPDATE_TASK_REQUIRED
    rem = r.kind == EventKind.REMOVE_TASK
    drop = upd & np.isin(key, key[rem])
    return _take(r, ~drop)


def windows(cfg: SimConfig, r: Rows, n_windows: int) -> Iterator[EventWindow]:
    """Sort rows into trace windows, split any window over
    ``events_per_window`` rows into chunks of that size, and pack each
    chunk into an ``EventWindow``; stops after ``n_windows`` chunks."""
    W, E = cfg.window_us, cfg.events_per_window
    r = _dedup(r, W, cfg.max_tasks)
    order = np.lexsort((np.arange(len(r.t)), r.t))
    r = _take(r, order)
    win = r.t // W
    bounds = np.searchsorted(win, np.arange(win[-1] + 2))
    produced = 0
    for w in range(len(bounds) - 1):
        lo, hi = int(bounds[w]), int(bounds[w + 1])
        for c in range(lo, max(hi, lo + 1), E):
            if produced == n_windows:
                return
            yield _pack(cfg, r, c, min(c + E, hi), w)
            produced += 1
    w = len(bounds) - 1
    while produced < n_windows:
        yield _pack(cfg, r, 0, 0, w)
        produced += 1
        w += 1


def _pack(cfg: SimConfig, r: Rows, lo: int, hi: int, w: int) -> EventWindow:
    E = cfg.max_events_per_window
    n = hi - lo
    out = []
    for f, x in zip(Rows._fields, r):
        if f == "t":
            continue
        buf = np.zeros((E,) + x.shape[1:], x.dtype)
        buf[:n] = x[lo:hi]
        out.append(buf)
    kind, slot, a, u, prio, job, cons, attr_idx, attr_val = out
    t_off = np.zeros(E, np.int32)
    t_off[:n] = r.t[lo:hi] - w * cfg.window_us
    return EventWindow(kind=kind, slot=slot, a=a, u=u, prio=prio, job=job,
                       constraints=cons, attr_idx=attr_idx,
                       attr_val=attr_val, t_off=t_off,
                       n_valid=np.asarray(n, np.int32))


def plan(cfg: SimConfig, r: Rows, tr: dict, batch: int) -> Plan:
    """Warm segment: every chunk of trace window 0 plus the trace windows
    after it up to ``warm_windows`` at least, rounded up to whole
    batches; then ``steady_windows`` timed windows."""
    W, E = cfg.window_us, cfg.events_per_window
    n0 = int(np.sum(r.t < W))
    open_chunks = max(1, -(-n0 // E))
    warm = max(open_chunks + 1, int(tr.get("warm_windows", 0)))
    warm = -(-warm // batch) * batch
    steady = int(tr["steady_windows"])
    if steady % batch:
        raise ValueError(f"steady_windows={steady} is not a whole number of "
                         f"{batch}-window batches")
    n = warm + steady
    return Plan(n_windows=n, warm=warm, steady=steady,
                trace_windows=n - open_chunks + 1)


def horizon_for(cfg: SimConfig, cl: dict, tr: dict, batch: int) -> int:
    """Trace windows to generate so that the plan's segments are covered
    (window 0's chunk count does not depend on the horizon)."""
    n0 = int(cl["n_machines"]) * (1 + sum(cl["attrs_per_machine"]) / 2) \
        + int(tr["standing_tasks"])
    chunks = -(-int(n0 * 1.05) // cfg.events_per_window) + 1
    warm = max(chunks + 1, int(tr.get("warm_windows", 0)))
    warm = -(-warm // batch) * batch
    return warm + int(tr["steady_windows"]) + 1


def make(cfg: SimConfig, cl: dict, tr: dict, seed: int, batch: int):
    """(rows, plan) of one run: the same seed gives the same rows."""
    horizon = horizon_for(cfg, cl, tr, batch)
    r = gcd_rows(cfg, cl, tr, seed, horizon)
    p = plan(cfg, r, tr, batch)
    if p.trace_windows > horizon:
        raise ValueError("generated horizon too short for the plan")
    counts = np.bincount(r.t // cfg.window_us)[1:p.trace_windows]
    if counts.size and counts.max() > cfg.events_per_window:
        raise ValueError(f"a trace window after the opening holds "
                         f"{counts.max()} rows > {cfg.events_per_window}: "
                         "it would split, and the timed windows would not "
                         "be whole trace windows")
    return r, p


def submits_per_stack_window(cfg: SimConfig, r: Rows, p: Plan) -> np.ndarray:
    """Task submits in each window of the stack (window 0's chunks get
    the opening's submits together on the first chunk)."""
    win = r.t[r.kind == EventKind.ADD_TASK] // cfg.window_us
    per_trace = np.bincount(win, minlength=p.trace_windows)[:p.trace_windows]
    open_chunks = p.n_windows - p.trace_windows + 1
    out = np.zeros(p.n_windows, np.int64)
    out[0] = per_trace[0]
    out[open_chunks:] = per_trace[1:]
    return out


def steady_rows_per_window(cfg: SimConfig, r: Rows, p: Plan) -> Dict[str, int]:
    """Row counts of the timed windows (the steady segment must hold no
    split window, so every timed window is ``window_us`` of trace)."""
    W = cfg.window_us
    win = r.t // W
    first_trace = p.trace_windows - p.steady
    counts = np.bincount(win, minlength=p.trace_windows)[
        first_trace:p.trace_windows]
    return {"min": int(counts.min()), "max": int(counts.max()),
            "mean": int(counts.mean())}
