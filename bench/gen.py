"""The benchmark's traffic generator: one cell's packed event windows, made
from ``--seed`` by vectorised numpy.

A deployment file (``configs/<name>.json``) fixes the cluster and names its
family; a traffic file (``traffic/<name>.json``) fixes the workload model
and the segments a run replays. The family (``families/<family>.py``)
writes the flat event rows of a run and says how many trace windows they
need; this module packs them, as every family shares, into the program's
``EventWindow`` layout ready for ``core.precompile.precompile_stream``.
Trace window 0 holds everything before the trace's first timed window (the
cluster's declarations and the standing workload), split into
``events_per_window``-row chunks as the parser splits an overlong window.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple

import numpy as np

from repro.config import SimConfig
from repro.core.events import EventKind, EventWindow


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


def new_rows(n: int, cfg: SimConfig, **cols) -> Rows:
    """``n`` rows, zero but for the columns given (a narrower 2-D column
    fills the leading entries)."""
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


def cat(parts: List[Rows]) -> Rows:
    return Rows(*[np.concatenate([getattr(p, f) for p in parts])
                  for f in Rows._fields])


def take(r: Rows, m) -> Rows:
    return Rows(*[x[m] for x in r])


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
    return take(r, ~drop)


def windows(cfg: SimConfig, r: Rows, n_windows: int) -> Iterator[EventWindow]:
    """Sort rows into trace windows, split any window over
    ``events_per_window`` rows into chunks of that size, and pack each
    chunk into an ``EventWindow``; stops after ``n_windows`` chunks."""
    W, E = cfg.window_us, cfg.events_per_window
    r = _dedup(r, W, cfg.max_tasks)
    order = np.lexsort((np.arange(len(r.t)), r.t))
    r = take(r, order)
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


def horizon_for(cfg: SimConfig, tr: dict, batch: int,
                opening_rows: float) -> int:
    """Trace windows to generate so that the plan's segments are covered,
    given an estimate of trace window 0's rows (5% is added to it; window
    0's chunk count does not depend on the horizon)."""
    chunks = -(-int(opening_rows * 1.05) // cfg.events_per_window) + 1
    warm = max(chunks + 1, int(tr.get("warm_windows", 0)))
    warm = -(-warm // batch) * batch
    return warm + int(tr["steady_windows"]) + 1


def make(family, cfg: SimConfig, config: dict, tr: dict, seed: int,
         batch: int):
    """(rows, plan) of one run of the deployment ``config`` of ``family``:
    the same seed gives the same rows."""
    horizon = family.horizon(cfg, config, tr, batch)
    r = family.rows(cfg, config, tr, seed, horizon)
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
