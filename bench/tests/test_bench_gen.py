"""The GCD family's vectorised generator (``families/gcd.py``) against the
program's own model (``core/tracegen.py``) at a small size, its rates
against the source's published totals at the cell's size, its stacks
through ``validate_replay``, and its rows and packed windows against
hashes pinned before it moved out of ``gen.py``."""
import csv
import glob
import hashlib
import json
import os

import numpy as np
import pytest

import gen
import run
from conftest import BENCH, REPO, TINY_SIM, tiny_files
from repro.config import SimConfig
from repro.core.events import EventKind
from repro.core.precompile import (load_window_range, precompile_stream,
                                   validate_replay)

W = 5_000_000
HORIZON = 400
N_JOBS = 3000


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


gcd = run.load_family(REPO, _json("configs", "gcd_cell_a.json"))


# core/tracegen.py's tail of tasks per job; the traffic files fit its tail
# to the source's mean instead (test_rates_match_source_totals)
TRACEGEN_TAIL = {"tasks_per_job_pareto": 1.2, "max_tasks_per_job": 64}


def _gcd_model():
    cl = dict(_json("configs", "gcd_cell_a.json")["cluster"], n_machines=32)
    tr = dict(_json("traffic", "greedy_backlog.json"), standing_tasks=0,
              jobs_per_hour=N_JOBS / ((HORIZON - 1) * W / 3.6e9),
              **TRACEGEN_TAIL)
    return cl, tr


@pytest.fixture(scope="module")
def ours():
    cl, tr = _gcd_model()
    cfg = SimConfig(**dict(TINY_SIM, max_tasks=32768))
    return gcd.gcd_rows(cfg, cl, tr, 3, HORIZON)


@pytest.fixture(scope="module")
def theirs(tmp_path_factory):
    from repro.core import tracegen
    out = tmp_path_factory.mktemp("tracegen")
    tracegen.generate_trace(str(out), n_machines=32, n_jobs=N_JOBS,
                            horizon_windows=HORIZON, seed=3)

    def rows(table):
        path = glob.glob(str(out / f"{table}-*.csv"))[0]
        with open(path) as f:
            return [r for r in csv.reader(f)]

    return rows("task_events"), rows("task_usage")


def _their_tasks(theirs):
    events, usage = theirs
    submit, end, cpu, per_job = {}, {}, [], {}
    for r in events:
        key = (r[2], r[3])
        if r[5] == "0":
            submit[key] = int(r[0])
            cpu.append(float(r[9]))
            per_job[r[2]] = per_job.get(r[2], 0) + 1
        elif r[5] in ("2", "3", "4", "5"):
            end[key] = int(r[0])
    dur = [(end[k] - submit[k]) / W for k in end]
    period = {int(r[1]) - int(r[0]) for r in usage}
    return np.array(list(per_job.values())), np.array(dur), np.array(cpu), \
        period


def _our_tasks(r):
    add = r.kind == EventKind.ADD_TASK
    rem = r.kind == EventKind.REMOVE_TASK
    per_job = np.bincount(r.job[add])
    per_job = per_job[per_job > 0]
    t_add = dict(zip(r.slot[add], r.t[add]))
    dur = np.array([(t - t_add[s]) / W for s, t in zip(r.slot[rem],
                                                        r.t[rem])])
    use = r.kind == EventKind.UPDATE_TASK_USED
    order = np.lexsort((r.t[use], r.slot[use]))
    s, t = r.slot[use][order], r.t[use][order]
    period = set(np.diff(t)[np.diff(s) == 0].tolist())
    return per_job, dur, r.a[add][:, 0], period


def test_gcd_model_matches_tracegen(ours, theirs):
    jobs_a, dur_a, cpu_a, period_a = _our_tasks(ours)
    jobs_b, dur_b, cpu_b, period_b = _their_tasks(theirs)
    # tasks per job: heavy-tailed Pareto(1.2) capped at 64
    assert abs(np.mean(jobs_a == 1) - np.mean(jobs_b == 1)) < 0.04
    assert abs(np.median(jobs_a) - np.median(jobs_b)) <= 1
    assert jobs_a.max() <= 64 and jobs_b.max() <= 64
    # lognormal lifetimes, in windows
    for q in (25, 50, 75):
        a, b = np.percentile(dur_a, q), np.percentile(dur_b, q)
        assert abs(a - b) / b < 0.15, (q, a, b)
    # lognormal cpu requests clipped to [0.001, 0.5]
    for q in (10, 50, 90):
        a, b = np.percentile(cpu_a, q), np.percentile(cpu_b, q)
        assert abs(a - b) / b < 0.1, (q, a, b)
    # one usage sample every 5-minute period while a task lives
    assert period_a == period_b == {300_000_000}


@pytest.mark.parametrize("traffic", ["greedy_backlog", "mixed_fleet"])
def test_rates_match_source_totals(traffic):
    """At the cell's size, the generated rates are the published cell-A
    totals of the deployment file: jobs, tasks per job, machine events and
    usage rows per window."""
    conf = _json("configs", "gcd_cell_a.json")
    tot, cl = conf["source_totals"], conf["cluster"]
    tr = _json("traffic", f"{traffic}.json")
    windows = tot["trace_days"] * 86400 * 1e6 / W
    assert tr["jobs_per_hour"] == pytest.approx(
        tot["jobs"] / (tot["trace_days"] * 24), rel=0.01)
    cfg = SimConfig(**conf["sim"])
    rows, plan = gen.make(gcd, cfg, conf, tr, 2**31 + 11, 16)
    # arrivals come after window 0, which holds the standing workload
    per_job = np.bincount(rows.job[(rows.kind == EventKind.ADD_TASK)
                                   & (rows.t >= W)])
    per_job = per_job[per_job > 0]
    want = (tot["tasks"] - tr["standing_tasks"]) / tot["jobs"]
    assert per_job.max() <= tr["max_tasks_per_job"]
    # a heavy tail: the mean of one run's jobs swings, the model's does not
    tail = np.minimum(1 + np.random.default_rng(0).pareto(
        tr["tasks_per_job_pareto"], 10**6).astype(np.int64),
        tr["max_tasks_per_job"])
    assert tail.mean() == pytest.approx(want, rel=0.03)
    assert 0.2 * want < per_job.mean() < 5 * want
    churn = (rows.t >= W) & np.isin(rows.kind, [
        EventKind.ADD_NODE, EventKind.REMOVE_NODE,
        EventKind.UPDATE_NODE_RESOURCES])
    assert cl["churn_prob"] * cl["n_machines"] == pytest.approx(
        tot["machine_events_after_start"] / windows, rel=0.05)
    assert churn.sum() < 0.5 * plan.trace_windows
    use = rows.kind == EventKind.UPDATE_TASK_USED
    first = plan.trace_windows - plan.steady
    per_window = np.bincount(rows.t[use] // W)[first:plan.trace_windows]
    assert per_window.mean() == pytest.approx(
        tot["task_usage_rows"] / windows, rel=0.1)
    assert gen.steady_rows_per_window(cfg, rows, plan)["max"] <= \
        cfg.events_per_window


@pytest.mark.parametrize("traffic", ["greedy_backlog", "mixed_fleet"])
def test_stack_replays_verified(tmp_path, traffic):
    conf = _json("configs", "gcd_cell_a.json")
    conf["cluster"] = dict(conf["cluster"], n_machines=48)
    tr = dict(_json("traffic", f"{traffic}.json"), steady_windows=32,
              standing_tasks=700, **TRACEGEN_TAIL)
    cfg = SimConfig(**TINY_SIM)
    rows, plan = gen.make(gcd, cfg, conf, tr, 2**31 + 7, 8)
    windows = list(gen.windows(cfg, rows, plan.n_windows))
    path = str(tmp_path / "s.npz")
    precompile_stream(cfg, iter(windows), path, plan.n_windows,
                      shard_windows=8)
    validate_replay(path, cfg, verify=True)
    back = load_window_range(path, plan.warm, plan.warm + 8)
    for f in back._fields:
        np.testing.assert_array_equal(
            getattr(back, f), np.stack([getattr(w, f) for w in
                                        windows[plan.warm:plan.warm + 8]]))
    assert plan.warm % 8 == 0 and plan.steady == 32
    # the timed windows are whole trace windows
    assert gen.steady_rows_per_window(cfg, rows, plan)["max"] <= \
        cfg.events_per_window
    # the same seed gives the same rows, another seed others
    again, _ = gen.make(gcd, cfg, conf, tr, 2**31 + 7, 8)
    other, _ = gen.make(gcd, cfg, conf, tr, 2**31 + 8, 8)
    assert all(np.array_equal(x, y) for x, y in zip(rows, again))
    assert not np.array_equal(rows.t, other.t) or \
        not np.array_equal(rows.a, other.a)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for x in arrays:
        x = np.ascontiguousarray(x)
        h.update(f"{x.dtype.str}{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


PINS = _json("tests", "data", "gcd_pin.json")["seeds"]


@pytest.mark.parametrize("seed", sorted(PINS))
def test_gcd_family_reproduces_pinned_stack(seed):
    """The tiny deployment's rows and packed windows hash as they did when
    ``gen.py`` held the GCD model (``data/gcd_pin.json``)."""
    files = tiny_files()
    conf = files["bench/configs/tiny_gcd.json"]
    tr = files["bench/traffic/tiny_single.json"]
    cfg = SimConfig(**conf["sim"])
    rows, plan = gen.make(gcd, cfg, conf, tr, int(seed), tr["batch_windows"])
    windows = gen.windows(cfg, rows, plan.n_windows)
    assert list(plan) == PINS[seed]["plan"]
    assert _digest(rows) == PINS[seed]["rows"]
    assert _digest(getattr(w, f) for w in windows for f in w._fields) == \
        PINS[seed]["windows"]
