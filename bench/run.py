#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a deployment
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The deployment file names its family
(``bench/families/<family>.py``): the generator of its rows, its plain
reference, and the state leaves and numbers it compares beyond the shared
ones. Per-layer metrics are read by ``bench/layers/<metric>.py``.
Everything is found by name, so a new cell, or a new family, needs new
files and entries only.

Set-up (``setup_s``: process start to the first timed window) generates the
cell's windows from ``--seed`` (the family's rows, packed by ``gen.py``),
writes them with the program's ``precompile_stream``, runs the warm segment
(the trace's opening burst and the windows up to the first whole batch)
through the program's drive loop, keeps that state on the device as the
start snapshot, and runs one batch of a restored trajectory so that every
program the window uses is compiled or loaded from the persistent cache.

The measured window then runs trajectories back to back over the steady
segment, each restored from the snapshot, for ``--seconds`` (the last one
runs to its end): ``replay_windows`` -> ``WindowPrefetcher`` ->
``Simulation`` (one lane) or ``ScenarioFleet`` (several). ``sim_speed`` is
the simulated seconds of every lane over the wall seconds of the whole
window. A compile inside the window fails the run.

A cell on ``chips`` > 1 is one process over ``jax.devices()[:chips]``: its
fleet gets the program's ``fleet_mesh(chips)``, so its lanes shard over the
chips in order (``ScenarioFleet(mesh=)``, ``run_scenarios_sharded``) and
each window batch goes to every chip. The snapshot, each restore and each
kept state keep that lane sharding; a restored state whose sharding is not
the one ``shard_over_fleet`` gives fails the run, since its first launch
would reshard or compile inside the window. ``memory_peak_bytes`` is the
fullest chip's, and ``device.count`` the chips used. A one-chip cell runs
as it did before the mesh path: no mesh, the same spans and readings.

Correctness: in each trajectory the states before and after one batch,
drawn from the seed, and that batch's stats rows are kept as the program
produced them; after the window the family's reference replays the batch
from the state before it and ``check.py`` compares. With ``--trace 1`` the
first trajectory of the window runs under the profiler and the per-layer
metrics are read from its trace and from host spans.

The run fails (exit code 2, no result line) where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``peaks.json``; where the deployment names no family, or one with no
module; and where a number has no limit or the program's state lacks a
leaf the family compares.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import re
import sys
import threading

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROGRAM_SEED_MOD = 1 << 30      # the program's seeds are int32 scalars
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
FAMILY_NEEDS = ("horizon", "rows", "run_batch", "STATE_FIELDS", "NUMBERS")


class BenchError(Exception):
    """A run that cannot produce a result (exit code 2)."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def need(obj, name: str):
    """``obj.<name>`` where ``name`` is a private name of the program that the
    harness relies on (PERF.md lists them): the drive loop's launch and
    resync hooks its spans wrap, the resync phase a restore sets, the feed
    thread it joins. A program without it fails the run, so that no span
    goes missing and no resync moves unseen."""
    if not hasattr(obj, name):
        owner = getattr(obj, "__name__", type(obj).__name__)
        raise BenchError(f"the program's {owner} has no {name}, which the "
                         "benchmark relies on")
    return getattr(obj, name)


# ---------------------------------------------------------------------------
# files, found by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object          # the module bench/families/<family>.py
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    here = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, conf["file"]))
    traffic = _read_json(os.path.join(here, "traffic", w["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    limits = _read_json(os.path.join(here, "limits", "default.json"))
    own = os.path.join(here, "limits", workload + ".json")
    if os.path.exists(own):
        limits.update(_read_json(own))
    family = load_family(root, config)
    import check
    unlimited = [k for k in check.NUMBERS + tuple(family.NUMBERS)
                 if k not in limits]
    if unlimited:
        raise BenchError(f"{workload}: no limit for {unlimited} in "
                         "bench/limits/")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                family=family, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                limits=limits)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"[.-]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layer(root: str, metric: str):
    """The reader module of a per-layer metric: ``layers/<metric>.py``."""
    return _load(os.path.join(root, "bench", "layers", metric + ".py"),
                 "bench_layer_" + metric)


def load_family(root: str, config: dict):
    """The family module the deployment file names under ``family``:
    ``families/<family>.py``, which provides ``FAMILY_NEEDS``:

    * ``horizon(cfg, config, traffic, batch)``: the trace windows a run
      needs, and ``rows(cfg, config, traffic, seed, horizon)``: its
      ``gen.Rows``, the same for the same seed;
    * ``run_batch(state, windows, seed, lane, *, dims, dtype)``: the plain
      reference of one lane over a batch of windows, from a dict of state
      leaves, with ``dims`` the configuration's numbers and ``dtype`` the
      precision of scores and tallies (bfloat16 for the control); returns
      (state after, stats rows);
    * ``STATE_FIELDS``: state leaves it compares beyond
      ``reference.STATE_FIELDS``, read from the program's state by name;
    * ``NUMBERS``: the numbers it adds to ``check.NUMBERS``, each with its
      limit in ``limits/``, computed per lane by
      ``numbers(after, rows, ref_after, ref_rows)``.
    """
    name = config.get("family")
    if name is None:
        raise BenchError(f"configuration {config.get('name')!r} names no "
                         "family")
    path = os.path.join(root, "bench", "families", f"{name}.py")
    if not (isinstance(name, str) and NAME.fullmatch(name)
            and os.path.isfile(path)):
        raise BenchError(f"configuration {config.get('name')!r} names the "
                         f"family {name!r}, which has no "
                         f"bench/families/{name}.py")
    mod = _load(path, "bench_family_" + name)
    needs = FAMILY_NEEDS + (("numbers",) if getattr(mod, "NUMBERS", ())
                            else ())
    lacks = [a for a in needs if not hasattr(mod, a)]
    if lacks:
        raise BenchError(f"the family {name!r} has no {lacks}")
    return mod


def sim_config(config: dict):
    """The cell's SimConfig: the program's own where the deployment file
    names one (its numbers must match the file's), else built from the
    file's numbers over the program's defaults."""
    from repro.config import SimConfig
    if "program_config" in config:
        from repro.configs import get_sim_config
        cfg = get_sim_config(config["program_config"])
        off = {k: (getattr(cfg, k), v) for k, v in config["sim"].items()
               if getattr(cfg, k) != v}
        if off:
            raise BenchError(f"{config['name']}: the program's "
                             f"{config['program_config']} differs from the "
                             f"deployment file: {off}")
        return cfg
    return SimConfig(**config["sim"])


def lane_specs(traffic: dict) -> list:
    """The traffic file's lanes: a list of ``ScenarioSpec`` keyword dicts."""
    return traffic["lanes"]


# ---------------------------------------------------------------------------
# host spans and the feed clock
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of the run (``TraceAnnotation``: on the profiler's clock
    when it is on) and the host time spent reading the feed."""

    def __init__(self):
        self.lock = threading.Lock()
        self.feed_s = 0.0
        self.feed_windows = 0

    def reset(self):
        with self.lock:
            self.feed_s = 0.0
            self.feed_windows = 0

    @staticmethod
    @contextlib.contextmanager
    def span(name: str):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name):
            yield

    def feed(self, it):
        """Wrap a batch source: time each batch read (stack read plus
        decompress), on the prefetcher's fill thread."""
        while True:
            t0 = time.perf_counter()
            with self.span("feed.read"):
                try:
                    b = next(it)
                except StopIteration:
                    return
            with self.lock:
                self.feed_s += time.perf_counter() - t0
                self.feed_windows += int(b.kind.shape[0])
            yield b


class CompileCounter:
    """Counts traces and backend compiles (cache loads included) made on
    the thread that drives the run, which dispatches every program; other
    threads of a host process (a test's server, say) are not the run's."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.n = 0
        self.names = []
        self.thread = threading.get_ident()

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS and threading.get_ident() == self.thread:
            self.n += 1
            self.names.append(kw.get("fun_name", event))


# ---------------------------------------------------------------------------
# the program's drive loops, with the benchmark's spans around its calls
# ---------------------------------------------------------------------------

def spanned_class(base, spans: Spans):
    need(base, "_advance")
    need(base, "_resync")

    class Spanned(base):
        def _advance(self, batch, seed):
            with spans.span("dispatch"):
                return super()._advance(batch, seed)

        def _resync(self):
            with spans.span("resync"):
                return super()._resync()

    Spanned.__name__ = base.__name__
    return Spanned


class Runner:
    """Builds the program's drive loop for one segment of the stack; on
    ``chips`` > 1, a fleet whose lanes shard over the first ``chips``."""

    def __init__(self, cfg, traffic, stack, seed, spans, chips: int):
        from repro.core.pipeline import Simulation
        from repro.scenarios import ScenarioFleet, ScenarioSpec
        self.cfg, self.stack, self.seed, self.spans = cfg, stack, seed, spans
        self.batch = int(traffic["batch_windows"])
        self.lanes = lane_specs(traffic)
        self.fleet = len(self.lanes) > 1
        self.mesh = self.lane_sharding = None
        if self.fleet:
            self.specs = [ScenarioSpec(name=f"lane{i}", **ln)
                          for i, ln in enumerate(self.lanes)]
            self.cls = spanned_class(ScenarioFleet, spans)
        else:
            if set(self.lanes[0]) != {"scheduler"}:
                raise BenchError("a one-lane cell runs an unperturbed "
                                 "Simulation: give it a scheduler only")
            self.cls = spanned_class(Simulation, spans)
        if chips > 1:
            if not self.fleet:
                raise BenchError(f"a one-lane cell cannot use {chips} "
                                 "chips: only a fleet's lanes shard")
            import jax.numpy as jnp
            from repro.scenarios import batch as batch_mod
            self.mesh = batch_mod.fleet_mesh(chips)
            self.lane_sharding = batch_mod.shard_over_fleet(
                jnp.zeros(chips), self.mesh).sharding

    def make(self, start: int, n: int):
        from repro.core.precompile import replay_windows
        src = self.spans.feed(replay_windows(self.stack, batch=self.batch,
                                             n_windows=n,
                                             start_window=start))
        if self.fleet:
            return self.cls(self.cfg, src, self.specs,
                            batch_windows=self.batch, seed=self.seed,
                            mesh=self.mesh)
        return self.cls(self.cfg, src, scheduler=self.lanes[0]["scheduler"],
                        batch_windows=self.batch, seed=self.seed)

    def restore(self, drv, snap, windows_done: int):
        """Resume ``drv`` from a device copy of ``snap`` at window
        ``windows_done``, as ``ScenarioFleet.restore`` resumes from a file.
        On a mesh the copy keeps the fleet's lane sharding."""
        import jax
        import jax.numpy as jnp
        from repro.core.pipeline import restored_resync_phase
        drv.state = jax.tree.map(jnp.copy, snap)
        if self.mesh is not None:
            off = sorted({str(x.sharding) for x in jax.tree.leaves(drv.state)
                          if x.sharding != self.lane_sharding})
            if off:
                raise BenchError(f"a restored state is not lane-sharded as "
                                 f"shard_over_fleet gives "
                                 f"({self.lane_sharding}): {off}")
        drv.windows_done = windows_done
        need(drv, "_since_resync")
        drv._since_resync = restored_resync_phase(
            windows_done, drv.prefetcher.batch,
            self.cfg.resync_windows if self.cfg.incremental_accounting else 0)


def resync_ends(cfg, warm: int, batch: int, n_batches: int) -> set:
    """Batch indices of a restored trajectory after which ``WindowedDriver``
    resyncs the accounting."""
    from repro.core.pipeline import restored_resync_phase
    every = cfg.resync_windows if cfg.incremental_accounting else 0
    if not every:
        return set()
    since = restored_resync_phase(warm, batch, every)
    out = set()
    for k in range(n_batches):
        since += batch
        if since >= every:
            out.add(k)
            since = 0
    return out


class Capture:
    """Keeps the state before and after batch ``k`` of a trajectory, and
    that batch's stats rows, as the timed path produced them."""

    def __init__(self, k: int, batch: int, warm: int):
        self.k, self.batch, self.warm = k, batch, warm
        self.before = self.after = self.rows = None

    def start(self, snap):
        import jax
        import jax.numpy as jnp
        self.before = jax.tree.map(jnp.copy, snap) if self.k == 0 else None
        self.after = self.rows = None

    def __call__(self, drv):
        import jax
        import jax.numpy as jnp
        done = drv.windows_done - self.warm
        if done == self.k * self.batch:
            self.before = jax.tree.map(jnp.copy, drv.state)
        elif done == (self.k + 1) * self.batch:
            self.after = jax.tree.map(jnp.copy, drv.state)
            self.rows = drv.stats_rows[self.k]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(jax, chips: int, require_tpu: bool, peaks: dict) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu:
        if d0.platform != "tpu":
            raise BenchError(f"no TPU: JAX sees {d0.platform} devices; this "
                             "benchmark measures the chip only")
        if d0.device_kind not in peaks:
            raise BenchError(f"device kind {d0.device_kind!r} is not in "
                             "peaks.json")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             compile_cache: bool = True, t_start: float = None,
             trace_out: str = None, control: bool = False) -> dict:
    """One run of ``workload``: its result line as a dict (see the module
    doc). ``require_tpu=False`` and ``compile_cache=False`` let the CPU
    tests drive a tiny cell (a CPU run measures nothing, so it skips the
    chip and the no-compile checks); ``trace_out`` keeps the traced run's
    ``.xplane.pb``; ``control`` compares the bfloat16 control in the
    program's place."""
    with CompileCounter() as compiles:
        return _run_cell(workload, seed, seconds, trace, root, require_tpu,
                         compile_cache,
                         T_PROCESS if t_start is None else t_start,
                         trace_out, control, compiles)


def _run_cell(workload, seed, seconds, trace, root, require_tpu,
              compile_cache, t_start, trace_out, control, compiles) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import env
    except ImportError as e:
        raise BenchError(f"the program (src/repro) is not in this checkout: "
                         f"{e}")
    cell = resolve(root, workload)
    cache = os.path.join(root, "bench", ".cache")
    import jax
    peaks = _read_json(os.path.join(HERE, "peaks.json"))
    device = device_info(jax, cell.chips, require_tpu, peaks)
    if compile_cache:
        env.enable_compile_cache()
    import gen
    from repro.core.precompile import precompile_stream, validate_replay

    cfg = sim_config(cell.config)
    tr = cell.traffic
    batch = int(tr["batch_windows"])
    rows, plan = gen.make(cell.family, cfg, cell.config, tr, seed, batch)
    rng = np.random.default_rng([seed, 7])
    n_batches = plan.steady // batch
    skip = resync_ends(cfg, plan.warm, batch, n_batches)
    submits = gen.submits_per_stack_window(cfg, rows, plan)[plan.warm:]
    # a batch with new work to schedule, and no resync at its end
    busy = [k for k in range(n_batches)
            if submits[k * batch:(k + 1) * batch].sum() > 0]
    choices = [k for k in busy if k not in skip] or \
        [k for k in range(n_batches) if k not in skip] or [0]
    k_check = int(rng.choice(choices))
    lo = plan.warm + k_check * batch
    kept = []

    def stash(it):
        for i, w in enumerate(it):
            if lo <= i < lo + batch:
                kept.append(w)
            yield w

    os.makedirs(cache, exist_ok=True)
    stack = os.path.join(cache, f"{workload}.npz")
    precompile_stream(cfg, stash(gen.windows(cfg, rows, plan.n_windows)),
                      stack, plan.n_windows, shard_windows=batch)
    validate_replay(stack, cfg)
    steady_rows = gen.steady_rows_per_window(cfg, rows, plan)
    del rows
    check_windows = {f: np.stack([getattr(w, f) for w in kept])
                     for f in kept[0]._fields}
    del kept

    spans = Spans()
    prog_seed = seed % PROGRAM_SEED_MOD
    runner = Runner(cfg, tr, stack, prog_seed, spans, cell.chips)
    lanes = len(runner.lanes)
    # warm segment: the opening burst, untimed; its end is the snapshot
    drv = runner.make(0, plan.warm)
    snap = drv.run()
    if drv.windows_done != plan.warm:
        raise BenchError(f"warm segment ran {drv.windows_done} of "
                         f"{plan.warm} windows")
    lacks = [f for f in cell.family.STATE_FIELDS if not hasattr(snap, f)]
    if lacks:
        raise BenchError(f"the program's state has no {lacks}, which the "
                         f"family {cell.config['family']!r} compares")
    occupancy = _occupancy(snap)
    del drv
    # every program of the window, once: the resync, and one restored batch
    drv = runner.make(plan.warm, batch)
    runner.restore(drv, snap, plan.warm)
    jax.block_until_ready(drv._resync())
    runner.restore(drv, snap, plan.warm)
    cap = Capture(k_check, batch, plan.warm)
    cap.start(snap)
    drv.run(on_batch=cap)
    del drv, cap
    gc.collect()

    # the measured window
    n0 = compiles.n
    spans.reset()
    cap = Capture(k_check, batch, plan.warm)
    t0 = time.time()
    setup_s = t0 - t_start
    deadline = time.perf_counter() + seconds
    w0 = time.perf_counter()
    trajectories = lane_windows = 0
    traced = None
    while True:
        n = plan.steady
        tracing = trace and trajectories == 0
        if tracing:
            tdir = os.path.join(cache, f"trace-{workload}")
            _clean(tdir)
            jax.profiler.start_trace(tdir)
        with spans.span("restore"):
            drv = runner.make(plan.warm, n)
            runner.restore(drv, snap, plan.warm)
            if not tracing:
                cap.start(snap)
        with spans.span("trajectory"):
            drv.run(on_batch=None if tracing else cap)
        if tracing:
            traced = {"dir": tdir,
                      "lane_windows": (drv.windows_done - plan.warm) * lanes}
            jax.profiler.stop_trace()
        got = drv.windows_done - plan.warm
        if got != n:
            raise BenchError(f"a trajectory ran {got} of {n} windows")
        need(drv.prefetcher, "_thread").join(60)
        lane_windows += got * lanes
        trajectories += 1
        del drv
        if time.perf_counter() >= deadline and cap.rows is not None:
            break
    wall = time.perf_counter() - w0
    if compiles.n != n0 and require_tpu:
        raise BenchError(f"{compiles.n - n0} compiles inside the measured "
                         f"window: {compiles.names[n0:]}")

    mem = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    device["memory_peak_bytes"] = int(max(m.get("peak_bytes_in_use", 0)
                                          for m in mem))
    sim_s = lane_windows * cfg.window_us / 1e6
    e2e = {"sim_speed": sim_s / wall, "setup_s": setup_s}

    # correctness, once the program's state is freed
    host = jax.tree.map(np.asarray, (cap.before, cap.after, cap.rows))
    del snap, cap
    gc.collect()
    import check
    t_check = time.perf_counter()
    batch_seed = prog_seed + plan.warm + k_check * batch
    chips = jax.devices()[:cell.chips]
    if control:
        host = (host[0],) + check.control_outputs(
            cell.family, cfg, runner.lanes, host[0], check_windows,
            batch_seed, chips)
    verdict = check.compare(cell.family, cfg, runner.lanes, host[0], host[1],
                            host[2], check_windows, batch_seed, cell.limits,
                            chips)
    check_s = time.perf_counter() - t_check

    out = {"correct": verdict["correct"], "attempted": trajectories * lanes,
           "failed": verdict["failed_lanes"],
           "metrics": {}, "device": device}
    t_read = time.perf_counter()
    if trace:
        import xplane
        reduced = xplane.reduce_dir(traced["dir"])
        ctx = {"trace": reduced, "spans": spans, "traced": traced,
               "lanes": lanes, "cfg": cfg, "config": cell.config}
        for m in cell.per_layer:
            v = load_layer(root, m["name"]).read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["top_gaps"]}
        if trace_out:
            import glob
            import shutil
            shutil.copy(glob.glob(os.path.join(
                traced["dir"], "plugins", "profile", "*", "*.xplane.pb"))[0],
                trace_out)
        _clean(traced["dir"])
    else:
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    os.remove(stack)
    log(f"[{workload}] seed={seed} device={device['kind']} lanes={lanes} "
        f"warm={plan.warm} steady={plan.steady} batch={batch} "
        f"steady_rows/window={steady_rows} warm_state={occupancy} "
        f"trajectories={trajectories} lane_windows={lane_windows} "
        f"wall_s={wall:.3f} sim_speed={e2e['sim_speed']:.3f} "
        f"setup_s={setup_s:.3f} checked_batch={k_check} "
        f"check_s={check_s:.3f} trace_read_s="
        f"{time.perf_counter() - t_read if trace else 0:.3f}")
    out["checks"] = verdict["numbers"]
    return out


def _occupancy(state) -> dict:
    """Running and pending tasks per lane of a state (an earlier output
    line's counts, so a change in the program's occupancy shows)."""
    s = np.atleast_2d(np.asarray(state.task_state))
    return {"running": [int(x) for x in (s == 2).sum(1)],
            "pending": [int(x) for x in (s == 1).sum(1)]}


def _clean(path: str):
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (BenchError, FileNotFoundError, KeyError) as e:
        log(f"bench: {type(e).__name__}: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
