"""The program's tracing (``core/trace.py``):

* under ``jax.profiler``, a ``Simulation`` and a ``ScenarioFleet`` emit
  every ``drive.*`` and ``feed.*`` span with its args on the host plane
  that ``ProfileData`` reads, and ``feed.h2d``'s ``rows`` / ``bytes`` are
  the batch's;
* every pass scope is in the op metadata of the window program, on each
  path (``run_windows_jit``, the switch fleet, the switchless fleet);
* a running profiler changes no result, bitwise;
* ``--profile-dir`` on the CLIs writes a trace;
* nothing else in the program opens a span or a scope;
* the compile cache keeps builds that differ in their scopes apart.
"""
import dataclasses
import glob
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import REDUCED_SIM
from repro.core import engine as eng
from repro.core import pipeline as pipe
from repro.core import trace
from repro.core.events import EventKind, HostEvent, pack_window, stack_windows
from repro.core.state import init_state
from repro.scenarios import ScenarioFleet, ScenarioSpec, build_knobs
from repro.scenarios import batch as batch_mod
from repro.sched import snapshot_dispatch

CFG = dataclasses.replace(REDUCED_SIM, max_nodes=16, max_tasks=96,
                          max_events_per_window=64, sched_batch=24,
                          resync_windows=8)
N_WINDOWS, BATCH = 12, 4
SPECS = [ScenarioSpec(name="g", scheduler="greedy"),
         ScenarioSpec(name="ff", scheduler="first_fit",
                      node_outage_frac=0.2)]


def _windows(n=N_WINDOWS, cfg=CFG):
    out = [pack_window(cfg, [HostEvent(0, EventKind.ADD_NODE, m,
                                       a=(1.0, 1.0, 1.0))
                             for m in range(8)], 0)]
    for i in range(1, n):
        out.append(pack_window(cfg, [
            HostEvent(i, EventKind.ADD_TASK, (3 * i + j) % 48,
                      a=(0.125, 0.125, 0.0)) for j in range(3)], i))
    return out


def _make(kind, windows):
    if kind == "simulation":
        return pipe.Simulation(CFG, iter(windows), batch_windows=BATCH)
    return ScenarioFleet(CFG, iter(windows), SPECS, batch_windows=BATCH)


def _spans(log_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("drive.", "feed.")):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return {k: [a for _, a in sorted(v, key=lambda x: x[0])]
            for k, v in out.items()}


@pytest.mark.parametrize("kind", ["simulation", "fleet"])
def test_drive_and_feed_spans_with_args(tmp_path, kind):
    windows = _windows()
    with jax.profiler.trace(str(tmp_path)):
        drv = _make(kind, windows)
        drv.max_inflight_batches = 1      # batches 2 and 3 wait on older
        drv.run()
    spans = _spans(str(tmp_path))
    assert set(spans) == {"feed.source", "feed.h2d", "drive.wait_feed",
                          "drive.dispatch", "drive.backpressure",
                          "drive.resync", "drive.drain"}
    n_batches = N_WINDOWS // BATCH
    # one read per window and one that finds the source's end
    assert len(spans["feed.source"]) == N_WINDOWS + 1
    assert [a["batch"] for a in spans["feed.source"][:-1]] == \
        [w // BATCH for w in range(N_WINDOWS)]
    h2d = spans["feed.h2d"]
    assert [a["batch"] for a in h2d] == list(range(n_batches))
    for k, a in enumerate(h2d):
        b = stack_windows(windows[k * BATCH:(k + 1) * BATCH])
        assert a["windows"] == BATCH
        assert a["rows"] == int(np.sum(b.n_valid))
        assert a["bytes"] == sum(np.asarray(f).nbytes for f in b)
    disp = spans["drive.dispatch"]
    assert [a["batch"] for a in disp] == list(range(n_batches))
    assert all(a["windows"] == BATCH for a in disp)
    assert [a["inflight"] for a in disp] == [0, 1, 1]
    # a fleet names the dispatch it compiled and its chip lane maps
    want = ({"sched": "table", "layouts": 1} if kind == "fleet" else {})
    assert all({k: a[k] for k in ("sched", "layouts") if k in a} == want
               for a in disp)
    assert [a["inflight"] for a in spans["drive.backpressure"]] == [2, 2]
    # every get, the end of the feed's queue included, with its depth
    assert len(spans["drive.wait_feed"]) == n_batches + 1
    assert all(a["queued"] >= 0 for a in spans["drive.wait_feed"])
    assert [a["windows_done"] for a in spans["drive.resync"]] == [8]
    assert [a["windows_done"] for a in spans["drive.drain"]] == [N_WINDOWS]


def test_profiler_changes_no_result(tmp_path):
    def final(profiled):
        with trace.profile(str(tmp_path) if profiled else None):
            return _make("fleet", _windows()).run()

    plain, traced = final(False), final(True)
    for f in plain._fields:
        a, b = np.asarray(getattr(plain, f)), np.asarray(getattr(traced, f))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), f


def _lowered_text(path):
    windows = jax.tree.map(jnp.asarray, stack_windows(_windows(BATCH)))
    if path in ("greedy", "first_fit"):
        low = eng.run_windows_jit.lower(init_state(CFG), windows, CFG, path,
                                        0)
    else:
        cfg = dataclasses.replace(
            CFG, sched_dispatch="switch" if path == "switch" else "auto")
        knobs, names = build_knobs(SPECS)
        table = snapshot_dispatch(names)
        lane_scheds = (None if path == "switch" else
                       tuple(names.index(s.scheduler) for s in SPECS))
        low = batch_mod.run_scenarios_jit.lower(
            batch_mod.init_batched_state(cfg, len(SPECS)), windows, knobs,
            cfg, names, seed=0, has_storm=False, table=table,
            lane_scheds=lane_scheds)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("path", ["first_fit", "switch", "switchless"])
def test_every_pass_scope_is_in_the_window_program(path):
    text = _lowered_text(path)
    for name in trace.PASSES:
        assert name in text, (path, name)


def test_greedy_proposal_compiles_to_no_op():
    """The greedy proposal returns the base pass's scores as they are, so a
    greedy trajectory has no ``pass.propose`` op to time."""
    text = _lowered_text("greedy")
    assert trace.PROPOSE not in text
    for name in set(trace.PASSES) - {trace.PROPOSE}:
        assert name in text, name


def test_profile_dir_writes_a_trace(tmp_path):
    from repro.launch import simulate, whatif
    tiny = ["--nodes", "16", "--jobs", "12", "--windows", "8",
            "--batch-windows", "4"]
    simulate.main(tiny + ["--profile-dir", str(tmp_path / "sim")])
    whatif.main(tiny + ["--schedulers", "greedy,first_fit",
                        "--profile-dir", str(tmp_path / "fleet")])
    for d in ("sim", "fleet"):
        assert glob.glob(str(tmp_path / d / "plugins" / "profile" / "*" /
                             "*.xplane.pb")), d


def test_only_the_trace_module_opens_spans_and_scopes():
    src = pathlib.Path(trace.__file__).parents[1]
    users = [str(p.relative_to(src)) for p in src.rglob("*.py")
             if p.name != "trace.py" and
             ("TraceAnnotation" in p.read_text() or
              "named_scope" in p.read_text())]
    assert users == []


CACHE_PROBE = """
import os, sys, jax, jax.numpy as jnp
from repro import env
env.enable_compile_cache()
def scoped(name):
    def g(x):
        with jax.named_scope(name):
            return x * 2 + 1
    return g
for name in sys.argv[1:]:
    jax.jit(scoped(name)).lower(jnp.ones(8)).compile()
print(sum(f.startswith("jit_g-") for f in os.listdir(
    os.environ["JAX_COMPILATION_CACHE_DIR"])))
"""


def test_compile_cache_keys_hold_the_pass_scopes(tmp_path):
    """Programs that differ in their pass scopes alone are cached apart,
    so a traced run never executes a build with other scopes."""
    import subprocess
    import sys
    src = pathlib.Path(trace.__file__).parents[2]
    out = subprocess.run(
        [sys.executable, "-c", CACHE_PROBE, trace.EVENTS, trace.COMMIT,
         trace.COMMIT],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.split()[-1] == "2"


def test_compile_cache_keys_hold_no_checkout_path(tmp_path):
    """The same program run from two checkouts at other paths compiles
    once: file names in the op metadata are relative to the checkout."""
    import shutil
    import subprocess
    import sys
    src = pathlib.Path(trace.__file__).parents[1]
    cache = tmp_path / "cache"
    for name in ("a", "b"):
        root = tmp_path / name
        shutil.copytree(src, root / "src" / "repro",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (root / "probe.py").write_text(CACHE_PROBE)
        out = subprocess.run(
            [sys.executable, str(root / "probe.py"), trace.COMMIT],
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=str(root / "src"),
                     JAX_COMPILATION_CACHE_DIR=str(cache)),
            capture_output=True, text=True, timeout=120, check=True)
        assert out.stdout.split()[-1] == "1"
