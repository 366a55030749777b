"""Async drive-loop + prefetcher behaviour:

* the driver no longer syncs per batch — stats rows stay device-resident
  and ``jax.block_until_ready`` runs exactly once per ``run()``;
* ``stats_frame`` materialises lazily (incl. scalar-row normalisation);
* the staging-buffer ring replaces per-batch ``np.stack`` without aliasing
  in-flight device batches (CPU jit would zero-copy raw numpy inputs);
* the prefetcher's event ledger is guarded and ``buffer_occupancy()``
  balances, and a source that fails mid-trace fails the run;
* donated state buffers are actually consumed;
* the periodic accounting resync fires on the configured cadence.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import REDUCED_SIM
from repro.core import engine as eng
from repro.core import pipeline as pipe
from repro.core.events import (EventKind, HostEvent, pack_window,
                               stack_windows)
from repro.core.state import init_state

CFG = dataclasses.replace(REDUCED_SIM, max_nodes=16, max_tasks=96,
                          max_events_per_window=64, sched_batch=24)


def _windows(n, cfg=CFG, tasks_per=3):
    out = [pack_window(cfg, [HostEvent(0, EventKind.ADD_NODE, m,
                                       a=(1.0, 1.0, 1.0))
                             for m in range(8)], 0)]
    slot = 0
    for i in range(1, n):
        evs = []
        for _ in range(tasks_per):
            evs.append(HostEvent(1, EventKind.ADD_TASK, slot % 48,
                                 a=(0.125, 0.125, 0.0)))
            slot += 1
        out.append(pack_window(cfg, evs, i))
    return out


def test_run_syncs_once_and_keeps_stats_on_device(monkeypatch):
    """One block_until_ready per run(), and the accumulated stats rows are
    still device arrays afterwards (nothing forced them to host)."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    sim = pipe.Simulation(CFG, iter(_windows(12)), batch_windows=4)
    sim.run()
    assert len(calls) == 1
    assert sim.windows_done == 12
    assert len(sim.stats_rows) == 3
    for row in sim.stats_rows:
        for v in row.values():
            assert isinstance(v, jax.Array), type(v)
    # materialisation happens in stats_frame, once, in place
    frame = sim.stats_frame()
    assert all(isinstance(v, np.ndarray) for v in frame.values())
    assert frame["n_running"].shape == (12,)
    for row in sim.stats_rows:
        for v in row.values():
            assert isinstance(v, np.ndarray)


def test_source_failure_mid_trace_fails_the_run():
    """A window source that raises after some batches must make run()
    raise on the consumer thread — not end early as if the trace were
    complete (the fill thread ends the queue either way)."""
    class TraceReadError(RuntimeError):
        pass

    def source():
        yield from _windows(9)
        raise TraceReadError("chunk 3 unreadable")

    sim = pipe.Simulation(CFG, source(), batch_windows=4)
    with pytest.raises(TraceReadError, match="chunk 3"):
        sim.run()
    # the batches before the failure were simulated; the rest were not
    assert sim.windows_done == 8


def test_runahead_is_bounded(monkeypatch):
    """Dispatch may run ahead of the device only by max_inflight_batches;
    beyond that the loop waits on the oldest outstanding batch."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    sim = pipe.Simulation(CFG, iter(_windows(12)), batch_windows=4)
    sim.max_inflight_batches = 1
    sim.run()
    # 3 batches: batches 2 and 3 each wait on an older one, plus the final
    # drain — backpressure without a sync on every batch
    assert len(calls) == 3


def test_stats_frame_normalises_scalar_and_device_rows():
    """Regression: 0-d per-batch stat rows — now jax scalars after async
    stats, previously numpy — normalise to length-1 vectors at
    materialisation and concatenate cleanly, including mixed host/device
    rows within one frame."""

    class ScalarDriver(pipe.WindowedDriver):
        def __init__(self, cfg, src, batch_windows):
            super().__init__(cfg, src, batch_windows)
            self.state = init_state(cfg)
            self._i = 0

        def _advance(self, batch, seed):
            self._i += 1
            dev = self._i % 2 == 0
            mk = jnp.asarray if dev else np.asarray
            return {"batch_idx": mk(self._i),            # 0-d row
                    "per_window": (jnp.zeros(batch.kind.shape[0])
                                   if dev else
                                   np.zeros(batch.kind.shape[0]))}

    drv = ScalarDriver(CFG, iter(_windows(12)), batch_windows=4)
    drv.run()
    frame = drv.stats_frame()
    assert frame["batch_idx"].shape == (3,)
    np.testing.assert_array_equal(frame["batch_idx"], [1, 2, 3])
    assert frame["per_window"].shape == (12,)


def test_prefetcher_occupancy_ledger_balances():
    ws = _windows(10)
    pf = pipe.WindowPrefetcher(CFG, iter(ws), batch_windows=4)
    batches = list(pf)
    assert sum(b.kind.shape[0] for b in batches) == 10
    occ = pf.buffer_occupancy()
    total = int(sum(int(w.n_valid) for w in ws))
    assert occ["events_parsed"] == total
    assert occ["events_consumed"] == total
    assert occ["events_in_buffer"] == 0
    assert occ["batches_in_buffer"] == 0
    assert pf.events_buffered == total


def test_prefetcher_batches_are_device_resident_and_unaliased():
    """The staging ring must never alias an already-yielded batch: with
    more batches than ring slots, every yielded batch still matches a
    reference np.stack of its windows bit-for-bit."""
    cfg = dataclasses.replace(CFG, buffer_windows=1000)
    ws = _windows(40, cfg=cfg)
    ref = [stack_windows(ws[i:i + 2]) for i in range(0, 40, 2)]
    pf = pipe.WindowPrefetcher(cfg, iter(ws), batch_windows=2)
    got = list(pf)
    assert len(got) == len(ref)
    for g in got:
        assert isinstance(g.kind, jax.Array)
    for g, r in zip(got, ref):
        for name in r._fields:
            np.testing.assert_array_equal(np.asarray(getattr(g, name)),
                                          getattr(r, name), err_msg=name)


def test_staging_pool_tail_batch_falls_back():
    ws = _windows(5)
    pf = pipe.WindowPrefetcher(CFG, iter(ws), batch_windows=4)
    shapes = [b.kind.shape[0] for b in pf]
    assert shapes == [4, 1]


def test_run_windows_jit_donates_state():
    """The donated SimState argument is consumed — XLA reuses its buffers
    for the output instead of double-buffering the task tables."""
    ws = jax.tree.map(jnp.asarray, stack_windows(_windows(4)))
    state = init_state(CFG)
    out, _ = eng.run_windows_jit(state, ws, CFG, "greedy", 0)
    jax.block_until_ready(out)
    assert state.task_req.is_deleted()
    assert not out.task_req.is_deleted()


@pytest.mark.parametrize("stride", [2, 4, 5])
def test_stats_frame_semantics_under_striding(stride, monkeypatch):
    """Stats decimation through the driver: frame length is the emitted row
    count (not windows_done), stats_window_indices() names each row's
    window, the final window is always reported, and the loop still syncs
    exactly once per run()."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    cfg = dataclasses.replace(CFG, stats_stride=stride)
    W = 14
    sim = pipe.Simulation(cfg, iter(_windows(W, cfg=cfg)), batch_windows=4)
    sim.run()
    assert len(calls) == 1
    assert sim.windows_done == W
    # the driver rounds batch_windows up to a stride multiple, so full
    # batches emit whole chunks and only the run's tail row is partial
    batch = max(4, ((4 + stride - 1) // stride) * stride)
    rows = 0
    left = W
    while left > 0:
        w = min(batch, left)
        rows += -(-w // stride)
        left -= w
    frame = sim.stats_frame()
    assert frame["n_running"].shape == (rows,)
    idx = sim.stats_window_indices()
    assert idx.shape == (rows,)
    assert idx[-1] == W                   # final state always reported
    assert all(b - a >= 1 for a, b in zip(idx, idx[1:]))
    # stride-1 reference: each strided row equals the stride-1 row at the
    # same window position (cumulative counters lose nothing)
    ref = pipe.Simulation(CFG, iter(_windows(W)), batch_windows=4)
    ref.run()
    rf = ref.stats_frame()
    for k in ("n_running", "n_pending", "completions", "evictions",
              "placements"):
        np.testing.assert_array_equal(frame[k], rf[k][idx - 1], err_msg=k)


def test_stats_window_indices_stride_one_is_identity():
    sim = pipe.Simulation(CFG, iter(_windows(12)), batch_windows=4)
    sim.run()
    np.testing.assert_array_equal(sim.stats_window_indices(),
                                  np.arange(1, 13))
    assert sim.stats_frame()["n_running"].shape == (12,)


def test_resync_fires_on_cadence():
    cfg = dataclasses.replace(CFG, resync_windows=8)
    sim = pipe.Simulation(cfg, iter(_windows(16, cfg=cfg)), batch_windows=4)
    sim.run()
    assert sim.resyncs_done == 2
    # full-recompute mode never resyncs (nothing drifts)
    cfg_f = dataclasses.replace(cfg, incremental_accounting=False)
    sim_f = pipe.Simulation(cfg_f, iter(_windows(16, cfg=cfg_f)),
                            batch_windows=4)
    sim_f.run()
    assert sim_f.resyncs_done == 0


def test_resync_restores_exact_recompute():
    """resync_accounting_jit == recompute_accounting on a drifted state."""
    cfg = CFG
    ws = jax.tree.map(jnp.asarray, stack_windows(_windows(6, cfg=cfg)))
    state, _ = eng.run_windows(init_state(cfg), ws, cfg,
                               __import__("repro.sched",
                                          fromlist=["get_scheduler"]
                                          ).get_scheduler("greedy"))
    # poison the tallies; the resync must rebuild them from the task table
    bad = state._replace(node_reserved=state.node_reserved + 0.5)
    oracle = eng.recompute_accounting(bad, cfg)
    fixed = eng.resync_accounting_jit(jax.tree.map(jnp.copy, bad), cfg)
    np.testing.assert_array_equal(np.asarray(fixed.node_reserved),
                                  np.asarray(oracle.node_reserved))
    np.testing.assert_array_equal(np.asarray(fixed.node_used),
                                  np.asarray(oracle.node_used))
