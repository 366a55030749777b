"""Host-side streaming pipeline — the paper's five buffering parser actors +
the §V-B streaming alternative, in Python threads feeding the device.

The 191 GB trace never fits in memory (paper §III): windows are parsed and
tensorised on worker threads into a bounded buffer *ahead of simulation time*
(default 30 sim-minutes / ≤1M events, the paper's limits), grouped into
device-batches of B windows, and handed to the jitted scan while the next
batch is being parsed — double buffering ≈ Akka actors filling buffers while
the WorkloadGenerator drains them.

The pipeline is fully asynchronous end-to-end:

* batches are staged into a preallocated buffer ring (no per-batch
  ``np.stack`` allocations) and copied to the device *on the fill thread*
  (from a private host copy — see ``WindowPrefetcher._put`` for why), so
  host tensorisation + H2D transfer of batch k+1 overlap device compute of
  batch k;
* the drive loop never materialises the per-batch stats pytree — rows stay
  device-resident and dispatch runs ahead, bounded to
  ``WindowedDriver.max_inflight_batches`` so a fast parser cannot pile up
  unexecuted device work without limit; ``stats_frame()`` materialises
  them lazily. Apart from that backpressure bound, the only host sync per
  ``run()`` is the final ``block_until_ready``.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from repro.config import SimConfig
from repro.core import engine as engine_mod
from repro.core import trace
from repro.core.events import EventWindow, stack_windows
from repro.core.state import SimState, init_state


class _StagingPool:
    """Ring of preallocated (W, ...) per-field staging buffers.

    ``stack`` copies a batch of windows into the next ring slot — replacing
    the per-batch ``np.stack`` allocations on the consumer-critical fill
    path. Reuse is safe because ``WindowPrefetcher._put`` transfers a
    private host copy of the slot, never the slot itself: ``jit`` and
    ``device_put`` zero-copy alias 64-byte-aligned numpy buffers on the CPU,
    and a TPU transfer may still read its source after ``device_put``
    returns, so either would let a later refill corrupt an in-flight batch
    (regression-tested in tests/test_pipeline_async.py).
    """

    def __init__(self, proto: EventWindow, batch: int, slots: int = 4):
        self.batch = batch
        self._ring = [
            EventWindow(*[np.empty((batch,) + np.shape(f),
                                   np.asarray(f).dtype) for f in proto])
            for _ in range(slots)]
        self._i = 0

    def stack(self, windows: List[EventWindow]) -> EventWindow:
        if len(windows) != self.batch:        # short tail batch
            return stack_windows(windows)
        buf = self._ring[self._i]
        self._i = (self._i + 1) % len(self._ring)
        for j, w in enumerate(windows):
            for dst, src in zip(buf, w):
                dst[j] = src
        return buf


class WindowPrefetcher:
    """Bounded-buffer producer/consumer over packed EventWindows.

    The source may yield single windows (staged here into device batches of
    ``batch_windows``) or pre-stacked (W, ...) batches — e.g. straight from
    ``core.precompile.replay_windows`` — which skip the staging copy. Either
    way the fill thread transfers each batch from a private host copy (never
    the staging ring itself, see ``_put``), so the consumer dequeues
    device-resident tensors and the H2D transfer overlaps the simulation of
    earlier batches.
    """

    def __init__(self, cfg: SimConfig, window_iter: Iterator[EventWindow],
                 batch_windows: int = 32):
        self.cfg = cfg
        self.batch = batch_windows
        depth = max(1, min(cfg.buffer_windows // max(batch_windows, 1), 64))
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._src = window_iter
        self._done = object()
        self._lock = threading.Lock()
        self._events_in = 0       # produced into the buffer (fill thread)
        self._events_out = 0      # consumed by the driver (main thread)
        self._batches_in = 0      # batches put (fill thread): the next id
        self.batch_id = -1        # id of the batch last handed out
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    @property
    def events_buffered(self) -> int:
        """Cumulative events tensorised into the buffer (guarded read — the
        counter is written by the fill thread)."""
        with self._lock:
            return self._events_in

    def buffer_occupancy(self) -> Dict[str, int]:
        """Consistent snapshot of the producer/consumer ledger."""
        with self._lock:
            pending = self._events_in - self._events_out
            return {"events_in_buffer": pending,
                    "batches_in_buffer": self._q.qsize(),
                    "events_parsed": self._events_in,
                    "events_consumed": self._events_out}

    def _put(self, item: EventWindow):
        n = int(np.sum(np.asarray(item.n_valid)))
        # transfer from a private host copy, never from the staging-ring slot
        # itself: on the CPU device_put zero-copy ALIASES an aligned numpy
        # buffer, and on a TPU the H2D copy may still be reading its source
        # after device_put returns, so either way the next batch would
        # rewrite the slot under an in-flight batch. The transfer runs here
        # on the fill thread, overlapping device compute of earlier batches.
        i = self._batches_in
        with trace.span("feed.h2d", batch=i, windows=int(item.kind.shape[0]),
                        rows=n, bytes=sum(int(np.asarray(f).nbytes)
                                          for f in item)):
            dev = jax.device_put(jax.tree.map(np.array, item))
        with self._lock:
            self._events_in += n
        self._batches_in += 1
        self._q.put((dev, n, i))

    def _windows(self):
        """The source's items, each read inside a ``feed.source`` span
        tagged with the batch it goes into."""
        src = iter(self._src)
        while True:
            with trace.span("feed.source", batch=self._batches_in):
                w = next(src, self._done)
            if w is self._done:
                return
            yield w

    def _fill(self):
        batch: List[EventWindow] = []
        pool: Optional[_StagingPool] = None
        end = self._done
        try:
            for w in self._windows():
                if w.kind.ndim == 2:          # pre-stacked (W, E) batch
                    if batch:                 # keep arrival order
                        self._put(pool.stack(batch))
                        batch = []
                    self._put(w)
                    continue
                if pool is None:
                    pool = _StagingPool(w, self.batch)
                batch.append(w)
                if len(batch) == self.batch:
                    self._put(pool.stack(batch))
                    batch = []
            if batch:
                self._put(pool.stack(batch))
        except BaseException as e:  # noqa: BLE001 — re-raised by __iter__
            end = e
        finally:
            self._q.put(end)

    def __iter__(self):
        while True:
            # queued == 0 on entry: the device side waits on the feed
            with trace.span("drive.wait_feed", queued=self._q.qsize()):
                item = self._q.get()
            if item is self._done:
                return
            if isinstance(item, BaseException):
                # a source that failed mid-trace must fail the run, not
                # end it early as if the trace were complete
                raise item
            dev, n, self.batch_id = item
            with self._lock:
                self._events_out += n
            yield dev


def restored_resync_phase(windows_done: int, batch_windows: int,
                          resync_windows: int) -> int:
    """The ``_since_resync`` counter a from-zero driver holds after
    ``windows_done`` windows of constant ``batch_windows``-sized batches.

    Resumed runs (fleet ``restore``, service fork-point queries) seed their
    counter with this so the periodic incremental-accounting resync fires at
    the same absolute windows as the from-zero run they must match bitwise.
    With constant batches of k windows the resync lands every
    ``ceil(resync_windows / k) * k`` windows.
    """
    if not resync_windows:
        return 0
    k = max(1, batch_windows)
    cadence = ((resync_windows + k - 1) // k) * k
    return windows_done % cadence


class WindowedDriver:
    """Shared drive loop: prefetcher -> jitted advance -> stats/pacing.

    Subclasses own ``self.state`` and implement ``_advance(batch, seed)``
    (consume one stacked window batch, update ``self.state``, return the
    stats pytree). Everything else — pause/resume, the per-batch seed
    derivation, real-time pacing, stats accumulation, the periodic
    accounting resync — lives here once, so the single-trajectory
    Simulation and the batched ScenarioFleet (repro/scenarios/runner.py)
    cannot drift apart (the scenario fleet's lane-0 bit-identity guarantee
    depends on sharing this exact loop).

    The loop is sync-free in the steady state: ``_advance`` returns device
    arrays (its jitted body dispatches asynchronously) and the stats rows
    are appended without materialisation, so batch k+1's host work overlaps
    batch k's device compute. Runahead is bounded: once more than
    ``max_inflight_batches`` dispatches are outstanding the loop waits for
    the oldest — without this a parser that outpaces the device would
    accumulate unexecuted device programs (and their event tensors) for
    the whole trace. The final ``block_until_ready`` drains the tail.
    """

    state: SimState
    max_inflight_batches: int = 4

    def __init__(self, cfg: SimConfig, window_source: Iterator[EventWindow],
                 batch_windows: int = 32, seed: Optional[int] = None):
        self.cfg = cfg
        # under stats decimation every full batch must emit whole stats
        # chunks, so the global row cadence stays exactly every stride-th
        # window (only the final short tail batch may add a partial row)
        if cfg.stats_stride > 1:
            k = cfg.stats_stride
            batch_windows = ((batch_windows + k - 1) // k) * k
        self.prefetcher = WindowPrefetcher(cfg, window_source, batch_windows)
        self.seed = cfg.seed if seed is None else seed
        self.stats_rows: List[Dict[str, np.ndarray]] = []
        self._row_windows: List[int] = []
        self.windows_done = 0
        self.resyncs_done = 0
        self._since_resync = 0
        self._inflight: "collections.deque" = collections.deque()
        self._paused = threading.Event()

    def _advance(self, batch: EventWindow, seed: int):
        raise NotImplementedError

    def _resync(self) -> SimState:
        """Full accounting recompute (subclass hook; identity by default)."""
        return self.state

    def _dispatch_args(self) -> dict:
        """Extra args of the ``drive.dispatch`` span (subclass hook)."""
        return {}

    def pause(self):
        self._paused.set()

    def resume(self):
        self._paused.clear()

    def run(self, max_windows: Optional[int] = None,
            on_batch: Optional[Callable] = None) -> SimState:
        t_start = time.time()
        resync_every = (self.cfg.resync_windows
                        if self.cfg.incremental_accounting else 0)
        for batch in self.prefetcher:
            while self._paused.is_set():
                time.sleep(0.01)
            W = batch.kind.shape[0]
            with trace.span("drive.dispatch", batch=self.prefetcher.batch_id,
                            windows=W, inflight=len(self._inflight),
                            **self._dispatch_args()):
                stats = self._advance(batch, self.seed + self.windows_done)
            k = self.cfg.stats_stride
            m, r = divmod(W, k)
            self._row_windows.extend(
                [self.windows_done + (j + 1) * k for j in range(m)]
                + ([self.windows_done + W] if r else []))
            self.windows_done += W
            self.stats_rows.append(stats)
            self._inflight.append(stats)
            if len(self._inflight) > self.max_inflight_batches:
                with trace.span("drive.backpressure",
                                inflight=len(self._inflight)):
                    jax.block_until_ready(self._inflight.popleft())
            if resync_every:
                self._since_resync += W
                if self._since_resync >= resync_every:
                    with trace.span("drive.resync",
                                    windows_done=self.windows_done):
                        self.state = self._resync()
                    self.resyncs_done += 1
                    self._since_resync = 0
            if on_batch is not None:
                on_batch(self)
            if self.cfg.speed_factor > 0:
                sim_elapsed = self.windows_done * self.cfg.window_us / 1e6
                target_wall = sim_elapsed / self.cfg.speed_factor
                lag = target_wall - (time.time() - t_start)
                if lag > 0:
                    time.sleep(lag)
            if max_windows is not None and self.windows_done >= max_windows:
                break
        with trace.span("drive.drain", windows_done=self.windows_done):
            jax.block_until_ready(self.state)
        return self.state

    def stats_window_indices(self) -> np.ndarray:
        """The cumulative window count each stats row was emitted at.

        Stride 1 gives ``[1, 2, ..., windows_done]``; under stats decimation
        (``cfg.stats_stride == k``) it is ``[k, 2k, ...]`` plus, if the run
        ended mid-chunk, one final partial row at ``windows_done``.  The
        length always equals the leading dimension of every
        ``stats_frame()`` array.
        """
        return np.asarray(self._row_windows, dtype=np.int64)

    def stats_frame(self) -> Dict[str, np.ndarray]:
        """Concatenate per-batch stat rows into (n_rows, ...) arrays.

        Materialisation point of the async stats stream: device rows are
        pulled to host (and scalar rows normalised to length-1 vectors)
        here, once, in place — so repeated calls don't re-transfer and the
        drive loop itself never syncs on stats.  With ``stats_stride == 1``
        n_rows == windows_done; under decimation each batch contributes
        ceil(W / stride) rows whose window positions are
        ``stats_window_indices()``.
        """
        if not self.stats_rows:
            return {}
        for i, r in enumerate(self.stats_rows):
            self.stats_rows[i] = {k: np.atleast_1d(np.asarray(v))
                                  for k, v in r.items()}
        keys = self.stats_rows[0].keys()
        frame = {k: np.concatenate([r[k] for r in self.stats_rows])
                 for k in keys}
        if self.cfg.stats_stride > 1 and frame:
            # guard against the host-side cadence bookkeeping drifting from
            # the device-side scan_strided row semantics
            n_rows = len(next(iter(frame.values())))
            assert n_rows == len(self._row_windows), (
                f"strided stats cadence drift: {n_rows} frame rows vs "
                f"{len(self._row_windows)} tracked window indices")
        return frame


class Simulation(WindowedDriver):
    """End-to-end driver: trace source -> prefetcher -> scanned engine.

    Supports pause/snapshot/resume (paper §IV — restore is 'not implemented
    yet' there; it is here, via core/snapshot.py) and an optional real-time
    speed factor (sleeps so that sim-time advances at `speed_factor` x
    wall-clock, matching the paper's 75x experiments).
    """

    def __init__(self, cfg: SimConfig, window_source: Iterator[EventWindow],
                 scheduler: Optional[str] = None, batch_windows: int = 32,
                 seed: Optional[int] = None):
        super().__init__(cfg, window_source, batch_windows, seed)
        self.scheduler = scheduler or cfg.scheduler
        self.state = init_state(cfg)

    def _advance(self, batch: EventWindow, seed: int):
        self.state, stats = engine_mod.run_windows_jit(
            self.state, batch, self.cfg, self.scheduler, seed)
        return stats

    def _resync(self):
        return engine_mod.resync_accounting_jit(self.state, self.cfg)
