"""Reduction of a profiler trace to the per-layer numbers.

Reads the ``.xplane.pb`` the JAX profiler writes (``jax.profiler.
ProfileData``, nothing beyond JAX). Device planes are ``/device:TPU:<i>``;
on each, the ``XLA Ops`` line holds every operation that ran and the
``XLA Modules`` line every launched program. The benchmark's own host
spans (``run.py``: ``restore``, ``trajectory``, ``dispatch``, ``resync``,
``feed.read``) are events of the host plane, on the same clock.

* the traced window runs from the first ``restore`` span's start to the
  last ``trajectory`` span's end;
* busy time is the union of a device's operation intervals inside the
  window, averaged over the devices (``busy_s``) and per device, in the
  order of the planes' names (``busy_by_device_s``);
* module time is the device time of each launched program, by name,
  averaged over the devices; :func:`chips_module_s` gives it summed over
  them, the device time of work that the chips split between them (a
  sharded fleet's lanes);
* an operation's time is its self time: its own interval less the
  operations nested in it (a loop's body runs nested in the loop),
  averaged over the devices;
* an idle gap is a stretch of the window in which device 0 runs no
  operation; it is named for the host span that covers its middle (the
  innermost: ``resync``, ``dispatch``, ``restore``, ``feed.read``), as
  ``drain`` when it follows the trajectory's last dispatch, or ``wait``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

HOST_SPANS = ("resync", "dispatch", "restore", "feed.read")
TOP = 10


def load(trace_dir: str):
    """(device planes {name: {line: [(name, start, end)]}}, host spans
    [(name, start, end)]) of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def from_profile(pd):
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [(e.name, e.start_ns, e.end_ns)
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS + ("trajectory",):
                        host.append((e.name, e.start_ns, e.end_ns))
    return devices, host


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short(op: str) -> str:
    """An HLO op's name without its text: ``%fusion.12 = f32[...] ...`` ->
    ``%fusion.12``."""
    return op.split(" = ", 1)[0]


def self_times(events, lo: float, hi: float) -> Dict[str, float]:
    """Per op name, the time inside [lo, hi) that the op spent outside the
    ops nested in it (a loop's body ops are nested in the loop's op)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, child time]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, end, child = stack.pop()
            out[name] = out.get(name, 0.0) + child[1] - child[0]

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2][0] += e - s
        stack.append([short(name), e, [0.0, e - s]])
    close(float("inf"))
    return out


def _label(mid: float, host, last_dispatch: float) -> str:
    covering = [n for n, s, e in host if s <= mid < e]
    for name in HOST_SPANS:
        if name in covering:
            return name
    if "trajectory" in covering:
        return "drain" if mid >= last_dispatch else "wait"
    return "none"


def reduce(devices, host) -> dict:
    """The traced window's numbers (seconds; see the module doc)."""
    starts = [s for n, s, e in host if n == "restore"]
    ends = [e for n, s, e in host if n == "trajectory"]
    if not starts or not ends or not devices:
        raise ValueError("the trace holds no traced trajectory or no TPU")
    lo, hi = min(starts), max(ends)
    busy, modules, ops = [], {}, {}
    gaps0 = None
    n_dev = len(devices)
    for dev in sorted(devices):
        lines = devices[dev]
        merged = union([(s, e) for _, s, e in lines.get("XLA Ops", [])],
                       lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in lines.get("XLA Modules", []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                modules[name] = modules.get(name, 0.0) + d / n_dev
        for name, d in self_times(lines.get("XLA Ops", []), lo, hi).items():
            ops[name] = ops.get(name, 0.0) + d / n_dev
        if gaps0 is None:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    last_dispatch = max([s for n, s, e in host if n == "dispatch"],
                        default=hi)
    gaps = sorted(((_label((s + e) / 2, host, last_dispatch), (e - s) / 1e9)
                   for s, e in gaps0), key=lambda g: -g[1])
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "busy_by_device_s": [b / 1e9 for b in busy],
            "modules_s": {k: v / 1e9 for k, v in modules.items()},
            "top_ops": [[k, v / 1e9] for k, v in
                        sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
            "top_gaps": [[n, d] for n, d in gaps[:TOP]],
            "gap_s_by_label": _by_label(gaps)}


def _by_label(gaps) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, d in gaps:
        out[n] = out.get(n, 0.0) + d
    return out


def module_s(reduced: dict, *needles: str) -> float:
    """Device seconds of the programs whose name holds any needle."""
    return sum(v for k, v in reduced["modules_s"].items()
               if any(n in k for n in needles))


def chips_module_s(reduced: dict, *needles: str) -> float:
    """Device seconds of the programs whose name holds any needle, summed
    over the chips."""
    return module_s(reduced, *needles) * len(reduced["busy_by_device_s"])


def reduce_dir(trace_dir: str) -> dict:
    return reduce(*load(trace_dir))
