"""The window program's device time by pass, and the program's own host
spans, from a profiler trace.

The program opens a ``jax.named_scope`` around each pass of its window
program (``pass.events``, ``pass.base``, ``pass.propose``, ``pass.commit``,
``pass.stats``) and ``TraceAnnotation`` spans in its drive loop and feed
(``drive.*``, ``feed.*``, counters as args). A device op's scope is in its
event *metadata* (the ``tf_op`` stat, the JAX name stack), which
``jax.profiler.ProfileData`` does not expose: this module decodes each
``/device:TPU:*`` plane's ``event_metadata`` and ``stat_metadata`` from the
``.xplane.pb`` with a small protobuf wire-format reader (standard library;
the lines, millions of events, are skipped unparsed) and joins them to
``xplane.from_profile``'s op events by program and op name.

* an op's pass is the innermost of the five names in its ``tf_op``
  (``vmap(pass.commit)`` counts as ``pass.commit``); an op under none is
  unscoped;
* pass time is op self time (``xplane.self_times``' rule) inside the
  window program's modules (``run_windows*`` / ``run_scenarios*``) inside
  the traced window (first ``restore`` to last ``trajectory``), averaged
  over the devices (``devices`` of them); a pass's device ms a lane-window
  (:func:`pass_ms`) is summed over the devices, since the chips of a
  sharded fleet each run their share of the lanes;
* idle time is device 0's, with the window and gaps of ``xplane.reduce``.

The names are spelled here, not imported from the program, so that a
renamed scope or span shows as a missing metric. A program without them
(an older commit) reads as nothing: no pass time and no spans.

    python3 bench/scopes.py <.xplane.pb[.gz] or trace dir>

prints the breakdown as JSON: pass split, unscoped ops over 1% of busy
time with their category and name stack, the ten ops with the most self
time with their pass and category, and the ten longest idle gaps named
by the program span over their middle.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Optional

import xplane

PASSES = ("pass.events", "pass.base", "pass.propose", "pass.commit",
          "pass.stats")
WINDOW_PROGRAMS = ("run_windows", "run_scenarios")
PROGRAM_SPANS = ("feed.source", "feed.h2d", "drive.wait_feed",
                 "drive.dispatch", "drive.backpressure", "drive.resync",
                 "drive.drain")
UNSCOPED = "unscoped"

# ---------------------------------------------------------------------------
# protobuf wire format: XSpace.planes (1); XPlane name (2), lines (3),
# event_metadata (4) and stat_metadata (5) maps (entry: key 1, value 2);
# XEventMetadata name (2), stats (5); XStatMetadata name (2); XStat
# metadata_id (1), double (2), uint64 (3), int64 (4), str (5), bytes (6),
# ref (7: the name of a stat metadata entry)
# ---------------------------------------------------------------------------


def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of a message in ``b[lo:hi]``: an int for a
    varint or fixed field, a (start, end) span for a length-delimited one."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = int.from_bytes(b[i:i + 8], "little")
            i += 8
        elif wire == 5:
            v = int.from_bytes(b[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b: bytes, entries) -> List[tuple]:
    """The value spans of a protobuf map's entries."""
    out = []
    for lo, hi in entries:
        for f, v in _fields(b, lo, hi):
            if f == 2:
                out.append(v)
    return out


def op_metadata(data: bytes) -> Dict[str, Dict[tuple, dict]]:
    """Per device plane, ``{(program id, op name): {"tf_op", "category"}}``
    for every op of its event metadata."""
    out = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stats = None, [], []
        for g, v in _fields(data, *plane):
            if g == 2:
                name = _text(data, v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name or not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for lo, hi in _map_values(data, stats):
            sid = sname = None
            for g, v in _fields(data, lo, hi):
                if g == 1:
                    sid = v
                elif g == 2:
                    sname = _text(data, v)
            stat_names[sid] = sname
        ops = {}
        for lo, hi in _map_values(data, events):
            op, st = None, {}
            for g, v in _fields(data, lo, hi):
                if g == 2:
                    op = _text(data, v)
                elif g == 5:
                    sid = val = None
                    for h, x in _fields(data, *v):
                        if h == 1:
                            sid = x
                        elif h in (5, 6):
                            val = _text(data, x)
                        elif h == 7:
                            val = stat_names.get(x)
                        elif h in (3, 4) and val is None:
                            val = x
                    st[stat_names.get(sid)] = val
            if op is not None:
                ops[(st.get("program_id"), op)] = {
                    "tf_op": st.get("tf_op") or "",
                    "category": st.get("hlo_category") or ""}
        out[name] = ops
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def trace_file(path: str) -> str:
    """The newest ``.xplane.pb`` under a trace directory, or ``path``."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "plugins", "profile",
                                              "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no trace under {path}")
        return files[-1]
    return path


def read_bytes(path: str) -> bytes:
    path = trace_file(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def program_spans(pd) -> List[tuple]:
    """The program's host spans: (name, start, end, {arg: value})."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def load(path: str) -> dict:
    """Op events, op metadata, the benchmark's and the program's host spans
    of a trace (a file, gzipped or not, or a trace directory)."""
    from jax.profiler import ProfileData
    data = read_bytes(path)
    pd = ProfileData.from_serialized_xspace(data)
    devices, host = xplane.from_profile(pd)
    return {"devices": devices, "host": host, "spans": program_spans(pd),
            "meta": op_metadata(data)}


def pass_of(tf_op: str) -> str:
    """The innermost pass scope of a JAX name stack, or ``UNSCOPED``."""
    found = UNSCOPED
    for part in tf_op.split("/"):
        part = part.split(":")[0]       # "op:type"
        while True:                     # vmap(pass.x) -> pass.x
            m = re.fullmatch(r"[\w.\-]+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in PASSES:
            found = part
    return found


def _program_id(module: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) if m else None


def _window(host) -> tuple:
    starts = [s for n, s, e in host if n == "restore"]
    ends = [e for n, s, e in host if n == "trajectory"]
    if starts and ends:
        return min(starts), max(ends)
    return float("-inf"), float("inf")


def window_modules(lines, lo: float, hi: float) -> List[tuple]:
    """(program id, start, end) of the window program's launches that
    overlap [lo, hi), clipped to it, in start order."""
    out = []
    for name, s, e in lines.get("XLA Modules", []):
        if any(p in name for p in WINDOW_PROGRAMS):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out.append((_program_id(name), s, e))
    return sorted(out, key=lambda m: m[1])


def nested_self_times(events, lo: float, hi: float):
    """Per event of ``events`` ((label, start, end), label None where the
    op has no name stack), clipped to [lo, hi): (label, self time), where
    self time is ``xplane.self_times``' rule and an unlabelled op takes the
    label of the ops nested in it where they all share one (a loop's
    control, which carries no name stack, belongs to its body's pass)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    span = {}
    parent = {}
    own = {}
    stack: List[list] = []          # [index, end]
    for i in order:
        _, s, e = events[i]
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        parent[i] = stack[-1][0] if stack else None
        if stack:
            own[stack[-1][0]] -= e - s
        own[i] = e - s
        span[i] = (s, e)
        stack.append([i, e])
    below: Dict[int, set] = {}
    for i in reversed([i for i in order if i in span]):
        label = events[i][0]
        mine = below.get(i, set())
        if label is None:
            label = next(iter(mine)) if len(mine) == 1 else None
        up = parent[i]
        if up is not None:
            below.setdefault(up, set()).update(
                mine | ({label} if label is not None else set()))
        own[i] = (label, own[i])
    return {i: own[i] for i in span}


def reduce(t: dict) -> dict:
    """Per-pass seconds, unscoped ops, feed and idle numbers of a trace
    loaded by :func:`load` (seconds; see the module doc)."""
    devices, host, spans = t["devices"], t["host"], t["spans"]
    lo, hi = _window(host)
    pass_s = {p: 0.0 for p in PASSES + (UNSCOPED,)}
    unscoped: Dict[str, dict] = {}
    top: Dict[str, list] = {}
    n_ops = n_joined = 0
    n_dev = max(len(devices), 1)
    for dev in sorted(devices):
        meta = t["meta"].get(dev, {})
        by_name: Dict[str, list] = {}
        for (pid, op), m in meta.items():
            by_name.setdefault(op, []).append(m)
        ops = sorted(devices[dev].get("XLA Ops", []), key=lambda o: o[1])
        starts = [o[1] for o in ops]
        for pid, ms, me in window_modules(devices[dev], lo, hi):
            inside = ops[bisect.bisect_left(starts, ms):
                         bisect.bisect_left(starts, me)]
            found = []
            for name, s, e in inside:
                m = meta.get((pid, name))
                if m is None and len(by_name.get(name, ())) == 1:
                    m = by_name[name][0]
                n_ops += 1
                n_joined += m is not None
                found.append(m)
            labelled = [(pass_of(m["tf_op"]) if m and m["tf_op"] else None,
                         s, e) for m, (_, s, e) in zip(found, inside)]
            for i, (label, d) in nested_self_times(labelled, ms,
                                                   me).items():
                d = d / 1e9 / n_dev
                pass_s[label or UNSCOPED] += d
                t_op = top.setdefault(xplane.short(inside[i][0]),
                                      [label or UNSCOPED, (found[i] or {})
                                       .get("category", ""), 0.0])
                t_op[2] += d
                if label in (None, UNSCOPED):
                    m = found[i] or {"category": "", "tf_op": ""}
                    u = unscoped.setdefault(
                        xplane.short(inside[i][0]),
                        {"category": m["category"], "tf_op": m["tf_op"],
                         "s": 0.0})
                    u["s"] += d
    out = {"window_s": (hi - lo) / 1e9 if hi > lo > float("-inf") else None,
           "pass_s": pass_s, "program_op_s": sum(pass_s.values()),
           "devices": n_dev,
           "ops": n_ops, "ops_joined": n_joined,
           "unscoped_ops": sorted(([k, v["category"], v["tf_op"], v["s"]]
                                   for k, v in unscoped.items()),
                                  key=lambda u: -u[3]),
           "top_ops": sorted(([k] + v for k, v in top.items()),
                             key=lambda o: -o[3])[:xplane.TOP]}
    out.update(_host_numbers(devices, host, spans, lo, hi))
    return out


def _host_numbers(devices, host, spans, lo, hi) -> dict:
    h2d = [(e - s, a.get("windows", 0)) for n, s, e, a in spans
           if n == "feed.h2d"]
    waits = [(s, e) for n, s, e, a in spans if n == "drive.wait_feed"]
    out = {"h2d_s": sum(d for d, _ in h2d) / 1e9,
           "h2d_windows": sum(w for _, w in h2d),
           "wait_feed_spans": len(waits),
           "idle_s": 0.0, "idle_in_wait_feed_s": 0.0, "top_gaps": []}
    if not devices or lo == float("-inf"):
        return out
    dev0 = devices[sorted(devices)[0]]
    merged = xplane.union([(s, e) for _, s, e in dev0.get("XLA Ops", [])],
                          lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    waited = xplane.union(waits, lo, hi)
    out["idle_s"] = sum(e - s for s, e in gaps) / 1e9
    out["idle_in_wait_feed_s"] = _overlap(gaps, waited) / 1e9
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:xplane.TOP]:
        mid = (s + e) / 2
        over = [(n, a) for n, ss, ee, a in sorted(spans, key=lambda x: x[1])
                if ss <= mid < ee]
        label, args = over[-1] if over else ("none", {})
        named.append([label, (e - s) / 1e9, args])
    out["top_gaps"] = named
    return out


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def of(ctx) -> Optional[dict]:
    """The reduced trace of a run's traced trajectory
    (``ctx["traced"]["dir"]``), read once and kept in ``ctx`` for the
    run's other readers; None, with the reason on stderr, where it cannot
    be read."""
    if "scopes" not in ctx:
        try:
            ctx["scopes"] = reduce(load(ctx["traced"]["dir"]))
        except Exception as e:  # noqa: BLE001 — a reader reports nothing
            print(f"scopes: cannot read the trace: {type(e).__name__}: {e}",
                  file=sys.stderr)
            ctx["scopes"] = None
    return ctx["scopes"]


def pass_ms(ctx, scope: str) -> Optional[float]:
    """Device ms of ``scope``, summed over the devices, per traced
    lane-window; None where no op carries it."""
    r = of(ctx)
    lane_windows = ctx["traced"]["lane_windows"]
    if r is None or not lane_windows or r["pass_s"][scope] <= 0:
        return None
    return 1e3 * r["pass_s"][scope] * r["devices"] / lane_windows


def summary(path: str) -> dict:
    """The breakdown the command line prints; ``busy_s`` is
    ``xplane.reduce``'s where the trace holds a traced trajectory."""
    t = load(path)
    r = reduce(t)
    try:
        r["busy_s"] = xplane.reduce(t["devices"], t["host"])["busy_s"]
    except ValueError:
        r["busy_s"] = r["program_op_s"]
    r["unscoped_ops"] = [u for u in r["unscoped_ops"]
                         if u[3] > 0.01 * r["busy_s"]]
    return r


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
