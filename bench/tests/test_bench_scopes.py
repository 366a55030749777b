"""The per-pass reduction (``scopes.py``) and the eight readers on it: on a
small trace recorded on a TPU v5e chip with the program's spans and pass
scopes (the tiny fleet cell, ``record_trace.py``), on the older trace
recorded without them, and on made-up events."""
import gzip
import os
import shutil

import pytest

import run
import scopes
import xplane
from conftest import REPO

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny_fleet_scoped.xplane.pb.gz")
UNSCOPED = os.path.join(DATA, "tiny_fleet.xplane.pb.gz")
READERS = ["pass.events.device_ms", "pass.base.device_ms",
           "pass.propose.device_ms", "pass.commit.device_ms",
           "pass.stats.device_ms", "pass.unscoped_share", "feed.h2d_ms",
           "drive.idle_feed_share"]
# the tiny fleet cell's traced trajectory: 32 steady windows x 4 lanes
TINY_LANE_WINDOWS = 32 * 4


def _ctx(tmp_path, trace):
    """A run's reader context around a trace, laid out as the profiler
    writes it."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(trace, "rb") as a, open(d / "t.xplane.pb", "wb") as b:
        shutil.copyfileobj(a, b)
    return {"traced": {"dir": str(tmp_path),
                       "lane_windows": TINY_LANE_WINDOWS}}


@pytest.fixture(scope="module")
def scoped():
    return scopes.load(SCOPED)


@pytest.fixture(scope="module")
def scoped_reduced(scoped):
    return scopes.reduce(scoped)


def test_same_op_events_as_profile_data(scoped):
    from jax.profiler import ProfileData
    with gzip.open(SCOPED, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            mine = scoped["devices"][plane.name]["XLA Ops"]
            theirs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            assert len(mine) == len(theirs) > 0
            assert sum(e - s for _, s, e in mine) == pytest.approx(
                sum(e - s for _, s, e in theirs), rel=1e-12)
            # every op event finds its metadata by name
            names = {op for _, op in scoped["meta"][plane.name]}
            assert {n for n, _, _ in mine} <= names


def test_every_reader_reads_the_scoped_trace(tmp_path):
    ctx = _ctx(tmp_path, SCOPED)
    for name in READERS:
        v = run.load_layer(REPO, name).read(ctx)
        assert v is not None and v > 0, name
        if name.endswith("share"):
            assert v <= 100, name


def test_passes_add_up_to_the_window_program(scoped, scoped_reduced):
    """The five passes and the unscoped part are the window program's op
    self time, as ``xplane.self_times`` counts it, inside the window."""
    devices, host = scoped["devices"], scoped["host"]
    lo = min(s for n, s, e in host if n == "restore")
    hi = max(e for n, s, e in host if n == "trajectory")
    total = 0.0
    for dev in devices:
        ops = devices[dev]["XLA Ops"]
        for _, ms, me in scopes.window_modules(devices[dev], lo, hi):
            inside = [o for o in ops if ms <= o[1] < me]
            total += sum(xplane.self_times(inside, ms, me).values()) / 1e9
    passes = scoped_reduced["pass_s"]
    assert sum(passes.values()) == pytest.approx(total, rel=1e-9)
    assert scoped_reduced["program_op_s"] == pytest.approx(total, rel=1e-9)
    assert all(passes[p] > 0 for p in scopes.PASSES)
    assert scoped_reduced["ops_joined"] == scoped_reduced["ops"] > 0


def test_program_spans_and_device_ops_share_a_clock(scoped):
    """The k-th launch of the window program starts after the k-th
    ``drive.dispatch`` span starts."""
    dispatch = sorted(s for n, s, e, a in scoped["spans"]
                      if n == "drive.dispatch")
    dev = scoped["devices"][sorted(scoped["devices"])[0]]
    launches = [s for _, s, _ in scopes.window_modules(
        dev, float("-inf"), float("inf"))]
    assert len(launches) == len(dispatch) > 0
    assert all(m >= d for d, m in zip(dispatch, launches))


def test_a_program_without_scopes_or_spans_reads_as_nothing(tmp_path):
    """An older program (the trace recorded before the scopes and spans
    were added): every new reader reports nothing, and none raises."""
    ctx = _ctx(tmp_path, UNSCOPED)
    for name in READERS:
        assert run.load_layer(REPO, name).read(ctx) is None, name


def test_a_missing_trace_reads_as_nothing(tmp_path, capsys):
    ctx = {"traced": {"dir": str(tmp_path), "lane_windows": 8}}
    assert run.load_layer(REPO, "pass.commit.device_ms").read(ctx) is None
    assert "cannot read the trace" in capsys.readouterr().err


@pytest.mark.parametrize("tf_op,want", [
    ("jit(run_windows_jit)/while/body/pass.events/scatter:", "pass.events"),
    ("jit(f)/while/body/closed_call/vmap(pass.commit)/while/body/add:",
     "pass.commit"),
    ("jit(f)/vmap(vmap(pass.base))/gather", "pass.base"),
    ("jit(f)/pass.propose/closed_call/pass.stats/x:", "pass.stats"),
    ("jit(f)/while/body/closed_call/vmap()/gather:", scopes.UNSCOPED),
    ("jit(f)/pass.eventsx/add:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_pass_of_a_name_stack(tf_op, want):
    assert scopes.pass_of(tf_op) == want


def test_a_loop_without_a_name_stack_takes_its_body_pass():
    events = [(None, 0, 100),               # loop control, body all commit
              ("pass.commit", 10, 30), ("pass.commit", 40, 60),
              (None, 200, 300),             # a loop over two passes
              ("pass.base", 210, 220), ("pass.stats", 230, 240),
              ("pass.events", 400, 410)]
    got = scopes.nested_self_times(events, 0, 1000)
    assert got == {0: ("pass.commit", 60), 1: ("pass.commit", 20),
                   2: ("pass.commit", 20), 3: (None, 80),
                   4: ("pass.base", 10), 5: ("pass.stats", 10),
                   6: ("pass.events", 10)}
    # the same self times as the trace reduction's rule
    named = [(str(i), s, e) for i, (_, s, e) in enumerate(events)]
    assert {int(k): v for k, v in xplane.self_times(named, 0, 1000)
            .items()} == {i: d for i, (_, d) in got.items()}


def test_idle_inside_wait_feed():
    devices = {"/device:TPU:0": {"XLA Ops": [("a", 10, 20), ("b", 40, 90)],
                                 "XLA Modules": []}}
    host = [("restore", 0, 10), ("trajectory", 10, 100)]
    spans = [("drive.wait_feed", 15, 35, {"queued": 0}),
             ("feed.h2d", 0, 4, {"windows": 2}),
             ("feed.h2d", 5, 8, {"windows": 2})]
    got = scopes._host_numbers(devices, host, spans, 0, 100)
    # idle: [0, 10), [20, 40), [90, 100); waited: [20, 35)
    assert got["idle_s"] == pytest.approx(40e-9)
    assert got["idle_in_wait_feed_s"] == pytest.approx(15e-9)
    assert got["h2d_s"] == pytest.approx(7e-9)
    assert got["h2d_windows"] == 4
    # longest first, each named by the program span over its middle
    assert [g[0] for g in got["top_gaps"]] == ["drive.wait_feed",
                                               "feed.h2d", "none"]


# --- a made-up XSpace, written with the wire format the reader decodes ----

def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _msg(*fields):
    """A message from (field number, value): int -> varint, str/bytes ->
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, events, stat_names):
    """XPlane: lines [(line name, [(metadata id, start ns, end ns,
    [(stat id, value)])])], event metadata {id: (name, [(stat id,
    (field, value))])}, stat metadata {id: name}."""
    def stat(sid, fv):
        return _msg((1, sid), fv)
    body = [(2, name)]
    for i, (lname, evs) in enumerate(lines):
        body.append((3, _msg((1, i), (2, lname), (3, 0), *[
            (4, _msg((1, mid), (2, s * 1000), (3, (e - s) * 1000),
                     *[(4, stat(sid, (4, v))) for sid, v in st]))
            for mid, s, e, st in evs])))
    for mid, (ename, st) in events.items():
        body.append((4, _msg((1, mid), (2, _msg(
            (1, mid), (2, ename), *[(5, stat(sid, fv)) for sid, fv in st])))))
    for sid, sname in stat_names.items():
        body.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*body)


def test_a_made_up_trace(tmp_path):
    """Op metadata by program (two programs share an op name), string
    stats given by reference, a loop without a name stack, span args."""
    stats = {1: "program_id", 2: "tf_op", 3: "hlo_category", 4: "fusion"}
    win = "jit(run_windows_jit)/while/body/"
    events = {
        10: ("jit_run_windows_jit(7)", []),
        11: ("jit_resync_accounting_jit(8)", []),
        20: ("%fusion.1 = f32[] fusion()", [
            (1, (3, 7)), (2, (5, win + "pass.events/add:")), (3, (7, 4))]),
        21: ("%fusion.1 = f32[] fusion()", [
            (1, (3, 8)), (2, (5, "jit(resync)/pass.events/add:"))]),
        22: ("%while.2 = () while()", [(1, (3, 7)), (3, (5, "while"))]),
        23: ("%fusion.3 = f32[] fusion()", [
            (1, (3, 7)), (2, (5, win + "vmap(pass.commit)/x:"))]),
        24: ("%copy.4 = f32[] copy()", [(1, (3, 7)), (3, (5, "copy"))]),
    }
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [(10, 100, 200, []), (11, 300, 320, [])]),
        ("XLA Ops", [(20, 100, 110, []), (22, 120, 180, []),
                     (23, 130, 170, []), (24, 190, 200, []),
                     (21, 300, 320, [])])], events, stats)
    host_stats = {1: "batch", 2: "windows", 3: "inflight", 4: "queued"}
    host_events = {1: ("restore", []), 2: ("trajectory", []),
                   3: ("drive.dispatch", []), 4: ("drive.wait_feed", []),
                   5: ("feed.h2d", [])}
    host = _plane("/host:CPU", [("python", [
        (1, 0, 50, []), (2, 50, 400, []), (4, 60, 95, [(4, 0)]),
        (3, 95, 99, [(1, 0), (2, 16), (3, 0)]),
        (5, 10, 20, [(1, 0), (2, 16)])])], host_events, host_stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))

    t = scopes.load(str(path))
    meta = t["meta"]["/device:TPU:0"]
    assert meta[(7, "%fusion.1 = f32[] fusion()")] == {
        "tf_op": win + "pass.events/add:", "category": "fusion"}
    assert meta[(8, "%fusion.1 = f32[] fusion()")]["tf_op"] == \
        "jit(resync)/pass.events/add:"
    assert ("drive.dispatch", 95, 99, {"batch": 0, "windows": 16,
                                       "inflight": 0}) in t["spans"]
    r = scopes.reduce(t)
    # the window program only (the resync's op is left out); the loop's
    # control takes its body's pass; the copy has no name stack
    assert r["pass_s"] == pytest.approx(
        {"pass.events": 10e-9, "pass.base": 0, "pass.propose": 0,
         "pass.commit": 60e-9, "pass.stats": 0, scopes.UNSCOPED: 10e-9})
    assert [u[:2] for u in r["unscoped_ops"]] == [["%copy.4", "copy"]]
    assert [o[:2] for o in r["top_ops"]] == [
        ["%fusion.3", "pass.commit"], ["%while.2", "pass.commit"],
        ["%fusion.1", "pass.events"], ["%copy.4", scopes.UNSCOPED]]
    assert r["top_ops"][1][2:] == ["while", pytest.approx(20e-9)]
    # idle in [0, 400): [0, 100), [110, 120), [180, 190), [200, 300),
    # [320, 400); the wait on the feed covers [60, 95)
    assert r["idle_s"] == pytest.approx(300e-9)
    assert r["idle_in_wait_feed_s"] == pytest.approx(35e-9)
    assert r["h2d_s"] == pytest.approx(10e-9) and r["h2d_windows"] == 16
