"""The trace readers on one device plane and on several.

On the one-plane trace recorded on a TPU v5e chip (the tiny fleet cell,
``record_trace.py``) every per-layer reading, and the reductions it is made
from, are pinned bit for bit: a reader that learns about several chips may
not move what one chip reads. On a made-up four-plane trace (four chips,
each running a quarter of the lanes) the per-lane-window readers count the
chips' summed device time, and the skew reads its definition."""
import gzip
import hashlib
import json
import os
import shutil

import pytest

import run
import scopes
import xplane
from conftest import REPO

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tiny_fleet_scoped.xplane.pb.gz")
TINY_LANE_WINDOWS = 32 * 4

# every per-layer reading of the recorded trace, as the readers read it
# before they learned about several chips (``feed.read_ms`` from a fixed
# feed clock of 0.25 s over 64 windows)
PINNED = {
    "feed.read_ms": 3.90625,
    "device.idle_share": 43.15479909677789,
    "step.device_ms": 0.7488567734375,
    "resync.device_share": 0.8470176629343805,
    "pass.events.device_ms": 0.07413952343750162,
    "pass.base.device_ms": 0.00558643750000001,
    "pass.propose.device_ms": 0.2394648437499567,
    "pass.commit.device_ms": 0.06769028906249247,
    "pass.stats.device_ms": 0.0015473125000000031,
    "pass.unscoped_share": 48.127220490204955,
    "feed.h2d_ms": 0.754475625,
    "drive.idle_feed_share": 0.021671413855026602,
}
# sha256 of the reductions' keys as they stood then (JSON, sorted keys)
XPLANE_KEYS = ("window_s", "busy_s", "modules_s", "top_ops", "top_gaps",
               "gap_s_by_label")
XPLANE_SHA = \
    "0d5a1e2a7b659a823025d37a80e0320faa4ef3194a59cdbc73493fb7f351cb84"
SCOPES_KEYS = ("h2d_s", "h2d_windows", "idle_in_wait_feed_s", "idle_s",
               "ops", "ops_joined", "pass_s", "program_op_s", "top_gaps",
               "top_ops", "unscoped_ops", "wait_feed_spans", "window_s")
SCOPES_SHA = \
    "184da621b6e87893985b7e5880db5ee6e632053e863dd42c11cf732bc05a0fca"


class _FeedClock:
    feed_s = 0.25
    feed_windows = 64


def _sha(d: dict, keys) -> str:
    return hashlib.sha256(json.dumps({k: d[k] for k in keys},
                                     sort_keys=True).encode()).hexdigest()


@pytest.fixture
def one_plane(tmp_path):
    from jax.profiler import ProfileData
    with gzip.open(SCOPED, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(SCOPED, "rb") as a, open(d / "t.xplane.pb", "wb") as b:
        shutil.copyfileobj(a, b)
    return {"trace": xplane.reduce(*xplane.from_profile(pd)),
            "spans": _FeedClock(),
            "traced": {"dir": str(tmp_path),
                       "lane_windows": TINY_LANE_WINDOWS},
            "lanes": 4}


def test_one_plane_readings_are_pinned(one_plane):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    got = {}
    for m in bench["per_layer"]:
        v = run.load_layer(REPO, m["name"]).read(one_plane)
        if v is not None:
            got[m["name"]] = v
    assert got == PINNED                # exact: not approx
    assert _sha(one_plane["trace"], XPLANE_KEYS) == XPLANE_SHA
    assert _sha(one_plane["scopes"], SCOPES_KEYS) == SCOPES_SHA
    assert one_plane["trace"]["busy_by_device_s"] == [
        one_plane["trace"]["busy_s"]]


def test_one_chip_has_no_skew(one_plane):
    assert run.load_layer(REPO, "mesh.busy_skew_share").read(
        one_plane) is None


# --- four made-up planes --------------------------------------------------

LANES, WINDOWS = 28, 4
# per chip: (window program launches, a resync launch) in ns; the chips
# differ in how long their lanes take
CHIP_STEP_NS = (1000, 1100, 1300, 1250)


def _four_planes():
    """Each chip launches the window program 4 times (one op under
    ``pass.events`` and one under ``pass.commit`` in each) and the resync
    once, inside a window of [0, 10000) ns."""
    devices, meta = {}, {}
    for c, step in enumerate(CHIP_STEP_NS):
        name = f"/device:TPU:{c}"
        mods, ops, t = [], [], 100
        for k in range(WINDOWS):
            mods.append(("jit_run_scenarios_sharded_jit(5)", t, t + step))
            ops.append(("%fusion.1 = f32[] fusion()", t, t + step // 2))
            ops.append(("%fusion.2 = f32[] fusion()", t + step // 2,
                        t + step))
            t += step + 200
        mods.append(("jit_resync_fleet_jit(6)", t, t + 300))
        ops.append(("%fusion.3 = f32[] fusion()", t, t + 300))
        devices[name] = {"XLA Modules": mods, "XLA Ops": ops}
        meta[name] = {
            (5, "%fusion.1 = f32[] fusion()"): {
                "tf_op": "jit(f)/shard_map/pass.events/add:",
                "category": "fusion"},
            (5, "%fusion.2 = f32[] fusion()"): {
                "tf_op": "jit(f)/shard_map/vmap(pass.commit)/add:",
                "category": "fusion"},
            (6, "%fusion.3 = f32[] fusion()"): {
                "tf_op": "jit(resync)/add:", "category": "fusion"}}
    host = [("restore", 0, 100), ("trajectory", 100, 10000)]
    return {"devices": devices, "host": host, "spans": [], "meta": meta}


def test_four_planes_sum_the_chips_device_time():
    t = _four_planes()
    red = xplane.reduce(t["devices"], t["host"])
    busy = [WINDOWS * s + 300 for s in CHIP_STEP_NS]
    assert red["busy_by_device_s"] == pytest.approx([b / 1e9 for b in busy])
    assert red["busy_s"] == pytest.approx(sum(busy) / 4 / 1e9)
    ctx = {"trace": red, "scopes": scopes.reduce(t), "lanes": LANES,
           "traced": {"dir": None, "lane_windows": LANES * WINDOWS}}

    def read(name):
        return run.load_layer(REPO, name).read(ctx)

    summed_step = WINDOWS * sum(CHIP_STEP_NS) / 1e9
    lane_windows = LANES * WINDOWS
    assert read("step.device_ms") * lane_windows == pytest.approx(
        1e3 * summed_step, rel=1e-12)
    assert read("pass.events.device_ms") * lane_windows == pytest.approx(
        1e3 * sum(WINDOWS * (s // 2) for s in CHIP_STEP_NS) / 1e9,
        rel=1e-12)
    assert read("pass.commit.device_ms") * lane_windows == pytest.approx(
        1e3 * sum(WINDOWS * (s - s // 2) for s in CHIP_STEP_NS) / 1e9,
        rel=1e-12)
    # shares stay shares: summed over summed
    assert read("resync.device_share") == pytest.approx(
        100.0 * 4 * 300 / sum(busy), rel=1e-12)
    assert read("device.idle_share") == pytest.approx(
        100.0 * (1 - sum(busy) / 4 / 10000), rel=1e-12)
    assert read("mesh.busy_skew_share") == pytest.approx(
        100.0 * (1 - (sum(busy) / 4) / max(busy)), rel=1e-12)
