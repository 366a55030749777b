"""The trace reduction, on a small trace recorded on a TPU v5e chip (the
tiny fleet cell, ``record_trace.py``) and on made-up intervals."""
import gzip
import os

import pytest

import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_fleet.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(TRACE, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return xplane.from_profile(pd)


def test_recorded_trace_reduces(recorded):
    devices, host = recorded
    assert list(devices) == ["/device:TPU:0"]
    assert {n for n, _, _ in host} >= {"restore", "trajectory", "dispatch",
                                       "feed.read"}
    r = xplane.reduce(devices, host)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the fleet program and its resync are found by name
    assert xplane.module_s(r, "run_scenarios") > 0
    assert xplane.module_s(r, "resync") > 0
    assert xplane.module_s(r, "run_scenarios") <= r["busy_s"] * 1.0001
    # top ops by self time, short names, longest first
    ops = r["top_ops"]
    assert 0 < len(ops) <= xplane.TOP
    assert all(" = " not in n for n, _ in ops)
    assert [d for _, d in ops] == sorted((d for _, d in ops), reverse=True)
    assert sum(d for _, d in ops) <= r["busy_s"] * 1.0001
    # the gaps and the busy time fill the window
    gaps = sum(r["gap_s_by_label"].values())
    assert gaps + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    assert set(r["gap_s_by_label"]) <= {"restore", "dispatch", "resync",
                                        "feed.read", "drain", "wait", "none"}


def test_union_clips_and_merges():
    got = xplane.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 11)
    assert got == [(1, 3), (5, 11)]


def test_self_time_subtracts_nested_ops():
    events = [("%while.1 = loop", 0, 10), ("%fusion.2 = f32[] x", 1, 4),
              ("%fusion.3 = f32[] y", 5, 6), ("%copy.4 = s32[] z", 12, 13)]
    assert xplane.self_times(events, 0, 20) == {
        "%while.1": 6, "%fusion.2": 3, "%fusion.3": 1, "%copy.4": 1}


def test_gaps_are_named_by_host_spans():
    ops = [("a", 10, 20), ("b", 30, 40), ("c", 60, 70)]
    devices = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
        ("jit_run_windows_jit(1)", 10, 70)]}}
    host = [("restore", 0, 10), ("trajectory", 10, 90),
            ("dispatch", 20, 30), ("resync", 40, 60)]
    r = xplane.reduce(devices, host)
    assert r["window_s"] == pytest.approx(90e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert dict((n, d) for n, d in r["top_gaps"]) == pytest.approx(
        {"restore": 10e-9, "dispatch": 10e-9, "resync": 20e-9,
         "drain": 20e-9})
    assert xplane.module_s(r, "run_windows") == pytest.approx(60e-9)
