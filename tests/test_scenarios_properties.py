"""Property tests (hypothesis) for the scenario perturbations: over random
event windows and knob draws,

* perturbations never *mint* capacity — every post-perturb node-capacity
  payload is bounded by scale_knob x the original;
* thinning only removes events (survivors are bit-identical, nothing new
  appears);
* injection only fills the reserved slot pool and preserves every original
  event, with fresh ids drawn from the reserved id range;
* identity knobs are a no-op bit-for-bit, whatever the stream contains.

These are the safety rails for the what-if fleet: a perturbation that
fabricates capacity or silently rewrites unrelated events would make every
scenario comparison meaningless.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.config import REDUCED_SIM
from repro.core.events import EventKind, HostEvent, pack_window
from repro.scenarios import ScenarioSpec, build_knobs
from repro.scenarios import perturb

CFG = REDUCED_SIM
INJECT_CFG = dataclasses.replace(CFG, inject_slots=16, inject_task_slots=64)

_KINDS = (EventKind.ADD_TASK, EventKind.UPDATE_TASK_REQUIRED,
          EventKind.UPDATE_TASK_USED, EventKind.REMOVE_TASK,
          EventKind.ADD_NODE, EventKind.UPDATE_NODE_RESOURCES,
          EventKind.REMOVE_NODE, EventKind.ADD_NODE_ATTR)


@st.composite
def host_events(draw, max_events=48):
    """A random, schema-plausible event list (distinct slots per kind-group,
    so dedup_events keeps everything and ordering stays deterministic)."""
    n = draw(st.integers(0, max_events))
    evs = []
    for i in range(n):
        kind = draw(st.sampled_from(_KINDS))
        is_node = kind in (EventKind.ADD_NODE,
                           EventKind.UPDATE_NODE_RESOURCES,
                           EventKind.REMOVE_NODE, EventKind.ADD_NODE_ATTR)
        slot = i % (CFG.max_nodes if is_node else CFG.max_tasks)
        a = tuple(draw(st.floats(0.0, 4.0, width=32)) for _ in range(3))
        u = tuple(draw(st.floats(0.0, 2.0, width=32))
                  for _ in range(CFG.n_usage_stats))
        evs.append(HostEvent(i, kind, slot, a=a, u=u,
                             prio=draw(st.integers(0, 11)),
                             job=draw(st.integers(0, 63)),
                             attr_idx=draw(st.integers(0, 7)),
                             attr_val=draw(st.integers(0, 100))))
    return evs


def _knobs(**over):
    knobs, _ = build_knobs([ScenarioSpec(**over)])
    return jax.tree.map(lambda a: a[0], knobs)


def _win(cfg, evs):
    return jax.tree.map(jnp.asarray, pack_window(cfg, evs, 0))


def _np(w):
    """Host copy with float subnormals flushed to zero: XLA flushes them in
    device ops on the CPU and the TPU alike, so payloads are compared after
    the same flush on both sides."""
    def flush(x):
        x = np.asarray(x)
        if x.dtype.kind != "f":
            return x
        return np.where(np.abs(x) < np.finfo(x.dtype).tiny,
                        np.zeros_like(x), x)
    return jax.tree.map(flush, w)


_NODE_CAP = (EventKind.ADD_NODE, EventKind.UPDATE_NODE_RESOURCES)

# width=32 strategies need bounds that are float32 values themselves:
# hypothesis rejects 0.1 and 1e-4, which float32 cannot represent
F32_TENTH = float(np.float32(0.1))
F32_TEN_THOUSANDTH = float(np.float32(0.0001))


@settings(max_examples=30, deadline=None)
@given(evs=host_events(),
       scale=st.floats(F32_TENTH, 4.0, width=32),
       win_idx=st.integers(0, 1000))
def test_capacity_never_minted(evs, scale, win_idx):
    """Post-perturb node capacity <= scale_knob x original, elementwise —
    no knob combination fabricates resources out of thin air."""
    w = _win(INJECT_CFG, evs)
    out = _np(perturb.perturb_window(w, _knobs(capacity_scale=scale),
                                     INJECT_CFG, window=jnp.int32(win_idx)))
    orig = _np(w)
    cap_rows = np.isin(orig.kind, _NODE_CAP)
    bound = orig.a * np.float32(scale) + 1e-5
    assert (out.a[cap_rows] <= bound[cap_rows]).all()
    # non-capacity payloads are not scaled at all
    assert (out.a[~cap_rows] == orig.a[~cap_rows]).all()


@settings(max_examples=30, deadline=None)
@given(evs=host_events(), rate=st.floats(F32_TEN_THOUSANDTH, 1.0, width=32))
def test_thinning_only_removes_events(evs, rate):
    """rate < 1 may only turn rows into PAD: survivors keep every field
    bit-for-bit and no new events appear anywhere (reserved rows included)."""
    w = _win(INJECT_CFG, evs)
    out = _np(perturb.perturb_window(w, _knobs(arrival_rate=rate),
                                     INJECT_CFG, window=jnp.int32(0)))
    orig = _np(w)
    was_pad = orig.kind == EventKind.PAD
    now_pad = out.kind == EventKind.PAD
    assert now_pad[was_pad].all()                  # nothing new appears
    survived = ~now_pad
    for f in out._fields:
        a, b = getattr(out, f), getattr(orig, f)
        if np.ndim(a):
            np.testing.assert_array_equal(a[survived], b[survived],
                                          err_msg=f)


@settings(max_examples=30, deadline=None)
@given(evs=host_events(),
       rate=st.floats(1.0, 8.0, width=32),
       win_idx=st.integers(0, 1000))
def test_injection_only_fills_reserved_slots(evs, rate, win_idx):
    """rate > 1 must leave all real rows bit-identical and only write
    ADD_TASKs with pool-range ids into the reserved tail."""
    cfg = INJECT_CFG
    w = _win(cfg, evs)
    out = _np(perturb.perturb_window(w, _knobs(arrival_rate=rate), cfg,
                                     window=jnp.int32(win_idx)))
    orig = _np(w)
    S = cfg.inject_slots
    for f in out._fields:
        a, b = getattr(out, f), getattr(orig, f)
        if np.ndim(a):
            np.testing.assert_array_equal(a[:-S], b[:-S], err_msg=f)
    tail_kind = out.kind[-S:]
    inj = tail_kind != EventKind.PAD
    assert np.isin(tail_kind[inj], [EventKind.ADD_TASK]).all()
    assert (out.slot[-S:][inj] >= cfg.real_task_slots).all()
    assert (out.slot[-S:][inj] < cfg.max_tasks).all()
    # untouched reserved rows keep their original bits
    for f in out._fields:
        a, b = getattr(out, f), getattr(orig, f)
        if np.ndim(a):
            np.testing.assert_array_equal(a[-S:][~inj], b[-S:][~inj],
                                          err_msg=f)
    # count law: round((rate-1) * arrivals), capped at the pool size
    n_arr = int((orig.kind == EventKind.ADD_TASK).sum())
    expect = min(S, int(np.round((np.float32(rate) - 1.0)
                                 * np.float32(n_arr))))
    assert int(inj.sum()) == expect


@settings(max_examples=30, deadline=None)
@given(evs=host_events(), win_idx=st.integers(0, 1000),
       with_pool=st.booleans())
def test_identity_knobs_are_bitwise_noop(evs, win_idx, with_pool):
    cfg = INJECT_CFG if with_pool else CFG
    w = _win(cfg, evs)
    out = perturb.perturb_window(w, _knobs(), cfg,
                                 window=jnp.int32(win_idx))
    out, w = _np(out), _np(w)
    for f in out._fields:
        np.testing.assert_array_equal(getattr(out, f), getattr(w, f),
                                      err_msg=f)
