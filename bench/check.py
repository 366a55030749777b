"""The comparison that decides ``correct``.

The program's state before one batch of the timed window goes through the
plain reference of the deployment's family (``families/<family>.py``, built
on ``reference.py``); the program's state after the batch and its stats
rows are compared with the reference's. The numbers every family compares
(``NUMBERS``), each held to its limit (``limits/``):

* ``events_differ``: entries of the node and task tables that event
  application writes (capacities, attributes, activity, requests,
  priorities, jobs, constraints, usage, the window counter) that differ;
* ``decisions_differ``: task slots whose state or node differs, plus the
  differences of the placement, completion, eviction and drop counters;
* ``tallies_differ``: entries of the reservation tally (``node_reserved``)
  that differ: the program sums it in a documented order, so it is exact;
* ``stats_differ``: integer entries of the stats rows that differ;
* ``stats_gap``: the widest relative gap of a float stats entry (sums over
  nodes or tasks in the program's order against the reference's);
* ``tally_drift``: the widest gap, as a share of the node's capacity,
  between a tally the program keeps incrementally (``node_reserved``,
  ``node_used``) and a float64 recompute from its own task table, over
  every lane, before and after the batch.

A family adds the state leaves its reference also keeps
(``STATE_FIELDS``) and numbers of its own (``NUMBERS``, per lane from
``numbers``); they go into the verdict and the result line as these do.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import reference as ref

EVENT_FIELDS = ("node_active", "node_total", "node_attrs", "task_req",
                "task_usage", "task_prio", "task_job", "task_constraints",
                "window")
COUNTERS = ("evictions", "completions", "placements", "overflow_drops")
USED_COLS = list(ref.USED_COLS)
NUMBERS = ("events_differ", "decisions_differ", "tallies_differ",
           "stats_differ", "stats_gap", "tally_drift")


def _lanes(tree, single: bool, axis: int = 0):
    """Give a one-trajectory state or stats rows a lane axis."""
    if not single:
        return tree
    return {k: np.expand_dims(np.asarray(v), axis) for k, v in tree.items()}


def state_fields(family) -> tuple:
    """The leaves compared: the shared ones, then the family's own."""
    return ref.STATE_FIELDS + tuple(f for f in family.STATE_FIELDS
                                    if f not in ref.STATE_FIELDS)


def as_dict(state, fields=ref.STATE_FIELDS) -> dict:
    if isinstance(state, dict):
        return {f: np.asarray(state[f]) for f in fields}
    return {f: np.asarray(getattr(state, f)) for f in fields}


def dims(cfg) -> dict:
    """The configuration's numbers, as the reference is given them."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if isinstance(getattr(cfg, f.name), (int, float))}


def drift(s: dict) -> float:
    """Widest |tally - float64 recompute| / capacity over nodes, resources
    and lanes; a whole capacity (1.0) where a running task's node is no
    node of the table, whose tally could hold it."""
    worst = 0.0
    for i in range(s["task_state"].shape[0]):
        run = s["task_state"][i] == ref.RUNNING
        node = s["task_node"][i][run]
        N = s["node_total"].shape[1]
        if np.any((node < 0) | (node >= N)):
            return 1.0
        cap = np.maximum(s["node_total"][i].astype(np.float64), 1e-6)
        for tally, vals in (("node_reserved", s["task_req"][i][run]),
                            ("node_used",
                             s["task_usage"][i][run][:, USED_COLS])):
            want = np.zeros((N, vals.shape[1]))
            np.add.at(want, node, vals.astype(np.float64))
            got = s[tally][i].astype(np.float64)
            live = s["node_active"][i][:, None]
            gap = np.where(live, np.abs(got - want) / cap, 0.0)
            worst = max(worst, float(gap.max(initial=0.0)))
    return worst


def reference_lanes(family, cfg, lanes, before, windows, seed, dtype=None,
                    devices=None) -> list:
    """The family reference's (state after, stats rows) of every lane, as
    host arrays. Each lane runs on one of ``devices`` (default: the first),
    in turn, and every lane is launched before any is read, so that the
    chips of a sharded cell replay their lanes side by side."""
    import jax
    import jax.numpy as jnp
    devices = devices or jax.devices()[:1]
    b = as_dict(before, state_fields(family))
    sizes = dims(cfg)
    single = np.ndim(b["window"]) == 0
    if single:
        b = _lanes(b, True)
    outs = []
    for i, lane in enumerate(lanes):
        dev = devices[i % len(devices)]

        def put(tree):
            return {k: jax.device_put(np.asarray(v), dev)
                    for k, v in tree.items()}
        outs.append(family.run_batch(
            put({k: v[i] for k, v in b.items()}), put(windows), seed, lane,
            dims=sizes, dtype=dtype or jnp.float32))
    return [jax.tree.map(np.asarray, o) for o in outs]


def control_outputs(family, cfg, lanes, before, windows, seed,
                    devices=None):
    """The control: the family's reference in the program's place, with
    scores and tallies in bfloat16, one step below the float32 the
    configuration states. Returns (state after, stats rows) shaped as the
    program's."""
    import jax.numpy as jnp
    single = np.ndim(as_dict(before)["window"]) == 0
    outs = reference_lanes(family, cfg, lanes, before, windows, seed,
                           dtype=jnp.bfloat16, devices=devices)
    after = {k: np.stack([o[0][k] for o in outs]) for k in outs[0][0]}
    rows = {k: np.stack([o[1][k] for o in outs], axis=1) for k in outs[0][1]}
    if single:
        after = {k: v[0] for k, v in after.items()}
        rows = {k: v[:, 0] for k, v in rows.items()}
    return after, rows


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    den = np.maximum(np.abs(b), 1e-6)
    return float((np.abs(a - b) / den).max(initial=0.0))


def lane_numbers(after_i, rows_i, r_state, r_rows) -> dict:
    """One lane's numbers: the program's state after the batch and stats
    rows against the reference's."""
    n = {"events_differ": 0, "decisions_differ": 0, "tallies_differ": 0,
         "stats_differ": 0, "stats_gap": 0.0}
    for f in EVENT_FIELDS:
        n["events_differ"] += int(np.sum(np.asarray(after_i[f])
                                         != r_state[f]))
    for f in ("task_state", "task_node"):
        n["decisions_differ"] += int(np.sum(after_i[f] != r_state[f]))
    for f in COUNTERS:
        n["decisions_differ"] += int(abs(int(after_i[f]) - int(r_state[f])))
    n["tallies_differ"] = int(np.sum(after_i["node_reserved"]
                                     != r_state["node_reserved"]))
    for k, got in rows_i.items():
        got = np.asarray(got)
        want = r_rows.get(k, np.zeros_like(got))
        if got.shape != want.shape:
            n["stats_differ"] += max(got.size, want.size)
        elif got.dtype.kind in "iub":
            n["stats_differ"] += int(np.sum(got != want))
        else:
            n["stats_gap"] = max(n["stats_gap"], _rel(got, want))
    return n


def compare(family, cfg, lanes, before, after, rows, windows, seed,
            limits, devices=None) -> dict:
    """Every number, the shared ones and the family's, the worst over the
    lanes, with its limit; ``correct`` when none is over its limit. The
    reference replays the lanes on ``devices`` (``reference_lanes``)."""
    fields = state_fields(family)
    b, a = as_dict(before, fields), as_dict(after, fields)
    single = len(lanes) == 1 and np.ndim(b["window"]) == 0
    if single:
        b, a = _lanes(b, True), _lanes(a, True)
        rows = _lanes(rows, True, axis=1)
    refs = reference_lanes(family, cfg, lanes, b, windows, seed,
                           devices=devices)
    worst = {}
    failed = 0
    for i in range(len(lanes)):
        a_i = {k: v[i] for k, v in a.items()}
        rows_i = {k: np.asarray(v)[:, i] for k, v in rows.items()}
        n = lane_numbers(a_i, rows_i, *refs[i])
        if family.NUMBERS:
            n.update(family.numbers(a_i, rows_i, *refs[i]))
        failed += any(n[k] > limits[k] for k in n)
        for k, v in n.items():
            worst[k] = max(worst.get(k, v), v)
    worst["tally_drift"] = max(drift(b), drift(a))
    failed = max(failed, int(worst["tally_drift"] > limits["tally_drift"]))
    numbers = {k: {"value": worst[k], "limit": limits[k]}
               for k in NUMBERS + tuple(family.NUMBERS)}
    return {"correct": all(v["value"] <= v["limit"]
                           for v in numbers.values()),
            "failed_lanes": failed, "numbers": numbers}
