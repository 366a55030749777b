"""Plain reference of one simulated window, for the correctness check.

Written from the semantics the program documents (``core/engine.py``'s
event-application order, ``sched``'s pending batch, best-fit scores and
capacity-checked commit, the four proposals the cells run, the what-if
outage and capacity knobs) and importing nothing of the program: the
state is a dict of arrays, the windows are the generator's, the config
is a dict of numbers.

The program fixes the order of every float sum a decision reads
(``core/ordered.py``: columns left to right, a pairwise tree, and a
stable segmented scan per node for scatter-adds), so that the chip and the
CPU decide alike. The reference sums in those same documented orders, so
the same operations on the same data give the same bits and every decision
can be compared exactly. The one tally the program scatter-adds in the
backend's own order (``node_used`` at commit) decides nothing; it is
compared against a float64 recompute instead (``check.py``).

``dtype`` is the precision of scores and tallies: float32 as the
configuration states, or bfloat16 for the control that must come out not
correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# event kinds (core/events.py's vocabulary) and task states
PAD, ADD_TASK, UPD_REQ, UPD_USED, UPD_CONS, REM_TASK = 0, 1, 2, 3, 4, 5
ADD_NODE, UPD_NODE, ADD_ATTR, REM_ATTR, REM_NODE = 6, 7, 8, 9, 10
EMPTY, PENDING, RUNNING = 0, 1, 2
OP_EQ, OP_NE, OP_LT, OP_GT = 1, 2, 3, 4
EVICT_REASON = 2.0
USED_COLS = (0, 1, 5)          # usage columns accounted as (cpu, mem, disk)
SA_STEPS, SA_T0 = 64, 0.1      # simulated annealing's registered parameters
RR_STRIDE = 131                # round robin's start advances 131 nodes a window
SALT_OUTAGE = 0x1

STATE_FIELDS = ("node_active", "node_total", "node_attrs", "node_reserved",
                "node_used", "task_state", "task_req", "task_usage",
                "task_node", "task_prio", "task_job", "task_constraints",
                "window", "evictions", "completions", "placements",
                "overflow_drops")


# --- sums in the program's documented order --------------------------------

def sum_columns(x):
    out = x[..., 0]
    for r in range(1, x.shape[-1]):
        out = out + x[..., r]
    return out


def tree_sum(x):
    n = x.shape[0]
    size = 1 << max(0, (n - 1).bit_length())
    x = jnp.pad(x, [(0, size - n)] + [(0, 0)] * (x.ndim - 1))
    while x.shape[0] > 1:
        x = x.reshape((x.shape[0] // 2, 2) + x.shape[1:])
        x = x[:, 0] + x[:, 1]
    return x[0]


def _seg(a, b):
    return a[0] | b[0], jnp.where(b[0][:, None], b[1], a[1] + b[1])


def ordered_add(acc, idx, mask, vals):
    """acc[idx] += vals where mask: each index's rows summed by a stable
    segmented scan, then added once."""
    n = acc.shape[0]
    key = jnp.where(mask & (idx >= 0) & (idx < n), idx, n)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    change = sk[1:] != sk[:-1]
    start = jnp.concatenate([jnp.ones((1,), bool), change])
    last = jnp.concatenate([change, jnp.ones((1,), bool)])
    _, run = jax.lax.associative_scan(_seg, (start, vals[order].astype(acc.dtype)))
    return acc.at[jnp.where(last, sk, n)].add(run, mode="drop")


def hash01(x, salt: int):
    h = x.astype(jnp.uint32) ^ jnp.uint32(salt & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h.astype(jnp.float32) * jnp.float32(2.0 ** -32)


# --- one window ------------------------------------------------------------

def perturb(w, outage, cap_scale, salt):
    """A what-if lane's view of the window: outage nodes never come up,
    node capacities scale."""
    node_ev = (w["kind"] == ADD_NODE) | (w["kind"] == UPD_NODE)
    hit = hash01(w["slot"], salt + SALT_OUTAGE) < outage
    kind = jnp.where(node_ev & hit, jnp.int8(PAD), w["kind"])
    a = jnp.where(node_ev[:, None], w["a"] * cap_scale, w["a"])
    return dict(w, kind=kind, a=a)


def apply_events(s, w, N, T):
    kind, slot, a = w["kind"], w["slot"], w["a"]

    def rows(mask, size):
        return jnp.where(mask, slot, size)

    # nodes: add / update, attributes, then removals
    add_n, upd_n, rem_n = (kind == ADD_NODE, kind == UPD_NODE,
                           kind == REM_NODE)
    active = s["node_active"].at[rows(add_n, N)].set(True, mode="drop")
    total = s["node_total"].at[rows(add_n | upd_n, N)].set(a, mode="drop")
    at_add, at_rem = kind == ADD_ATTR, kind == REM_ATTR
    attrs = s["node_attrs"].at[rows(at_add | at_rem, N), w["attr_idx"]].set(
        jnp.where(at_add, w["attr_val"], 0), mode="drop")
    active = active.at[rows(rem_n, N)].set(False, mode="drop")
    # tasks: incremental accounting deltas read the pre-window rows
    rem, add, upd = kind == REM_TASK, kind == ADD_TASK, kind == UPD_REQ
    ucon, use = kind == UPD_CONS, kind == UPD_USED
    old = s["task_state"][slot]
    live = old != EMPTY
    was_run = old == RUNNING
    node = s["task_node"][slot]
    old_req = s["task_req"][slot]
    old_used = s["task_usage"][slot][:, USED_COLS]
    gone = (rem | add) & was_run
    reserved = ordered_add(s["node_reserved"], node, gone, -old_req)
    used = ordered_add(s["node_used"], node, gone, -old_used)
    reserved = ordered_add(reserved, node, upd & was_run, a - old_req)
    evicted = rem & live & (a[:, 0] == EVICT_REASON)
    n_evict = jnp.sum(evicted).astype(jnp.int32)
    n_done = jnp.sum(rem & live).astype(jnp.int32) - n_evict
    state = s["task_state"].at[rows(rem, T)].set(EMPTY, mode="drop")
    tnode = s["task_node"].at[rows(rem, T)].set(-1, mode="drop")
    state = state.at[rows(add, T)].set(PENDING, mode="drop")
    tnode = tnode.at[rows(add, T)].set(-1, mode="drop")
    req = s["task_req"].at[rows(add | upd, T)].set(a, mode="drop")
    prio = s["task_prio"].at[rows(add | upd, T)].set(w["prio"], mode="drop")
    job = s["task_job"].at[rows(add, T)].set(w["job"], mode="drop")
    cons = s["task_constraints"].at[rows(add | ucon, T)].set(
        w["constraints"], mode="drop")
    samp = use & (state[slot] == RUNNING)
    used = ordered_add(used, node, samp, w["u"][:, USED_COLS] - old_used)
    usage = s["task_usage"].at[rows(use, T)].set(w["u"], mode="drop")
    return dict(s, node_active=active, node_total=total, node_attrs=attrs,
                node_reserved=reserved, node_used=used, task_state=state,
                task_node=tnode, task_req=req, task_prio=prio, task_job=job,
                task_constraints=cons, task_usage=usage,
                completions=s["completions"] + n_done,
                evictions=s["evictions"] + n_evict)


def evict_invalid(s):
    """Running tasks on a removed node, or on a node whose capacity fell
    below its reservation, go back to pending; those nodes' tallies drop
    to zero."""
    node = jnp.maximum(s["task_node"], 0)
    dead = ~s["node_active"][node]
    over = (s["node_reserved"] > s["node_total"] + 1e-6).any(axis=1)
    bad = (s["task_state"] == RUNNING) & (dead | over[node])
    bad_node = (~s["node_active"] | over)[:, None]
    return dict(s, task_state=jnp.where(bad, PENDING, s["task_state"]),
                task_node=jnp.where(bad, -1, s["task_node"]),
                evictions=s["evictions"] + jnp.sum(bad).astype(jnp.int32),
                node_reserved=jnp.where(bad_node, 0.0, s["node_reserved"]),
                node_used=jnp.where(bad_node, 0.0, s["node_used"]))


def pending_batch(s, P, T):
    """The P pending tasks of highest priority, ties by slot."""
    pend = s["task_state"] == PENDING
    top = (np.iinfo(np.int32).max // T) - 2
    rank = jnp.where(pend, jnp.clip(s["task_prio"], 0, top) + 1, 0)
    key = rank * T + (T - 1 - jnp.arange(T, dtype=jnp.int32))
    _, idx = jax.lax.top_k(key, P)
    return idx, pend[idx]


def scores(s, idx, dtype):
    """(P, N) best-fit scores, -inf where a constraint, the free capacity
    or the node's state rules the pair out."""
    req = s["task_req"][idx].astype(dtype)
    cons = s["task_constraints"][idx]
    total = s["node_total"].astype(dtype)
    free = total - s["node_reserved"].astype(dtype)
    got = s["node_attrs"][:, cons[:, :, 0]]
    op, val = cons[None, :, :, 1], cons[None, :, :, 2]
    ok = jnp.where(op == OP_EQ, got == val,
         jnp.where(op == OP_NE, got != val,
         jnp.where(op == OP_LT, got < val,
         jnp.where(op == OP_GT, got > val, True)))).all(-1).T
    fit = (req[:, None, :] <= free[None] + 1e-9).all(-1)
    ok = ok & fit & s["node_active"][None, :]
    left = (free[None] - req[:, None]) / jnp.maximum(total, 1e-6)[None]
    return jnp.where(ok, -sum_columns(left), -jnp.inf)


def balance(reserved, total, active):
    frac = jnp.where(active[:, None], reserved / jnp.maximum(total, 1e-9), 0.0)
    f = sum_columns(frac) / frac.shape[-1]
    na = jnp.maximum(active.sum(), 1)
    mu = tree_sum(f) / na
    return tree_sum(jnp.where(active, (f - mu) ** 2, 0.0)) / na


def annealed(s, idx, valid, ok, rng, dtype):
    """Simulated annealing over a (P, N) preference: 64 single-entry
    pushes, each kept if the argmax placement's reservation balance
    improves, or by the Metropolis rule."""
    P, N = ok.shape
    req = s["task_req"][idx].astype(dtype)
    reserved = s["node_reserved"].astype(dtype)
    total = s["node_total"].astype(dtype)
    weight = (valid & ok.any(1))[:, None]
    k_init, k_steps = jax.random.split(rng)
    pref = jax.random.uniform(k_init, (P, N))

    def energy(p):
        choice = jnp.argmax(jnp.where(ok, p, -jnp.inf), axis=1)
        onehot = jax.nn.one_hot(choice, N, dtype=dtype) * weight
        trial = reserved + jnp.matmul(onehot.T, req,
                                      precision=jax.lax.Precision.HIGHEST)
        return balance(trial, total, s["node_active"])

    def body(i, carry):
        p, e, key = carry
        key, k1, k2, k3 = jax.random.split(key, 4)
        a = jax.random.randint(k1, (), 0, P)
        b = jax.random.randint(k2, (), 0, N)
        cand = p.at[a, b].add(1.0)
        e_new = energy(cand)
        temp = SA_T0 * (1.0 - i / SA_STEPS) + 1e-6
        take = (e_new < e) | (jax.random.uniform(k3) <
                              jnp.exp(-(e_new - e) / temp))
        return (jnp.where(take, cand, p), jnp.where(take, e_new, e), key)

    p, _, _ = jax.lax.fori_loop(0, SA_STEPS, body, (pref, energy(pref),
                                                    k_steps))
    return p


def commit(s, idx, valid, ok, pref, dynamic, dtype):
    """Walk the batch in priority order; each task takes its preferred
    node among those its request fits now (best fit re-scored against the
    running tally when ``dynamic``), or stays pending."""
    P = idx.shape[0]
    req = s["task_req"][idx].astype(dtype)
    total = jnp.where(s["node_active"][:, None], s["node_total"],
                      -1.0).astype(dtype)
    denom = jnp.maximum(s["node_total"], 1e-6).astype(dtype)

    def body(i, carry):
        reserved, node_of = carry
        free = total - reserved
        fit = (req[i][None, :] <= free + 1e-9).all(-1) & ok[i]
        if dynamic:
            sc = -sum_columns((free - req[i][None, :]) / denom)
        else:
            sc = pref[i]
        n = jnp.argmax(jnp.where(fit, sc, -jnp.inf)).astype(jnp.int32)
        can = fit[n] & valid[i]
        reserved = reserved.at[n].add(jnp.where(can, req[i], 0.0))
        return reserved, node_of.at[i].set(jnp.where(can, n, -1))

    reserved, node_of = jax.lax.fori_loop(
        0, P, body, (s["node_reserved"].astype(dtype),
                     jnp.full((P,), -1, jnp.int32)))
    return node_of, reserved.astype(s["node_reserved"].dtype)


def schedule(s, rng, sched: str, N, P, T, dtype):
    idx, valid = pending_batch(s, P, T)
    sc = scores(s, idx, dtype)
    ok = jnp.isfinite(sc)
    if sched == "greedy":
        pref, dynamic = sc, True
    elif sched == "first_fit":
        pref, dynamic = -jnp.broadcast_to(
            jnp.arange(N, dtype=jnp.float32)[None], ok.shape), False
    elif sched == "round_robin":
        start = (s["window"] * RR_STRIDE) % N
        order = (jnp.arange(N) - start) % N
        pref, dynamic = -jnp.broadcast_to(order.astype(jnp.float32)[None],
                                          ok.shape), False
    elif sched == "simulated_annealing":
        pref, dynamic = annealed(s, idx, valid, ok, rng, dtype), False
    else:
        raise ValueError(f"the reference has no scheduler {sched!r}")
    node_of, reserved = commit(s, idx, valid, ok, pref, dynamic, dtype)
    placed = node_of >= 0
    state = s["task_state"].at[idx].set(
        jnp.where(placed, RUNNING, s["task_state"][idx]).astype(jnp.int8))
    tnode = s["task_node"].at[idx].set(jnp.where(placed, node_of,
                                                 s["task_node"][idx]))
    used_cols = s["task_usage"][idx][:, USED_COLS]
    used = s["node_used"].at[jnp.where(placed, node_of, N)].add(
        jnp.where(placed[:, None], used_cols, 0.0), mode="drop")
    return dict(s, task_state=state, task_node=tnode, node_reserved=reserved,
                node_used=used,
                placements=s["placements"] + placed.sum().astype(jnp.int32))


def stats(s):
    """One stats row: populations, counters and the cluster's float
    totals, fractions and balance, summed plainly."""
    active, total = s["node_active"], s["node_total"]
    run = s["task_state"] == RUNNING
    pend = s["task_state"] == PENDING
    prio = jnp.clip(s["task_prio"], 0, 11)
    by = jnp.zeros((12, 2), jnp.int32).at[prio].add(
        jnp.stack([run, pend], 1).astype(jnp.int32))
    cap = jnp.where(active[:, None], total, 0.0).sum(0)
    reserved = s["node_reserved"].sum(0)
    used = s["node_used"].sum(0)
    denom = jnp.maximum(cap, 1e-9)
    n_run = run.sum()
    usage_mean = jnp.where(
        n_run > 0, (s["task_usage"] * run[:, None]).sum(0)
        / jnp.maximum(n_run, 1), 0.0)
    na = jnp.maximum(active.sum(), 1)

    def spread(x):
        mean = x.sum() / na
        return jnp.where(active, (x - mean) ** 2, 0.0).sum() / na

    util = jnp.where(active[:, None], s["node_used"]
                     / jnp.maximum(total, 1e-9), 0.0)[:, 0]
    res = jnp.where(active[:, None], s["node_reserved"]
                    / jnp.maximum(total, 1e-9), 0.0).mean(-1)
    return {"capacity": cap, "reserved": reserved, "used": used,
            "reserved_frac": reserved / denom, "used_frac": used / denom,
            "overestimate_frac": 1.0 - used / jnp.maximum(reserved, 1e-9),
            "usage_mean": usage_mean, "util_balance_var": spread(util),
            "reserved_balance_var": spread(res),
            "n_nodes": active.sum().astype(jnp.int32),
            "n_running": run.sum().astype(jnp.int32),
            "n_pending": pend.sum().astype(jnp.int32),
            "running_by_priority": by[:, 0], "pending_by_priority": by[:, 1],
            "evictions": s["evictions"], "completions": s["completions"],
            "placements": s["placements"],
            "overflow_drops": s["overflow_drops"]}


@functools.partial(jax.jit, static_argnames=("salt", "sched", "dims",
                                             "dtype"))
def run_batch(s, windows, seed, outage, cap_scale, salt, *, sched, dims,
              dtype=jnp.float32):
    """Advance one lane over a (W, ...) batch of windows; returns the
    final state and one stats row per window."""
    N, P, T = dims
    keys = jax.random.split(jax.random.PRNGKey(seed), windows["kind"].shape[0])

    def step(s, xs):
        w, key = xs
        w = perturb(w, outage, cap_scale, salt)
        s = apply_events(s, w, N, T)
        s = evict_invalid(s)
        s = schedule(s, key, sched, N, P, T, dtype)
        s = dict(s, window=s["window"] + 1)
        return s, stats(s)

    return jax.lax.scan(step, s, (windows, keys))
