"""Arithmetic in an order every backend follows.

A reduce or a scatter-add leaves the order in which it accumulates to the
backend, and the TPU's order differs from the CPU's. The float results then
differ in the last bit, and where a decision compares them — a borderline
fit, a best-fit near-tie, a strict "better energy" test — the chip and the
CPU place tasks differently. The sums that decisions read go through here:
each is a fixed graph of elementwise adds, which both backends round
identically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sum_columns(x: jax.Array) -> jax.Array:
    """Sum the last axis left to right, ``(x0 + x1) + x2 + ...`` (used for
    the few resource columns of a score)."""
    out = x[..., 0]
    for r in range(1, x.shape[-1]):
        out = out + x[..., r]
    return out


def tree_sum(x: jax.Array) -> jax.Array:
    """Sum the leading axis as a fixed pairwise tree (zero-padded to a
    power of two): log2(n) levels of elementwise adds."""
    n = x.shape[0]
    size = 1 << max(0, (n - 1).bit_length())
    x = jnp.pad(x, [(0, size - n)] + [(0, 0)] * (x.ndim - 1))
    while x.shape[0] > 1:
        x = x.reshape((x.shape[0] // 2, 2) + x.shape[1:])
        x = x[:, 0] + x[:, 1]
    return x[0]


def _segmented_add(a, b):
    """Segmented-sum operator: a run restarts where ``b`` starts one."""
    a_start, a_sum = a
    b_start, b_sum = b
    return a_start | b_start, jnp.where(b_start[:, None], b_sum, a_sum + b_sum)


def ordered_scatter_add(acc: jax.Array, idx: jax.Array, mask: jax.Array,
                        vals: jax.Array) -> jax.Array:
    """acc (N, R) += vals (E, R) at ``idx`` where ``mask``; rows indexing
    outside [0, N) are dropped.

    The rows are stably sorted by index and each index's rows summed by a
    segmented ``associative_scan`` — a fixed tree of elementwise adds over
    the sorted rows, log2(E) levels deep however many rows share an index.
    Each index then takes its one sum, so every backend computes the same
    ``acc + (v0 + v1 + ...)`` bit for bit."""
    n = acc.shape[0]
    key = jnp.where(mask & (idx >= 0) & (idx < n), idx, n)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    change = sk[1:] != sk[:-1]
    start = jnp.concatenate([jnp.ones((1,), bool), change])
    last = jnp.concatenate([change, jnp.ones((1,), bool)])
    _, run = jax.lax.associative_scan(
        _segmented_add, (start, vals[order].astype(acc.dtype)))
    # the run's last row holds its sum; the other rows, and the dropped
    # rows' run (key n), fall outside acc
    return acc.at[jnp.where(last, sk, n)].add(run, mode="drop")
