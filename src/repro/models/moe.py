"""Mixture-of-Experts with sort-based dispatch (no (T, E, C) one-hot tensors).

Dispatch pipeline:
  router logits -> top-k -> flatten (T*k assignments) -> stable sort by expert
  -> position-within-expert -> drop beyond capacity -> scatter into per-expert
  buffers (E, C, d) -> batched expert matmuls -> gather back, weighted combine.

At the train_4k shape this moves ~1M tokens through 128 experts without ever
materialising a (1M, 128, C) tensor. The expert dim is sharded over the TP
axis ('expert' -> 'model', expert parallelism) when E divides it; otherwise
(qwen2-moe's 60 experts) the per-expert ff dim is sharded instead
('expert_ff' -> 'model').

Shared experts (qwen2-moe) are a fused always-on SwiGLU with hidden
n_shared * shared_d_ff.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical_constraint
from repro.models.layers import ParamDef, swiglu


def param_defs(cfg) -> Dict[str, ParamDef]:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.02),
        "wi": ParamDef((e, d, 2 * ff), ("expert", "embed", "expert_ff")),
        "wo": ParamDef((e, ff, d), ("expert", "expert_ff", "embed")),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * cfg.shared_d_ff
        defs["shared_wi"] = ParamDef((d, 2 * sff), ("embed", "ff"))
        defs["shared_wo"] = ParamDef((sff, d), ("ff", "embed"))
        defs["shared_gate"] = ParamDef((d, 1), ("embed", None), scale=0.02)
    return defs


def capacity(n_tokens: int, cfg) -> int:
    per_expert = (n_tokens * cfg.moe_top_k) / cfg.n_experts
    cap = int(per_expert * cfg.capacity_factor)
    return max(8, -(-cap // 8) * 8)   # round up to 8, floor 8


def route(x2d: jax.Array, router_w: jax.Array, cfg
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Return (weights (T,k), expert_idx (T,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, cfg.moe_top_k)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    dispatch_frac = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (x2d.shape[0] * cfg.moe_top_k))
    prob_frac = probs.mean(axis=0)
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    return weights, expert_idx.astype(jnp.int32), aux


def apply(params, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss). Dispatches to the explicit
    shard_map EP implementation when configured and applicable."""
    if cfg.moe_impl == "shard_map":
        from repro.distributed.sharding import _current, mesh_axis_size
        mesh, rules = _current()
        if mesh is not None and "model" in mesh.axis_names:
            # Non-divisible expert counts (qwen2-moe: 60 over 16 shards) pad
            # to the next multiple inside _apply_shard_map; the router never
            # selects padded experts. Shared experts are a plain dense MLP —
            # no scatter involved — so they run on the regular GSPMD path
            # and add outside the shard_map region.
            y, aux = _apply_shard_map(params, cfg, x, mesh, rules)
            if cfg.n_shared_experts:
                y = y + _shared_experts(params, cfg, x)
            return y, aux
    return _apply_gspmd(params, cfg, x)


def _shared_experts(params, cfg, x: jax.Array) -> jax.Array:
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    sh = jnp.einsum("td,df->tf", x2d, params["shared_wi"].astype(x.dtype))
    sh = swiglu(sh)
    sh = jnp.einsum("tf,fd->td", sh, params["shared_wo"].astype(x.dtype))
    gate = jax.nn.sigmoid(
        jnp.einsum("td,do->to", x2d.astype(jnp.float32),
                   params["shared_gate"].astype(jnp.float32)))
    return (sh * gate.astype(x.dtype)).reshape(B, S, d)


def _apply_gspmd(params, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.moe_top_k
    x2d = logical_constraint(x.reshape(T, d), "tokens", None)

    weights, expert_idx, aux = route(x2d, params["router"], cfg)

    # ---- sort-based dispatch ----
    flat_e = expert_idx.reshape(-1)                       # (T*K,)
    sort_idx = jnp.argsort(flat_e, stable=True)           # (T*K,)
    sorted_e = flat_e[sort_idx]
    token_of = sort_idx // K                               # source token per slot
    w_sorted = weights.reshape(-1)[sort_idx]

    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                   # exclusive prefix
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]

    C = capacity(T, cfg)
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # overflow -> scratch row

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x2d[token_of], 0).astype(x.dtype))
    buf = logical_constraint(buf[: E * C].reshape(E, C, d), "expert", None, None)

    # ---- expert compute (batched over E) ----
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(x.dtype))
    h = logical_constraint(h, "expert", None, "expert_ff")
    h = swiglu(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
    out_buf = logical_constraint(out_buf, "expert", None, None)

    # ---- combine ----
    flat_out = jnp.concatenate(
        [out_buf.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    # replicate before the gather+scatter-add combine: without this the SPMD
    # partitioner (observed on the 0.4.x CPU backend, data x model mesh)
    # keeps per-model-shard partials through the scatter and sums them twice
    flat_out = logical_constraint(flat_out, None, None)
    y_slots = flat_out[slot] * (w_sorted * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[token_of].add(y_slots.astype(jnp.float32))
    y = logical_constraint(y.astype(x.dtype), "tokens", None)

    if cfg.n_shared_experts:
        sh = jnp.einsum("td,df->tf", x2d, params["shared_wi"].astype(x.dtype))
        sh = swiglu(sh)
        sh = jnp.einsum("tf,fd->td", sh, params["shared_wo"].astype(x.dtype))
        gate = jax.nn.sigmoid(
            jnp.einsum("td,do->to", x2d.astype(jnp.float32),
                       params["shared_gate"].astype(jnp.float32)))
        y = y + (sh * gate.astype(x.dtype))

    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Explicit expert-parallel dispatch (shard_map) — perf-log iteration 2.
#
# Under pure GSPMD the runtime-indexed scatter into the (E, C, d) buffers
# (experts sharded over 'model', tokens over 'data') is lowered as
# "replicate destination + combine with all-reduce": ~100 GiB of all-reduce
# per qwen3-moe layer at train_4k. The explicit version exploits the layout
# directly: activations are replicated over 'model', so every model shard
# already holds all tokens of its data shard — each shard dispatches *only to
# its local experts* and the partial outputs combine with ONE psum(T_loc, d)
# per layer (~100 MiB wire). FSDP's weight all-gathers become explicit
# all_gathers over the data axes, same as the dense layers pay.
# ---------------------------------------------------------------------------


def _local_dispatch(x2d, weights, expert_idx, keep_mask, wi, wo, e_lo, E_loc,
                    C, dtype):
    """Dense sort-based dispatch restricted to experts [e_lo, e_lo + E_loc)."""
    T, d = x2d.shape
    K = expert_idx.shape[-1]
    flat_e = expert_idx.reshape(-1)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + E_loc) & keep_mask.reshape(-1)
    local_e = jnp.where(mine, flat_e - e_lo, E_loc)          # E_loc = dropped
    sort_idx = jnp.argsort(local_e, stable=True)
    sorted_e = local_e[sort_idx]
    token_of = sort_idx // K
    w_sorted = weights.reshape(-1)[sort_idx]

    counts = jnp.zeros((E_loc + 1,), jnp.int32).at[local_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    keep = (sorted_e < E_loc) & (pos_in_e < C)
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E_loc * C)

    buf = jnp.zeros((E_loc * C + 1, d), dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x2d[token_of], 0).astype(dtype))
    buf = buf[: E_loc * C].reshape(E_loc, C, d)

    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(dtype))
    h = swiglu(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(dtype))

    flat_out = jnp.concatenate(
        [out_buf.reshape(E_loc * C, d), jnp.zeros((1, d), dtype)], axis=0)
    y_slots = flat_out[slot] * (w_sorted * keep)[:, None].astype(dtype)
    y = jnp.zeros((T, d), jnp.float32).at[token_of].add(
        y_slots.astype(jnp.float32))
    return y


def _apply_shard_map(params, cfg, x, mesh, rules) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model"
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_deg = sizes.get(tp, 1)
    n_dp = 1
    for a in dp:
        n_dp *= sizes[a]
    E = cfg.n_experts
    E_pad = -(-E // tp_deg) * tp_deg         # pad experts up (60 -> 64 @ 16)
    E_loc = E_pad // tp_deg
    B, S, d = x.shape
    T_loc = (B // n_dp) * S
    C = capacity(T_loc, cfg)
    dtype = x.dtype

    wi_p, wo_p = params["wi"], params["wo"]
    if E_pad != E:
        # padded experts are routed to by nobody (router has only E outputs);
        # their capacity rows stay zero — 1 - E/E_pad wasted expert FLOPs
        wi_p = jnp.pad(wi_p, ((0, E_pad - E), (0, 0), (0, 0)))
        wo_p = jnp.pad(wo_p, ((0, E_pad - E), (0, 0), (0, 0)))

    def inner(x_loc, router, wi, wo):
        # gather FSDP-sharded weights over the data axes (the normal FSDP
        # bill) — in bf16: casting BEFORE the gather halves the wire bytes
        # (perf iteration 5)
        wi = wi.astype(dtype)
        wo = wo.astype(dtype)
        if dp:
            router = jax.lax.all_gather(router, dp, axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, dp, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, dp, axis=2, tiled=True)
        Bl, Sl, _ = x_loc.shape
        x2d = x_loc.reshape(Bl * Sl, d)
        weights, expert_idx, aux = route(x2d, router, cfg)
        e_lo = jax.lax.axis_index(tp) * E_loc
        keep_mask = jnp.ones(expert_idx.shape, bool)
        y = _local_dispatch(x2d, weights, expert_idx, keep_mask, wi, wo,
                            e_lo, E_loc, C, dtype)
        y = jax.lax.psum(y, tp)                    # combine expert partials
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(Bl, Sl, d).astype(dtype), aux

    dpx = dp if len(dp) > 1 else (dp[0] if dp else None)
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(dpx, None, None), P(dpx, None),
                  P(tp, dpx, None), P(tp, None, dpx)),
        out_specs=(P(dpx, None, None), P()),
        check_vma=False,
    )(x, params["router"], wi_p, wo_p)
    return out

