"""Logical-axis sharding: a minimal flax-linen-style logical partitioning layer.

Model code annotates params and activations with *logical* axis names
("embed", "ff", "batch", ...). A rule set maps logical names to mesh axes.
Rules differ between training (FSDP over data+pod, TP over model) and serving
(TP over model, weight-gather over data), and adapt per-architecture (e.g.
expert-parallel only when n_experts divides the TP degree).

Everything degrades to a no-op when no mesh/rules are active, so the same
model code runs single-device smoke tests unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, None, Tuple[str, ...]]

_state = threading.local()


def _current() -> Tuple[Optional[Mesh], Optional[Dict[str, Axis]]]:
    return getattr(_state, "mesh", None), getattr(_state, "rules", None)


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]]):
    """Activate (mesh, rules) for logical_constraint / make_sharding calls."""
    old = _current()
    _state.mesh, _state.rules = mesh, rules
    try:
        yield
    finally:
        _state.mesh, _state.rules = old


def resolve_spec(axes: Sequence[Optional[str]],
                 rules: Dict[str, Axis]) -> P:
    """Map logical axis names -> PartitionSpec, dropping duplicate mesh axes.

    A mesh axis may appear at most once in a PartitionSpec; when two logical
    dims resolve to the same mesh axis, the later one is left unsharded.
    """
    used = set()
    out = []
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        if m is None:
            out.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a not in used)
        if not ms:
            out.append(None)
        else:
            used.update(ms)
            out.append(ms if len(ms) > 1 else ms[0])
    return P(*out)


def logical_constraint(x, *axes: Optional[str]):
    """with_sharding_constraint by logical axes; no-op without an active mesh."""
    mesh, rules = _current()
    if mesh is None or rules is None:
        return x
    spec = resolve_spec(axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def make_sharding(axes: Sequence[Optional[str]], mesh: Mesh,
                  rules: Dict[str, Axis]) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(axes, rules))


def _divides(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def make_rules(mesh: Mesh, mode: str, cfg=None) -> Dict[str, Axis]:
    """Build the logical->mesh rule set.

    mode='train': batch + FSDP over (pod?, data); TP over model.
    mode='prefill': train layout + serve-style KV-cache sharding (the cache
                    is the prefill OUTPUT and must fit like decode's input).
    mode='serve': batch over (pod?, data); weights TP over model and
                  secondary-sharded over data (gathered per layer by XLA).
    mode='serve_seq': B too small to shard -> KV-cache sequence over data.
    """
    has_pod = "pod" in mesh.axis_names
    dp: Axis = ("pod", "data") if has_pod else ("data",)
    tp = "model"
    tp_deg = mesh_axis_size(mesh, "model")

    rules: Dict[str, Axis] = {
        # activations
        "batch": dp,
        "seq": None,
        "act_embed": None,
        "act_ff": tp,
        "act_q": tp,
        "act_kv": None,
        "tokens": dp,          # flattened token dim in MoE dispatch
        "seq_kv": None,
        # params
        "embed": dp,           # FSDP dim
        "vocab": tp,
        "q_dim": tp,
        "kv_dim": tp,
        "ff": tp,
        "ssm_proj": tp,
        "ssm_inner": tp,
        "conv_ch": tp,
        "ssm_heads": tp,
        "ssm_state": None,
        "head_dim": None,
        "heads": None,         # set below if divisible
        "expert": None,        # set below
        "expert_ff": None,
        "codebook": None,
        "stack": None,         # scan-over-repeats leading dim
    }

    if cfg is not None:
        if _divides(getattr(cfg, "n_heads", 0), tp_deg) or \
                getattr(cfg, "pad_head_shard", False):
            rules["heads"] = tp
        if _divides(getattr(cfg, "ssm_heads", 0), tp_deg):
            rules["act_ssm_heads"] = tp
        else:
            rules["act_ssm_heads"] = None
        n_exp = getattr(cfg, "n_experts", 0)
        if _divides(n_exp, tp_deg):
            rules["expert"] = tp           # expert parallelism
            rules["expert_ff"] = None
        elif n_exp:
            rules["expert"] = None         # per-expert tensor parallelism
            rules["expert_ff"] = tp

    if mode == "prefill":
        if cfg is not None and _divides(getattr(cfg, "n_kv_heads", 0), tp_deg):
            rules["act_kv"] = tp
        else:
            rules["seq_kv"] = (tp,)
    elif mode == "serve":
        rules["embed"] = dp                # weights stay data-sharded, gathered per layer
        # KV caches are the serving memory bill: batch shards over data, and
        # the cache shards over the TP axis too — by kv-HEADS when the count
        # divides it (MHA archs; keeps the cache update local), else by
        # SEQUENCE (GQA's 4-8 kv heads can't shard 16 ways; attention over a
        # seq-sharded cache costs one small psum per layer). Without this no
        # 32K-context decode cell fits in 16 GB (perf log iterations 0/0b).
        if cfg is not None and _divides(getattr(cfg, "n_kv_heads", 0), tp_deg):
            rules["act_kv"] = tp
        else:
            rules["seq_kv"] = (tp,)
    elif mode == "serve_seq":
        rules["embed"] = dp
        rules["batch"] = None
        rules["tokens"] = None
        rules["seq_kv"] = ("data", tp)     # B=1: sequence is the only big dim
    elif mode != "train":
        raise ValueError(f"unknown sharding mode {mode!r}")
    return rules
