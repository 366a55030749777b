"""jit'd wrapper for the constraint-match kernel: padding, the transposed
node layout, active-node folding, kernel/ref dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import by_platform
from repro.kernels.constraint_match.kernel import constraint_match_pallas
from repro.kernels.constraint_match.ref import constraint_match_ref


def _pad_to(x: jax.Array, n: int, axis: int = 0, fill=0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("use_kernel", "tile_p",
                                             "tile_n"))
def constraint_match(req, cons, node_total, node_reserved, node_attrs,
                     node_active, *, use_kernel: bool = False,
                     tile_p: int = 128, tile_n: int = 128) -> jax.Array:
    """Dispatch to the Pallas kernel or the pure-jnp reference. Shapes: req
    (P,R), cons (P,C,3), node_* (N,...). Returns (P, N) f32 scores with
    -inf for infeasible pairs. The kernel compiles for a TPU and runs in
    the Pallas interpreter elsewhere (:func:`repro.kernels.by_platform`)."""
    if not use_kernel:
        return constraint_match_ref(req, cons, node_total, node_reserved,
                                    node_attrs, node_active)

    P, N = req.shape[0], node_total.shape[0]
    Pp = -(-P // tile_p) * tile_p
    Np = -(-N // tile_n) * tile_n

    # fold node_active into capacity: inactive nodes can never fit any task
    total = jnp.where(node_active[:, None], node_total, -1.0)

    def run(*args, interpret):
        return constraint_match_pallas(*args, tile_p=tile_p, tile_n=tile_n,
                                       interpret=interpret)

    scores = by_platform(
        run, _pad_to(req, Pp),
        _pad_to(cons[:, :, 0], Pp), _pad_to(cons[:, :, 1], Pp),
        _pad_to(cons[:, :, 2], Pp),
        _pad_to(total, Np, fill=-1.0).T, _pad_to(node_reserved, Np).T,
        _pad_to(node_attrs.astype(jnp.int32), Np).T)
    return scores[:P, :N]
