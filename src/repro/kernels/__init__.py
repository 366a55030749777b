"""Pallas kernels for the simulator's hot passes, each as kernel.py (the
Pallas body) + ops.py (padding, layout, dispatch) + ref.py (the pure-jnp
oracle the tests hold the kernel to).

:func:`by_platform` is the one place that decides how a kernel runs: the
Mosaic-compiled kernel when the call lowers for a TPU, the Pallas
interpreter when it lowers for anything else (the CPU test backend). The
choice is made at lowering time (``lax.platform_dependent``), so a program
compiled for a TPU can never reach the interpreter, and one process can run
the same traced function on both its TPU and its CPU devices.
"""
from __future__ import annotations

import functools
import math

import jax


def by_platform(fn, *args):
    """``fn(*args, interpret=False)`` when lowered for a TPU, else
    ``fn(*args, interpret=True)``. ``fn`` may pick its tiling from
    ``interpret``; both branches must return the same shapes."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(fn, interpret=False),
        default=functools.partial(fn, interpret=True))


def on_tpu(kernel, default, *args):
    """``kernel(*args)`` when the call lowers for a TPU, ``default(*args)``
    for any other platform: the same lowering-time choice as
    :func:`by_platform`, for a pass whose TPU path is always its kernel
    (compiled there by ``by_platform`` inside ``kernel``) and whose other
    platforms keep a reference. ``default is kernel`` makes no choice."""
    if default is kernel:
        return kernel(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=default)


def vmem_limit(blocks, headroom: int = 4 << 20) -> int:
    """Scoped-VMEM request for a kernel whose blocks are ``blocks`` — a
    list of ``(shape, dtype)``. Each block is double-buffered and padded to
    the (8, 128) native tile; the result is clamped to [16 MiB, 100 MiB]
    (a v5e core has 128 MiB of VMEM)."""
    total = 0
    for shape, dtype in blocks:
        dims = list(shape) or [1]
        dims[-1] = -(-dims[-1] // 128) * 128
        if len(dims) > 1:
            dims[-2] = -(-dims[-2] // 8) * 8
        total += 2 * math.prod(dims) * jax.numpy.dtype(dtype).itemsize
    return int(min(max(total + headroom, 16 << 20), 100 << 20))
