"""Host spans and device pass scopes, on the profiler's clock.

The one place the program calls the profiler. Both record only while a
``jax.profiler`` trace runs (``--profile-dir`` on the CLIs, or any
``jax.profiler.trace`` around a run) and cost nothing otherwise:

* :func:`span` is a ``TraceAnnotation``: a host event with its keyword
  args as stats, encoded only when a trace is on. The drive loop and the
  prefetcher open them (``drive.*``, ``feed.*``; README "Tracing").
* :func:`scoped` runs a function under a ``jax.named_scope``: trace-time
  metadata on every device op the function emits (the op's ``tf_op`` in a
  device trace), so the window program's time can be split by pass. No
  run-time cost; it changes no value.

The pass names below are spelled here only.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax

EVENTS = "pass.events"      # event apply, eviction, perturbation, recomputes
BASE = "pass.base"          # pending top-k, constraint match, scores
PROPOSE = "pass.propose"    # each scheduler's proposal
COMMIT = "pass.commit"      # capacity-checked commit and its tally
STATS = "pass.stats"        # the stats row
PASSES = (EVENTS, BASE, PROPOSE, COMMIT, STATS)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` whose ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def profile(log_dir: Optional[str]):
    """A profiler trace of the enclosed run written under ``log_dir``
    (``plugins/profile/<run>/*.xplane.pb``, TensorBoard's layout); no trace
    where ``log_dir`` is None."""
    if log_dir is None:
        return contextlib.nullcontext()
    return jax.profiler.trace(log_dir)


def scope(name: str):
    """A device pass scope: every op traced inside it carries ``name``."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: trace the function inside the pass scope ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
