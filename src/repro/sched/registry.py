"""Declarative scheduler registry — schedulers plug in by name.

CloudSim's pluggable ``VmAllocationPolicy`` (and CloudSim Express's
declarative extension registry) is the baseline extensibility story this
reproduction benchmarks against; here the equivalent surface is one call:

    from repro.sched import register_scheduler

    def propose_pack_left(state, cfg, rng, idx, valid, base_ok, scores):
        # consolidate: prefer the most-reserved feasible node
        return jnp.broadcast_to(state.node_reserved.sum(-1)[None, :],
                                base_ok.shape)

    register_scheduler("pack_left", propose_pack_left)

A *proposal* has the uniform signature

    propose(state, cfg, rng, idx, valid, base_ok, scores) -> pref (P, N)

and the registry glues it to the shared passes (``base.base_pass`` in front,
``commit.finalize`` behind) to derive the classic ``(state, cfg, rng) ->
state`` entry point. Registered names are immediately usable everywhere a
scheduler name is accepted: ``SimConfig.scheduler``, ``ScenarioSpec``
scenario lanes (the fleet's ``lax.switch`` dispatch table is built from
``PROPOSERS``), the ``simulate``/``whatif`` CLIs, and benchmarks.

``SCHEDULERS`` / ``PROPOSERS`` / ``DYNAMIC_BESTFIT`` / ``TABLE_FORMS`` are
*derived views* of the registry kept in sync by :func:`register_scheduler`
— code that holds a reference to the dicts sees plugins registered after
import because the dict objects are shared, not copied. Fleet dispatch does
NOT read the live views at trace time: :func:`snapshot_dispatch` freezes
the rows a fleet was built against, so later registrations cannot retarget
a running fleet's scheduler indices.

A proposal may additionally register a *table form* — a parameterised
score transform over the shared base pass (see ``sched.table``) — which
lets the scenario fleet dispatch it switchlessly (grouped batched
evaluation instead of a vmapped ``lax.switch`` that runs every branch on
every lane) and, under ``cfg.use_kernels``, fuse the preference derivation
into the placement-commit kernel:

    register_scheduler("pack_left", propose_pack_left,
                       table_form=TableForm(tf_pack_left, params=()))

Plugins without a table form still work everywhere — fleets that mix one
in simply keep the ``lax.switch`` path (bitwise the same trajectories).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from repro.core import trace
from repro.sched.base import base_pass
from repro.sched.commit import finalize
from repro.sched.table import DispatchTable, TableForm


@dataclasses.dataclass(frozen=True)
class SchedulerEntry:
    """One registered scheduler: its proposal fn + commit-time policy."""
    name: str
    propose: Callable                 # (state, cfg, rng, idx, valid,
    #                                    base_ok, scores) -> pref (P, N)
    entry: Callable                   # (state, cfg, rng) -> state
    dynamic_bestfit: bool = False     # finaliser re-scores vs running tally
    doc: str = ""
    table_form: Optional[TableForm] = None
    #                                 # switchless/fused dispatch form; None
    #                                 # = opaque (fleets fall back to switch)


_REGISTRY: Dict[str, SchedulerEntry] = {}

# Derived views (same dict objects forever — register_scheduler mutates them
# in place so every importer, however old, observes new registrations).
SCHEDULERS: Dict[str, Callable] = {}
PROPOSERS: Dict[str, Callable] = {}
DYNAMIC_BESTFIT: Dict[str, bool] = {}
TABLE_FORMS: Dict[str, Optional[TableForm]] = {}


def register_scheduler(name: str, propose: Callable, *,
                       dynamic_bestfit: bool = False,
                       doc: Optional[str] = None,
                       table_form: Optional[TableForm] = None,
                       overwrite: bool = False) -> Callable:
    """Register a proposal fn under ``name``; returns the derived scheduler.

    The returned entry point is pure-JAX with signature
    ``(state, cfg, rng) -> state`` and is vmap-able, so registered
    schedulers work in the single-trajectory engine, the vmapped scenario
    fleet and the mesh-sharded fleet alike. ``dynamic_bestfit=True`` makes
    the finaliser re-score candidates against the running reservation tally
    (true best-fit-decreasing) instead of the static proposal.

    ``table_form`` (optional) registers the scheduler's proposal-table form
    for switchless fleet dispatch — a ``TableForm(transform, params,
    fused)`` whose transform must produce bitwise-identical preferences to
    ``propose`` (tested for every built-in). Without it the scheduler is
    *opaque*: usable everywhere, but a fleet mixing it keeps ``lax.switch``
    dispatch.
    """
    if not overwrite and name in _REGISTRY:
        raise ValueError(f"scheduler {name!r} already registered "
                         "(pass overwrite=True to replace it)")

    def scheduler(state, cfg, rng):
        idx, valid, base_ok, scores = base_pass(state, cfg)
        with trace.scope(trace.PROPOSE):
            pref = propose(state, cfg, rng, idx, valid, base_ok, scores)
        # a proposal that hands back the base pass's scores (greedy) lets
        # the commit derive base_ok, their finiteness mask, in place
        return finalize(state, cfg, idx, valid,
                        None if pref is scores else base_ok, pref,
                        dynamic_bestfit=dynamic_bestfit)

    scheduler.__name__ = name
    scheduler.__qualname__ = f"scheduler<{name}>"
    entry = SchedulerEntry(name=name, propose=propose, entry=scheduler,
                           dynamic_bestfit=dynamic_bestfit,
                           doc=(doc if doc is not None
                                else (propose.__doc__ or "").strip()),
                           table_form=table_form)
    _REGISTRY[name] = entry
    SCHEDULERS[name] = scheduler
    PROPOSERS[name] = propose
    DYNAMIC_BESTFIT[name] = dynamic_bestfit
    TABLE_FORMS[name] = table_form
    return scheduler


def unregister_scheduler(name: str) -> None:
    """Remove a registered scheduler (plugin teardown; built-ins included —
    there is nothing special about them beyond being registered first)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown scheduler {name!r}; have {list(_REGISTRY)}")
    del _REGISTRY[name]
    del SCHEDULERS[name]
    del PROPOSERS[name]
    del DYNAMIC_BESTFIT[name]
    del TABLE_FORMS[name]


def snapshot_dispatch(scheduler_names: Tuple[str, ...]) -> DispatchTable:
    """Freeze the registry rows ``scheduler_names`` into an immutable
    :class:`DispatchTable` — the fleet's dispatch contract.

    Taken once at fleet build time: the returned table is what the compiled
    program closes over, so registering / overwriting / removing schedulers
    afterwards cannot reorder or retarget an existing fleet's scheduler
    indices (regression-tested). Hashable — rides jit static args."""
    entries = [get_entry(n) for n in scheduler_names]
    return DispatchTable(
        names=tuple(scheduler_names),
        proposers=tuple(e.propose for e in entries),
        dynamic=tuple(e.dynamic_bestfit for e in entries),
        forms=tuple(e.table_form for e in entries))


def get_scheduler(name: str) -> Callable:
    try:
        return _REGISTRY[name].entry
    except KeyError:
        raise KeyError(f"unknown scheduler {name!r}; have {list(_REGISTRY)}")


def get_entry(name: str) -> SchedulerEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown scheduler {name!r}; have {list(_REGISTRY)}")


def list_schedulers() -> List[SchedulerEntry]:
    """Registered schedulers in registration order (built-ins first)."""
    return list(_REGISTRY.values())


def describe_schedulers() -> str:
    """Human-readable registry dump (the CLIs' --list-schedulers)."""
    lines = []
    for e in list_schedulers():
        summary = e.doc.split("\n")[0].strip() if e.doc else ""
        tag = " [dynamic best-fit commit]" if e.dynamic_bestfit else ""
        lines.append(f"  {e.name:<22}{summary}{tag}")
    return "registered schedulers:\n" + "\n".join(lines)
