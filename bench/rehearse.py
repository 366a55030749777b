#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e chip, on the CPU,
and print their ``memory_analysis()``: no chip, no run, no time.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [workload ...] [--repeat-lanes k]

For each cell (all of ``BENCHMARK.json`` by default) it lowers and compiles
the window program the cell's drive loop launches (``run_windows_jit`` for one
lane, ``run_scenarios_jit`` for a fleet) at the cell's batch of windows,
and the accounting resync, for one chip of a ``v5e:2x2`` topology. A cell
on ``chips`` > 1 compiles ``run_scenarios_sharded_jit`` and
``resync_fleet_jit`` instead, over a ``('data',)`` mesh of the topology's
first ``chips`` devices, the lanes sharded over it and the window batch
replicated (``NamedSharding`` arguments, as ``ScenarioFleet(mesh=)``
launches them); its ``memory_analysis()`` is each device's.
``--repeat-lanes k`` compiles a fleet with its lane list repeated k times,
to size a fleet against the chip's memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def _gb(n: int) -> str:
    return f"{n / 2**30:.2f} GB"


def report(tag: str, compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {"args": ma.argument_size_in_bytes, "out": ma.output_size_in_bytes,
           "alias": ma.alias_size_in_bytes, "temp": ma.temp_size_in_bytes,
           "code": ma.generated_code_size_in_bytes}
    out["total"] = (out["args"] + out["out"] - out["alias"] + out["temp"]
                    + out["code"])
    print(f"{tag}: " + " ".join(f"{k}={_gb(v)}" for k, v in out.items()),
          flush=True)
    return out


def rehearse(workload: str, repeat: int, topo) -> dict:
    import jax
    import numpy as np
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              PartitionSpec as P, SingleDeviceSharding)
    import run
    from repro.core import engine
    from repro.core.events import empty_window
    from repro.core.state import init_state
    from repro.scenarios import ScenarioFleet, ScenarioSpec
    from repro.scenarios import batch as batch_mod

    cell = run.resolve(ROOT, workload)
    cfg = run.sim_config(cell.config)
    W = int(cell.traffic["batch_windows"])
    lanes = run.lane_specs(cell.traffic) * repeat
    sh = SingleDeviceSharding(topo.devices[0])

    def spec(tree, lead=(), sharding=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            lead + x.shape, x.dtype, sharding=sharding or sh), tree)

    windows = spec(empty_window(cfg), (W,))
    res = {}
    if len(lanes) == 1:
        state = spec(jax.eval_shape(lambda: init_state(cfg)))
        res["window"] = report(f"{workload} run_windows_jit W={W}",
                               engine.run_windows_jit.lower(
                                   state, windows, cfg,
                                   lanes[0]["scheduler"], 0).compile())
        res["resync"] = report(f"{workload} resync_accounting_jit",
                               engine.resync_accounting_jit.lower(
                                   state, cfg).compile())
        return res
    specs = [ScenarioSpec(name=f"lane{i}", **ln) for i, ln in
             enumerate(lanes)]
    probe = ScenarioFleet(cfg, iter(()), specs, batch_windows=W)
    if cell.chips > 1:
        if len(lanes) % cell.chips:
            raise SystemExit(f"{workload}: {len(lanes)} lanes do not split "
                             f"over {cell.chips} chips")
        mesh = Mesh(np.array(topo.devices[:cell.chips]),
                    (batch_mod.FLEET_AXIS,), axis_types=(AxisType.Auto,))
        lane_sh = NamedSharding(mesh, P(batch_mod.FLEET_AXIS))
        state = spec(jax.eval_shape(lambda: probe.state), sharding=lane_sh)
        knobs = spec(probe.knobs, sharding=lane_sh)
        windows = spec(empty_window(cfg), (W,), NamedSharding(mesh, P()))
        tag = f"B={len(lanes)} over {cell.chips} devices"
        res["window"] = report(
            f"{workload} run_scenarios_sharded_jit {tag} W={W}",
            batch_mod.run_scenarios_sharded_jit.lower(
                state, windows, knobs, cfg, probe.scheduler_names, mesh, 0,
                has_storm=run.need(probe, "_has_storm"),
                table=probe.dispatch_table).compile())
        res["resync"] = report(f"{workload} resync_fleet_jit {tag}",
                               batch_mod.resync_fleet_jit.lower(
                                   state, cfg).compile())
        return res
    state = spec(jax.eval_shape(lambda: probe.state))
    knobs = spec(probe.knobs)
    res["window"] = report(
        f"{workload} run_scenarios_jit B={len(lanes)} W={W}",
        batch_mod.run_scenarios_jit.lower(
            state, windows, knobs, cfg, probe.scheduler_names, 0,
            has_storm=run.need(probe, "_has_storm"),
            table=probe.dispatch_table,
            lane_scheds=run.need(probe, "_lane_scheds")).compile())
    res["resync"] = report(f"{workload} resync_fleet_jit B={len(lanes)}",
                           batch_mod.resync_fleet_jit.lower(
                               state, cfg).compile())
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--repeat-lanes", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = args.workloads or [w["name"] for w in
                                   json.load(f)["workloads"]]
    out = {n: rehearse(n, args.repeat_lanes, topo) for n in names}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
