"""Per-chip switchless dispatch of the mesh-sharded fleet.

Under a mesh the fleet keeps its static lane map: each chip's lanes get
their own switchless dispatch, picked by shard index (a conditional that
runs one branch), and chips that share a lane map need no conditional.
Every case holds stats rows and final states lane for lane, bitwise,
against the unsharded switchless fleet and the forced ``lax.switch``
sharded fleet. Fake CPU devices, in subprocesses, as in
``tests/test_scenarios_sharded.py``.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count={n}")
    import dataclasses, json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import REDUCED_SIM
    from repro.core.events import stack_windows
    from repro.core.tracegen import SHIFT_US, generate_trace
    from repro.parsers.gcd import GCDParser
    from repro.scenarios import ScenarioFleet, ScenarioSpec, fleet_mesh
    from repro.scenarios import batch as batch_mod
    from repro.sched import register_scheduler

    assert jax.device_count() == {n}
    CFG = dataclasses.replace(REDUCED_SIM, inject_slots=16,
                              inject_task_slots=64)
    MIX = ("greedy", "simulated_annealing", "round_robin", "first_fit")

    def mixed(n_lanes, mix=MIX):
        # chip_smoke.mixed_specs: scheduler i % 4, outage 10% x (i // 4)
        return [ScenarioSpec(name=f"{{mix[i % len(mix)]}}/{{i}}",
                             scheduler=mix[i % len(mix)],
                             node_outage_frac=0.1 * (i // len(mix)))
                for i in range(n_lanes)]

    tmp = tempfile.mkdtemp()
    generate_trace(tmp, n_machines=24, n_jobs=40, horizon_windows=12,
                   seed=29, usage_period_us=10_000_000)

    def windows(cfg):
        return GCDParser(cfg, tmp).packed_windows(
            16, start_us=SHIFT_US - cfg.window_us)

    def run(specs, mesh, cfg=CFG):
        fleet = ScenarioFleet(cfg, windows(cfg), specs, batch_windows=8,
                              mesh=mesh)
        fleet.run()
        return fleet

    def same(a, b):
        fa, fb = a.stats_frame(), b.stats_frame()
        for key in fa:
            np.testing.assert_array_equal(np.asarray(fa[key]),
                                          np.asarray(fb[key]), err_msg=key)
        n = a.n_scenarios
        for f in a.state._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a.state, f))[:n],
                np.asarray(getattr(b.state, f))[:n], err_msg=f)
        return True

    def conditionals(fleet):
        batch = jax.tree.map(jnp.asarray,
                             stack_windows(list(windows(fleet.cfg))[:8]))
        return batch_mod.run_scenarios_sharded_jit.lower(
            fleet.state, batch, fleet.knobs, fleet.cfg,
            fleet.scheduler_names, fleet.mesh, 0,
            has_storm=fleet._has_storm, table=fleet.dispatch_table,
            lane_scheds=fleet._lane_scheds).compile().as_text().count(
                "conditional(")

    out = {{}}
    mesh = fleet_mesh({n})
    specs = mixed({lanes})
    ref = run(specs, None)
    table = run(specs, mesh)
    switch = run(specs, mesh, dataclasses.replace(CFG,
                                                  sched_dispatch="switch"))
    out["lanes"] = table.n_lanes
    out["table_eq_unsharded"] = same(ref, table)
    out["table_eq_switch"] = same(switch, table)
    out["reported"] = [ref.sched_dispatch, ref.n_layouts,
                       table.sched_dispatch, table.n_layouts,
                       switch.sched_dispatch]
    out["conditionals"] = conditionals(table)
    if {n} == 4:
        # one lane map on every chip: no conditional
        one = mixed(8, MIX[:2])
        one_fleet = run(one, mesh)
        out["one_layout"] = [one_fleet.n_layouts, conditionals(one_fleet),
                             same(run(one, None), one_fleet)]
        # an opaque plugin on a mesh: the lax.switch fallback, unchanged
        def pack_left(state, cfg, rng, idx, valid, base_ok, scores):
            return jnp.broadcast_to(state.node_reserved.sum(-1)[None, :],
                                    base_ok.shape)
        register_scheduler("_t_opaque", pack_left)
        opaque = mixed(4) + [ScenarioSpec(name="p", scheduler="_t_opaque")]
        o_mesh = run(opaque, mesh)
        out["opaque"] = [o_mesh.sched_dispatch, same(run(opaque, None),
                                                     o_mesh)]
        # 'table' on a mesh: runs with the lane map, raises without it
        strict = dataclasses.replace(CFG, sched_dispatch="table")
        out["strict_eq"] = same(ref, run(specs, mesh, strict))
        probe = ScenarioFleet(strict, iter(()), specs, mesh=mesh)
        batch = jax.tree.map(jnp.asarray,
                             stack_windows(list(windows(strict))[:8]))
        try:
            batch_mod.run_scenarios_sharded(
                probe.state, batch, probe.knobs, strict,
                probe.scheduler_names, mesh, 0, probe._has_storm,
                probe.dispatch_table, None)
            out["strict_no_map"] = "ran"
        except ValueError as e:
            out["strict_no_map"] = str(e)
    print(json.dumps(out))
""")


def _run(n_devices: int, lanes: int) -> dict:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(n=n_devices, lanes=lanes)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def four():
    """12 mixed lanes over 4 fake devices: 3 a chip, 4 distinct lane maps
    (greedy, annealing, round robin, first fit in ``mixed_specs`` order)."""
    return _run(4, 12)


@pytest.fixture(scope="module")
def eight_padded():
    """13 mixed lanes over 8 fake devices: 3 pad lanes, 2 a chip."""
    return _run(8, 13)


def test_per_chip_dispatch_matches_unsharded_and_switch(four):
    assert four["lanes"] == 12
    assert four["table_eq_unsharded"] and four["table_eq_switch"]


def test_padded_per_chip_dispatch_matches(eight_padded):
    assert eight_padded["lanes"] == 16
    assert eight_padded["table_eq_unsharded"]
    assert eight_padded["table_eq_switch"]


def test_fleet_reports_its_dispatch(four, eight_padded):
    assert four["reported"] == ["table", 1, "table", 4, "switch"]
    # chips of 2: (g SA) (RR FF) repeat, the last chip holds a pad lane
    assert eight_padded["reported"][2:4] == ["table", 3]


def test_distinct_chip_maps_compile_to_one_conditional(four, eight_padded):
    assert four["conditionals"] == 1
    assert eight_padded["conditionals"] == 1


def test_one_chip_map_compiles_to_no_conditional(four):
    n_layouts, n_conditionals, equal = four["one_layout"]
    assert (n_layouts, n_conditionals) == (1, 0) and equal


def test_opaque_plugin_on_a_mesh_keeps_the_switch(four):
    assert four["opaque"] == ["switch", True]


def test_table_mode_on_a_mesh_needs_the_lane_map(four):
    assert four["strict_eq"]
    assert "lane_scheds" in four["strict_no_map"]
