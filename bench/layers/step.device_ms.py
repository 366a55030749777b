"""Device time of the window program (``core/engine.run_windows_jit`` or
``scenarios/batch.run_scenarios_jit`` / ``_sharded_jit``), summed over the
chips, per lane-window advanced in the traced trajectory."""
import xplane


def read(ctx):
    s = xplane.chips_module_s(ctx["trace"], "run_windows",
                              "run_scenarios")
    if s <= 0 or not ctx["traced"]["lane_windows"]:
        return None
    return 1e3 * s / ctx["traced"]["lane_windows"]
