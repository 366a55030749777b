"""Device time of the accounting resync (``core/engine.resync_accounting_
jit``, ``scenarios/batch.resync_fleet_jit``) over the device's busy time
in the traced trajectory."""
import xplane


def read(ctx):
    s = xplane.module_s(ctx["trace"], "resync")
    busy = ctx["trace"]["busy_s"]
    if s <= 0 or busy <= 0:
        return None
    return 100.0 * s / busy
