"""Host ms of the feed's transfer of one window to the device: the
program's ``feed.h2d`` spans (its private host copy and ``device_put``)
summed over the traced trajectory, over the windows they carried."""
import scopes


def read(ctx):
    r = scopes.of(ctx)
    if r is None or not r["h2d_windows"]:
        return None
    return 1e3 * r["h2d_s"] / r["h2d_windows"]
