"""Share of the traced trajectory (restore to drain) in which the device
ran no operation: 1 - (union of device op intervals) / window."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
