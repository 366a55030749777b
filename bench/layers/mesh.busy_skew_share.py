"""How far the chips of a sharded fleet wait on the slowest: 1 - (mean
over the chips of their busy time) / (the busiest chip's), where a chip's
busy time is the union of its op intervals in the traced trajectory
(``xplane.reduce``'s ``busy_by_device_s``); nothing on one chip."""


def read(ctx):
    busy = ctx["trace"]["busy_by_device_s"]
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / max(busy))
