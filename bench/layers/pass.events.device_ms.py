"""Device time of the window program's ``pass.events`` scope (op self time,
``scopes.py``), summed over the chips, per lane-window advanced in
the traced trajectory."""
import scopes


def read(ctx):
    return scopes.pass_ms(ctx, "pass.events")
