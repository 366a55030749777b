"""Share of the window program's op self time in the traced trajectory
that lies under none of the five pass scopes (``scopes.py``); nothing
where no op carries a pass scope."""
import scopes


def read(ctx):
    r = scopes.of(ctx)
    if r is None or r["program_op_s"] <= 0 or \
            r["pass_s"][scopes.UNSCOPED] >= r["program_op_s"]:
        return None
    return 100.0 * r["pass_s"][scopes.UNSCOPED] / r["program_op_s"]
