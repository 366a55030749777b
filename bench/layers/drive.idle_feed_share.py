"""Share of device 0's idle time in the traced trajectory (the window and
gaps of ``xplane.reduce``) that lies inside the program's
``drive.wait_feed`` spans: the drive loop waiting on the feed."""
import scopes


def read(ctx):
    r = scopes.of(ctx)
    if r is None or not r["wait_feed_spans"] or r["idle_s"] <= 0:
        return None
    return 100.0 * r["idle_in_wait_feed_s"] / r["idle_s"]
