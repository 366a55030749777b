"""Host time to produce one window of the feed: the stack read and
decompress of ``core.precompile.replay_windows``, timed by the benchmark
around the source it hands ``WindowPrefetcher``, over every
trajectory of the measured window."""


def read(ctx):
    spans = ctx["spans"]
    if not spans.feed_windows:
        return None
    return 1e3 * spans.feed_s / spans.feed_windows
