"""Device idle share of the window, in %: one minus the union of the
device's op intervals over the traced window
(``trace.Summary.idle_share``). Read as ``idle_share.throughput`` in
the cells that report ``qps`` and as ``idle_share.latency`` in those
that report ``p99_ms``."""


def read(ctx):
    share = ctx.trace.idle_share
    return None if share is None else 100.0 * share
