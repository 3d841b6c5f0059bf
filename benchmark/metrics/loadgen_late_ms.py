"""99th percentile of how late the open-loop generator sent a request
behind its schedule, in ms. A starved generator shows here instead of
passing for a fast server."""

from benchmark import stats


def read(ctx):
    late = ctx.window.late_s
    return 1e3 * stats.percentile(late, 99) if late else None
