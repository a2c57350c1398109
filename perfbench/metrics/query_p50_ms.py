"""Median latency over every request of the window, at the client's
side of the socket; a failed or wrong request counts as +infinity."""
from perfbench.lib import stats


def read(ctx):
    return stats.percentile(stats.latencies_ms(ctx.log), 50)
