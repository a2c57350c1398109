"""95th percentile of the same latencies, all requests of all clients."""
from perfbench.lib import stats


def read(ctx):
    return stats.percentile(stats.latencies_ms(ctx.log), 95)
