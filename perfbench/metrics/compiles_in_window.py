"""Layer: kernels. Source: program_counter: ``compileCalls`` of
/debug/kernels over the window (should be 0). Moves query_p95_ms."""
from perfbench.lib import layer


def read(ctx):
    return layer.counter_delta(ctx, "compileCalls")
