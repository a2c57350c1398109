"""Layer: kernels. Source: device_trace: device milliseconds a launch
of the programs named ``jit_pilosa_count_batched*`` on the trace's
``XLA Modules`` line: the scan of a bucketed cover and its segment
row. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.module_ms(ctx, spans.COUNT_PROGRAMS)
