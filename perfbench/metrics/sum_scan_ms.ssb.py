"""Layer: kernels. Source: device_trace: device milliseconds a launch
of the programs named ``jit_pilosa_sum_batched*`` on the trace's
``XLA Modules`` line. Moves query_p50_ms."""
from perfbench.lib import spans, sum_layer


def read(ctx):
    return spans.module_ms(ctx, sum_layer.SUM_PROGRAM)
