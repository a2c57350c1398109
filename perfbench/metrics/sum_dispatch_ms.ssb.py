"""Layer: kernels. Source: program_span: ``kernel.fn`` (program lookup)
+ ``kernel.dispatch`` (the jitted Sum call until it returns) of a
request, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("kernel.fn", "kernel.dispatch"))
