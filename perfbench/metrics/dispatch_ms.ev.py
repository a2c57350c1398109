"""Layer: kernels. Source: program_span: ``kernel.fn`` (program lookup)
+ ``kernel.dispatch`` (the jitted call, 3 to 65 device-resident
operands, until it returns) of a request, median, as ``dispatch_ms.c1``.
Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("kernel.fn", "kernel.dispatch"))
