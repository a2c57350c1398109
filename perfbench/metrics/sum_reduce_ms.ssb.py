"""Layer: kernels. Source: program_span: ``sum.reduce`` (the host's
weighted sum of the plane counts, 2^i a plane, in Python integers) of a
request, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("sum.reduce",))
