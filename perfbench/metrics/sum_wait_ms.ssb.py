"""Layer: device. Source: program_span: ``kernel.wait``
(``block_until_ready`` on both outputs) + ``kernel.fetch`` (the plane
and filter counts copied to the host) of a request, median. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("kernel.wait", "kernel.fetch"))
