"""Layer: device. Source: program_span: ``top.wait`` (``block_until_ready``)
+ ``top.fetch`` (the copy of the fragment's counts to the host) of a
request, every scan of it, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("top.wait", "top.fetch"))
