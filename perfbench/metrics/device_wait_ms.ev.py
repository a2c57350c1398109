"""Layer: device. Source: program_span: ``kernel.wait``
(``block_until_ready``) + ``kernel.fetch`` (the copy to the host) of a
request, median, as ``device_wait_ms.c1``. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("kernel.wait", "kernel.fetch"))
