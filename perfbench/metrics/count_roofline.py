"""Layer: kernels. Source: device_trace: the Count programs' share of
the HBM roofline (operands x slices x 128 KiB over 819 GB/s, over the
programs' device time in the trace). Moves query_p50_ms."""
from perfbench.lib import layer


def read(ctx):
    return layer.roofline_pct(ctx, layer.count_bytes_of(ctx))
