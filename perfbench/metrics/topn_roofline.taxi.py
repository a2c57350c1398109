"""Layer: kernels. Source: device_trace: the group-by TopN requests' share of
the HBM roofline: the bytes the traced requests need by their text alone
(``perfbench/lib/taxi_bytes_model.py``: one scan of the grouped frame's
64 or 10 rows and of the filter's leaves, the window as its minimal
cover of views, x 339 slices x 128 KiB; whatever path served them) over
819 GB/s, over the device time of every program launched in the traced
interval. Moves query_p50_ms."""
from perfbench.lib import layer, pql, taxi_bytes_model


def read(ctx):
    shape = ctx.config["shape"]
    return layer.roofline_pct(
        ctx, lambda q: taxi_bytes_model.request_bytes(pql.parse(q), shape))
