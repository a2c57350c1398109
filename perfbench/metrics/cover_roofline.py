"""Layer: kernels. Source: device_trace: the windowed Counts' share of the
HBM roofline: the bytes the traced requests need by their text alone
(``perfbench/lib/cover_bytes_model.py``: the views of each window's
minimal cover, computed there, and one row a Bitmap leaf, x slices x
128 KiB; whatever tier served them, however wide the Union was made)
over 819 GB/s, over the device time of every program launched in the
traced interval. Moves query_p50_ms."""
from perfbench.lib import cover_layer

read = cover_layer.roofline_pct
