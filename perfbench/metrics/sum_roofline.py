"""Layer: kernels. Source: device_trace: the Sum requests' share of the HBM
roofline: the bytes the traced requests need by their text alone
(``perfbench/lib/sum_bytes_model.py``: planes + exists of the summed
field and of each distinct field under a condition, one row a Bitmap
leaf, x slices x 128 KiB; whatever tier served them) over 819 GB/s, over
the device time of every program launched in the traced interval. Moves
query_p50_ms."""
from perfbench.lib import sum_layer

read = sum_layer.roofline_pct
