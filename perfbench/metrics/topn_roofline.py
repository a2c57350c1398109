"""Layer: kernels. Source: device_trace: the TopN requests' share of the HBM
roofline: the bytes the traced requests need from their shape alone
(``perfbench/lib/topn_bytes_model.py``: one scan of the fragment, rows x
(512 + 4) B + 512 B of probe, and a recount of the answer's rows, 512 B
each; whatever path served them) over 819 GB/s, over the device time of
every program launched in the traced interval. Moves query_p50_ms."""
from perfbench.lib import topn_layer

read = topn_layer.roofline_pct
