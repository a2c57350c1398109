"""Layer: tier choice. Source: program_span: the share of ``servedBy``
notes of ``?profile=true`` that say ``serial`` (the per-slice path).
Moves query_p50_ms."""
from perfbench.lib import layer

read = layer.serial_share_pct
