"""Layer: tier choice. Source: program_span: the share of ``servedBy``
notes of ``?profile=true`` that say ``serial`` (the per-slice path: the
path model's look at the loser, or a fallback), as ``serial_share.c1``.
Moves query_p50_ms."""
from perfbench.lib import layer

read = layer.serial_share_pct
