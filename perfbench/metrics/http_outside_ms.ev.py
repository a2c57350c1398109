"""Layer: HTTP front end. Source: program_span (client latency minus the
root span of ``?profile=true``), median, as ``http_outside_ms.c1``. Moves
query_p50_ms."""
from perfbench.lib import layer

read = layer.http_outside_ms
