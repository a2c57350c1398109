"""Layer: tier choice. Source: program_span: requests whose
``fallbackChain`` holds an ``:error`` hop (should be 0). Moves
query_p95_ms."""
from perfbench.lib import layer

read = layer.error_hops
