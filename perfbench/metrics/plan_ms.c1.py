"""Layer: parse and plan. Source: program_span (``planMs`` of
``?profile=true``), median. Moves query_p50_ms."""
from perfbench.lib import layer

read = layer.plan_ms
