"""Layer: device. Source: device_trace: of the device's idle time in
the traced interval, the share under no leaf span of a profiled request
(between requests, or in a parent span's self time); the idle seconds by
innermost span go to stderr. Moves query_p50_ms."""
from perfbench.lib import spans

read = spans.idle_outside_spans_pct
