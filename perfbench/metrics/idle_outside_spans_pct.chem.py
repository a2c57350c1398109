"""Layer: device. Source: device_trace: of the device's idle time in
the traced interval, the share under no leaf span of a profiled request
(between requests, or in a parent span's self time), with the device's
clock under the by-name shift (``top.kernel`` paired with the launch of
its ``program``); the idle seconds by innermost span go to stderr
(``idle_by_span``). As ``idle_outside_spans_pct.c1``. Moves
query_p50_ms."""
from perfbench.lib import chains

read = chains.idle_outside_spans_pct
