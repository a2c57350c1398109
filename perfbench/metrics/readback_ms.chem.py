"""Layer: device. Source: device_trace: end of the launch a request's
``top.kernel`` span caused (found by the program's name) to the end of
its ``top.fetch`` span, the counts on the host: ``completion_ms`` plus
the fetch span, median over the paired requests, as ``readback_ms.c1``.
Moves query_p50_ms."""
from perfbench.lib import chains

read = chains.readback_ms
