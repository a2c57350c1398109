"""Layer: device. Source: device_trace: end of the launch a request's
``kernel.dispatch`` span caused (found by the program's name) to the end of
its ``kernel.fetch`` span, the counts on the host: ``completion_ms`` plus
the fetch span, median over the paired requests, as ``readback_ms.c1``.
Moves query_p50_ms."""
from perfbench.lib import chains

read = chains.readback_ms
