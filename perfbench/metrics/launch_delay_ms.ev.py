"""Layer: device. Source: device_trace: start of a request's ``kernel.dispatch``
span to the start of the launch it caused, the launch found by the
program's name (the span's tag ``program``), on the trace's clock under
the by-name shift (a LOWER limit: the fastest launch of a capture reads
0), median over the paired requests; a request with a
``path.probe`` span or several launch sites is left out. As
``launch_delay_ms.c1`` where a request may launch other programs too.
Moves query_p50_ms."""
from perfbench.lib import chains

read = chains.launch_delay_ms
