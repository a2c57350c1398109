"""Layer: device. Source: device_trace: 1 minus the union of the device's
operation intervals over the traced window, as ``device_idle_pct.c1``.
Moves query_p50_ms."""
from perfbench.lib import layer

read = layer.device_idle_pct
