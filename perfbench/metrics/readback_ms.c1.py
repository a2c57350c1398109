"""Layer: device. Source: device_trace: end of the program launch a
request caused to the end of its ``kernel.fetch`` span, on the trace's
clock, median. Moves query_p50_ms."""
from perfbench.lib import spans

read = spans.readback_ms
