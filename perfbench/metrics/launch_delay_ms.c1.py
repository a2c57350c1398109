"""Layer: device. Source: device_trace: start of a request's
``kernel.dispatch`` span to the start of the program launch it caused,
on the trace's clock (the capture's anchor maps the span), median.
Moves query_p50_ms."""
from perfbench.lib import spans

read = spans.launch_delay_ms
