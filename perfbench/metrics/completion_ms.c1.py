"""Layer: device. Source: device_trace: end of the launch a request's
``kernel.dispatch`` span caused (found by the program's name) to the end of
its ``kernel.wait`` span, the return of ``block_until_ready``: how long the
completion's notice takes to reach the calling thread, median over the
paired requests; an UPPER limit (the device clock is placed by causality:
``lib/chains.py``). With ``launch_delay_ms`` and the scan it adds up to the
launch-site span plus the wait span. Moves query_p50_ms."""
from perfbench.lib import chains

read = chains.completion_ms
