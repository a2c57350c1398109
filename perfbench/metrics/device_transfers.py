"""Layer: staging. Source: program_counter: host-to-device transfers
(/debug/kernels ``transfers.count``) over the window; 0 once staged.
Moves query_p95_ms."""
from perfbench.lib import layer


def read(ctx):
    return layer.counter_delta(ctx, "deviceTransfers")
