"""Layer: device. Source: program_counter: the highest
``peak_bytes_in_use`` of any device of the server after the window
(/debug/vars device.memoryStats, the device's own counter as the
program reports it), in GB (1e9 bytes). Moves query_p95_ms: where the
peak nears the chip's 16.9 GB an allocation fails and the request falls
to the per-slice path (``batched:error``, PR 21)."""


def read(ctx):
    return max(ctx.memory_peaks) / 1e9 if ctx.memory_peaks else None
