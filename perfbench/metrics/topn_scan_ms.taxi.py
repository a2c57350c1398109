"""Layer: kernels. Source: device_trace: device milliseconds a request of
the programs named ``jit_pilosa_topn*`` on the trace's ``XLA Modules``
line (both phases' src programs and counts programs, and a serial
probe's per-fragment scans): their summed device time over the capture
by the number of profiled requests the capture holds. Moves
query_p50_ms."""
from perfbench.lib import layer

TOPN_PROGRAMS = "jit_pilosa_topn"


def read(ctx):
    if not ctx.trace:
        return None
    captured = sum(1 for _, p in layer.profiles(ctx) if p.get("capture"))
    seconds = sum(s for name, (s, _) in ctx.trace["modules"].items()
                  if name.startswith(TOPN_PROGRAMS))
    return 1000.0 * seconds / captured if captured else None
