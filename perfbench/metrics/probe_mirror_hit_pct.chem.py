"""Layer: TopN phases. Source: program_counter: of the per-fragment TopN
scans with a src that the window's profiled requests made, the share
whose probe the scan's program read from the HBM mirror
(``resources.topnProbeFromMirror``) and not from host words built by
executing the child (``topnProbeFromHost``). None where no profile has
the keys (an older program) or no scan had a src. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    mirror = spans.resources_sum(ctx, "topnProbeFromMirror")
    host = spans.resources_sum(ctx, "topnProbeFromHost")
    if mirror is None or host is None or mirror + host == 0:
        return None
    return 100.0 * mirror / (mirror + host)
