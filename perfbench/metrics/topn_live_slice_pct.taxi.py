"""Layer: TopN phases. Source: program_counter: of the slices the batched
TopN phase 1 scanned for the window's profiled requests
(``resources.topnSlicesScanned``), the share whose column of the count
matrix was not all zero (``topnSlicesLive``): with the rides in pickup
order a window of months is about 14 slices a month, and the rest were
read and could give nothing. None where no profile has the keys (a
program without the counters) or nothing was scanned. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    scanned = spans.resources_sum(ctx, "topnSlicesScanned")
    live = spans.resources_sum(ctx, "topnSlicesLive")
    if not scanned or live is None:
        return None
    return 100.0 * live / scanned
