"""Layer: staging. Source: program_counter: of the lookups of a BSI
condition's predicate bits among the executor's resident device copies
that the window's profiled requests made, the share that hit
(``resources.bsiBitsHits`` over hits + ``bsiBitsMisses``): a miss uploads
the vector once, a hit passes the launch an array already on the device.
The flight's bounds come from a small closed set, so warm-up sees almost
every vector. None where no profile has the keys (a program without the
resident copies) or no lookup was made. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    hits = spans.resources_sum(ctx, "bsiBitsHits")
    misses = spans.resources_sum(ctx, "bsiBitsMisses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
