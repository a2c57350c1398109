"""Layer: staging. Source: program_counter: of the (frame, view)
fragment lists the window's profiled requests needed for their
preludes (one a view of a cover), the share served from the plan
cache's ``leaf`` entries (``resources.leafMemoHits``) and not walked
(``leafMemoMisses``), as ``leaf_memo_hit_pct.c1``. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    hits = spans.resources_sum(ctx, "leafMemoHits")
    misses = spans.resources_sum(ctx, "leafMemoMisses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
