"""Layer: TopN phases. Source: program_counter: of the batched TopN
phase-1 passes of the window's profiled requests, the share that found
the grouped frame's ranked-cache membership memoised on its fragment
list (``resources.topnMembershipHits`` over hits +
``topnMembershipMisses``): a miss builds it from every fragment's cache,
a hit touches no fragment. One list a grouped frame over the 339 slices,
replaced only by a write, so warm-up builds both. None where no profile
has the keys (a program without the memo) or no pass was made. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    hits = spans.resources_sum(ctx, "topnMembershipHits")
    misses = spans.resources_sum(ctx, "topnMembershipMisses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
