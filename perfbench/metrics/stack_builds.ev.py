"""Layer: staging. Source: program_counter: ``resources.stackBuilds``
(device stacks a query had to build) summed over the window's profiled
requests, as ``stack_builds.c1``; 0 once all 410 are staged. Moves
query_p95_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.resources_sum(ctx, "stackBuilds")
