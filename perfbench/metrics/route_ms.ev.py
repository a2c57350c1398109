"""Layer: tier choice. Source: program_span: ``count.plan`` (the
planner: the tree's first walk, a cardinality estimate an operand,
the order of the Intersect, the tier) + ``result.memo`` + ``exec.route``
of a request, median, as ``route_ms.c1``. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("count.plan", "result.memo",
                                      "exec.route"))
