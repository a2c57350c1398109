"""Layer: parse and plan. Source: program_span: ``range.cover`` (a time
Range's minimal view cover and its bucketed width, tags ``frame``,
``views``, ``operands``; once a walk of the tree, under ``count.plan``
and again under ``plan.tree`` where the planner reordered the call)
summed over a request, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("range.cover",))
