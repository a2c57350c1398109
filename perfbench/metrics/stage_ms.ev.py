"""Layer: staging. Source: program_span: ``plan_and_stage`` (a batched
Count's prelude: the plan lookup, the prelude memo, one fragment-list
lookup a (frame, view) and one stack-cache lookup an operand of the
bucketed cover, the window, the budget) of a request, median. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("plan_and_stage",))
