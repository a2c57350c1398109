"""Layer: staging. Source: program_span: ``sum.plan`` (a batched Sum's
prelude: plan lookup, prelude memo, fragment lists, window, budget, the
stacks from the stack cache, the predicate bits; tags ``memo``,
``leaves``, ``rows``) of a request, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("sum.plan",))
