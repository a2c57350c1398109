"""Layer: TopN phases. Source: program_span: span ``topn.phase2`` (the exact
re-query of phase 1's ids; tags ``path``, ``candidates``, ``bucket``) of a
request, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("topn.phase2",))
