"""Layer: TopN phases. Source: program_span: span ``topn.mask`` (phase 1's
host work on the fetched count matrix: the per-slice ranked-cache mask,
a Python loop over (slice, cached id), and the per-slice cut at n; tags
``slices``, ``candidates``, ``cut``) of a request, median. None where
the program has no such span. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("topn.mask",))
