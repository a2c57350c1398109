"""Layer: fragment selection. Source: program_span: ``top.select`` (``isin``,
threshold mask, ``argpartition``, ``lexsort`` and pair building over the
fragment's counts, on the host; tag ``rows``) of a request, every scan of
it, median. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("top.select",))
