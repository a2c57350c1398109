"""Layer: tier choice. Source: program_span: ``result.memo`` (the Sum
result memo's lookup, kind ``sum_res``) + ``exec.route`` (node
partition and the path model's choice) of a request, median. Moves
query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(ctx, ("result.memo", "exec.route"))
