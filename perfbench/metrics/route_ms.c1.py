"""Layer: tier choice. Source: program_span: ``count.plan`` (planner
pass and tier decision) + ``result.memo`` (result-memo lookup) +
``exec.route`` (node partition, path model) of a request, median.
Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_span_ms(
        ctx, ("count.plan", "result.memo", "exec.route"))
