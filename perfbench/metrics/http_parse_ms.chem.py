"""Layer: HTTP front end. Source: program_span: the root span's
``httpParseMs`` tag (request line read to root span start), median, as
``http_parse_ms.c1``. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.median_root_tag(ctx, "httpParseMs")
