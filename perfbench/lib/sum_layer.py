"""What the BSI Sum cell's per-layer readers share."""
from . import layer, pql, spans, sum_bytes_model

# The batched Sum program on the trace's ``XLA Modules`` line.
SUM_PROGRAM = "jit_pilosa_sum_batched"


def fields_of(config):
    shape = config["shape"]
    return {(shape["bsi_frame"], name): field
            for name, field in shape["fields"].items()}


def roofline_pct(ctx):
    """``layer.roofline_pct`` over the bytes each request needs by its
    text alone (``sum_bytes_model.sum_bytes``), whatever tier served
    it."""
    fields, n_slices = fields_of(ctx.config), ctx.config["shape"]["slices"]
    return layer.roofline_pct(
        ctx, lambda q: sum_bytes_model.sum_bytes(pql.parse(q), fields,
                                                 n_slices))


def prelude_hit_pct(ctx):
    """Of the lookups of the BSI prelude memo that the window's profiled
    requests made, the share that hit. None where no profile has the
    keys (an older program) or no lookup was made."""
    hits = spans.resources_sum(ctx, "bsiPreludeHits")
    misses = spans.resources_sum(ctx, "bsiPreludeMisses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
