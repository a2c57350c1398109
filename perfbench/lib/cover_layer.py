"""What the time-window cell's per-layer readers share."""
from . import layer, pql, spans, cover_bytes_model


def roofline_pct(ctx):
    """``layer.roofline_pct`` over the bytes each request needs by its
    text alone (``cover_bytes_model.count_bytes``), whatever tier served
    it and however wide the engine made the Union."""
    shape = ctx.config["shape"]
    return layer.roofline_pct(
        ctx, lambda q: cover_bytes_model.count_bytes(
            pql.parse(q), shape["time_quantum"], shape["slices"]))


def cover_pad_pct(ctx):
    """Of the operands that the plans of the window's profiled requests
    gave their time Ranges, the share that read a view of the cover
    again. None where no profile has the keys (an older program) or no
    Range was planned."""
    views = spans.resources_sum(ctx, "rangeCoverViews")
    operands = spans.resources_sum(ctx, "rangeCoverOperands")
    if views is None or not operands:
        return None
    return 100.0 * (operands - views) / operands
