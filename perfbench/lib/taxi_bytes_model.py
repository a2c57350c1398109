"""Bytes a group-by ``TopN(Intersect(<window>, <filter>), frame=<G>)``
request of the taxi deployment needs from HBM, from its text and the
schema alone: ONE scan of every row of the grouped frame ``G`` (its
candidates) and of the filter's leaf rows, the window read as the views
of its MINIMAL cover (``cover_bytes_model``: a month view each month,
a year view each whole year), each once over every slice. These are the
algorithm's bytes. Phase 2's recount of the candidates, the round trip
of the src bitmap through HBM between two programs, and the views a
bucketed Union reads again are the implementation's, and lower the
share: a later change that skips a redundant recount raises it towards
100 %, never past."""
from . import cover_bytes_model
from .bytes_model import SLICE_ROW_BYTES


def grouped_rows(call, shape):
    """Rows of the frame the TopN groups by: its candidates."""
    frame = call.args["frame"]
    if frame == shape["passenger_frame"]:
        return len(shape["passenger_pct"])
    if frame == shape["distance_frame"]:
        return shape["distance_rows"]
    raise ValueError(f"not a grouped frame of the taxi deployment: {frame}")


def request_rows(call, shape):
    """Rows a slice that one parsed TopN reads: the grouped frame's
    rows and the filter's leaves, a window as its minimal cover."""
    return grouped_rows(call, shape) + sum(
        cover_bytes_model.count_rows(c, shape["time_quantum"])
        for c in call.children)


def request_bytes(call, shape):
    return request_rows(call, shape) * shape["slices"] * SLICE_ROW_BYTES
