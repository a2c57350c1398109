"""Bytes a BSI ``Sum(filter, frame, field)`` request needs from HBM, from
its text and the schema alone: the planes and the exists row of the
summed field, those of each DISTINCT field under a condition of the
filter (two conditions on one field read it once), and one row a
``Bitmap`` leaf; each once over every slice. These are the algorithm's
bytes, not what an implementation happens to move (a plane stack read
once a condition, a masked copy of the planes before the popcount, is
the implementation's and lowers the share)."""
from . import pql
from .bytes_model import SLICE_ROW_BYTES


def field_rows(field):
    """Rows a bit-sliced field holds: a plane a bit of ``max - min``,
    and the exists row."""
    return int(field["max"] - field["min"]).bit_length() + 1


def sum_rows(call, fields):
    """Rows a slice that one parsed ``Sum`` reads. ``fields`` is
    {(frame, field): {"min", "max"}}."""
    read = {(call.args["frame"], call.args["field"])}
    read |= {(frame, field) for frame, field, _ in pql.conditions(call)}
    return sum(field_rows(fields[f]) for f in read) + len(pql.leaves(call))


def sum_bytes(call, fields, n_slices):
    return sum_rows(call, fields) * n_slices * SLICE_ROW_BYTES
