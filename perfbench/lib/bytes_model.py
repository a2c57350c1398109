"""Bytes a query needs from HBM, from its shape alone. These are the
algorithm's bytes, not what an implementation happens to move (XLA's
``cost_analysis`` counts that): each operand row is read once."""
from . import pql

SLICE_ROW_BYTES = (1 << 20) // 8          # one row of one slice, packed


def count_bytes(call, n_slices):
    """Count over a tree of set operations: every ``Bitmap`` leaf is one
    packed row over every slice; the result is a scalar."""
    return len(pql.leaves(call)) * n_slices * SLICE_ROW_BYTES
