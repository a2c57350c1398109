"""Bytes a ``TopN(Bitmap(rowID=p), n, tanimotoThreshold=T)`` request
needs from HBM, from its shape alone: the fragment's rows and columns
and the pairs it answers with. One scan of the fragment finds the rows
past the threshold; the exact recount reads those rows again. These are
the algorithm's bytes, not what an implementation happens to move (a
copy of the matrix before the scan, or a second scan in place of the
recount, is the implementation's, and lowers the share)."""

ROW_COUNT_BYTES = 4                 # |row|, int32: the gate's denominator


def row_bytes(n_columns):
    return -(-n_columns // 8)


def scan_bytes(n_rows, n_columns):
    """Every packed row and its row count once, and the probe row."""
    row = row_bytes(n_columns)
    return n_rows * (row + ROW_COUNT_BYTES) + row


def recount_bytes(n_candidates, n_columns):
    """The candidates' packed rows."""
    return n_candidates * row_bytes(n_columns)


def request_bytes(n_answered, n_rows, n_columns):
    """One request over one slice: a scan, and a recount of the pairs it
    answers with (at one slice the candidates of the recount are the
    answer's rows: the scan's survivors, cut at n)."""
    return scan_bytes(n_rows, n_columns) + recount_bytes(n_answered,
                                                         n_columns)
