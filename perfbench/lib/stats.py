"""The arithmetic of the end-to-end metrics, over a request log.

A request is a dict with ``t0`` and ``t1`` (seconds on one monotonic
clock, send and last byte read), ``ok`` (HTTP 200 and a body that
parsed) and, once compared, ``correct``. A failed or wrong request has
no latency: it counts as missing every limit, so it enters a percentile
as +infinity."""
import math


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between
    closest ranks, as numpy's default; +inf entries sort last. None for
    an empty list."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_ms(log):
    return [(r["t1"] - r["t0"]) * 1000.0 if r.get("correct") else math.inf
            for r in log]
