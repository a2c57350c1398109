"""Bytes a ``Count`` under time ``Range``s needs from HBM, from its text
and the frame's time quantum alone: one packed row over every slice for
each view of the MINIMAL cover of each window, and one for each
``Bitmap`` leaf. The cover is computed here (upstream's ViewsByTimeRange,
time.go:112-184, written again from its description: walk up from the
start by the finest unit until the next coarser one is aligned, then
down from the coarsest unit that still fits), not taken from the
program. These are the algorithm's bytes: a view read again to fill a
bucketed Union is the implementation's, and lowers the share."""
import datetime

from .bytes_model import SLICE_ROW_BYTES

UNITS = "YMDH"
TIME_FORMAT = "%Y-%m-%dT%H:%M"


def _next(t, unit):
    """The start of the unit after the one that holds ``t``."""
    if unit == "Y":
        return datetime.datetime(t.year + 1, 1, 1)
    if unit == "M":
        return datetime.datetime(t.year + t.month // 12, t.month % 12 + 1, 1)
    if unit == "D":
        return datetime.datetime(t.year, t.month, t.day) \
            + datetime.timedelta(days=1)
    return t + datetime.timedelta(hours=1)


def _view(t, unit):
    return t.strftime({"Y": "%Y", "M": "%Y%m", "D": "%Y%m%d",
                       "H": "%Y%m%d%H"}[unit])


def _first_of(t, unit):
    """Whether ``t`` opens a unit of the next coarser kind."""
    return {"H": t.hour == 0, "D": t.day == 1, "M": t.month == 1}[unit]


def _step_up(t, end, quantum):
    """The unit the walk up from ``t`` steps by next: the finest one of
    which ``t`` does not open the next coarser unit. None when that
    coarser unit no longer fits before ``end``, or ``t`` is aligned all
    the way up."""
    for unit in "HDM":
        if unit not in quantum:
            continue
        if _next(t, UNITS[UNITS.index(unit) - 1]) > end:
            return None
        if not _first_of(t, unit):
            return unit
    return None


def _step_down(t, end, quantum):
    """The coarsest unit that fits between ``t`` and ``end``; an hour
    always does."""
    return next((u for u in UNITS if u in quantum
                 and (u == "H" or _next(t, u) <= end)), None)


def cover(start, end, quantum):
    """The view suffixes (``2017``, ``201702``, ``20170214``, ...) of the
    minimal cover of [start, end) by the units of ``quantum``."""
    t, out = start, []
    for step in (_step_up, _step_down):
        while t < end:
            unit = step(t, end, quantum)
            if unit is None:
                break
            out.append(_view(t, unit))
            t = _next(t, unit)
    return out


def count_rows(call, quantum):
    """Rows a slice that one parsed ``Count`` reads: the views of each
    window's minimal cover and each ``Bitmap`` leaf."""
    if call.name == "Bitmap":
        return 1
    if call.name == "Range" and "start" in call.args:
        return len(cover(
            datetime.datetime.strptime(call.args["start"], TIME_FORMAT),
            datetime.datetime.strptime(call.args["end"], TIME_FORMAT),
            quantum))
    return sum(count_rows(c, quantum) for c in call.children)


def count_bytes(call, quantum, n_slices):
    return count_rows(call, quantum) * n_slices * SLICE_ROW_BYTES
