"""The plain reference for the taxi deployment's group-by counts:

    TopN(Intersect(Range(frame=pickup, rowID=0, start, end), <filter>),
         frame=<grouped dimension>[, n=N])

It works from the rides' attributes (pickup month, passenger count,
rounded miles) and knows nothing of bitmaps, views, covers or stacks.
The rides are reduced once, while they are generated, to the exact int64
cube slice x month x passenger count x distance (``Cube.add``); a window
of months is a difference of the cube's running sums over the months. A
query, read by ``perfbench/lib/pql.py``, is answered with upstream's
two-phase TopN semantics (executeTopN, executor.go:369-406):

1. per slice, the pairs (row, count) with a count of at least 1 over the
   rows of that fragment's ranked cache (the rows the slice holds at
   all), ordered by (-count, id) and cut at ``n`` where ``n > 0``;
2. merged by sum: the merged ids are the candidates;
3. the candidates recounted over every slice;
4. ordered by (-count, id) and cut at ``n``.

Nothing of the program is imported.

Two controls, each of which has to come out as not correct:
``control=True`` (or ``"window"``) takes the end month too (a <= month
<= b: the reading of ``Range``'s end that is one month off);
``control="no_recount"`` answers with phase 1's merged totals, so a row
cut from one slice's top ``n`` keeps only the counts of the slices that
kept it."""
import datetime

import numpy as np

from ..lib import pql


class Cube:
    """int64[slices, months, passenger counts, distances]: rides a cell."""

    def __init__(self, n_slices, n_months, n_passengers, n_distances):
        self.counts = np.zeros((n_slices, n_months, n_passengers,
                                n_distances), dtype=np.int64)

    def add(self, s, month, passenger, distance):
        """Slice s's rides: equal-length integer arrays of month index,
        passenger count and distance row."""
        _, m, p, d = self.counts.shape
        cell = (month.astype(np.int64) * p + passenger) * d + distance
        self.counts[s] += np.bincount(cell, minlength=m * p * d) \
            .reshape(m, p, d)


class Reference:
    def __init__(self, config, data):
        shape = config["shape"]
        cube = data["cube"].counts
        self.n_months = cube.shape[1]
        # Running sums over the months: a window [a, b) is two reads.
        self.running = np.concatenate(
            [np.zeros_like(cube[:, :1]), np.cumsum(cube, axis=1)], axis=1)
        self.first = datetime.date.fromisoformat(shape["first_month"] + "-01")
        self.pickup = shape["pickup_frame"]
        self.axes = {shape["passenger_frame"]: 2, shape["distance_frame"]: 3}
        # Rows each slice's fragment of a grouped frame holds: its ranked
        # cache, whose rows are TopN's candidates there.
        whole = self.running[:, -1]
        self.cached = {2: whole.sum(axis=2) > 0, 3: whole.sum(axis=1) > 0}

    def _month(self, text):
        """Months since the first of a PQL time that opens a month."""
        t = datetime.datetime.strptime(text, "%Y-%m-%dT%H:%M")
        if (t.day, t.hour, t.minute) != (1, 0, 0):
            raise ValueError(f"not a month boundary: {text}")
        return (t.year - self.first.year) * 12 + t.month - self.first.month

    def parse(self, query):
        """(grouped axis, n, first month, end month, filter axis, filter
        values) of one query."""
        call = pql.parse(query)
        group = self.axes.get(call.args.get("frame"))
        if (call.name != "TopN" or group is None
                or set(call.args) - {"frame", "n"}
                or len(call.children) != 1
                or call.children[0].name != "Intersect"
                or len(call.children[0].children) != 2):
            raise ValueError(f"not a group-by TopN of the taxi mix: {query}")
        window, filt = call.children[0].children
        args = window.args
        if (window.name != "Range" or args.get("frame") != self.pickup
                or set(args) != {"frame", "rowID", "start", "end"}
                or args["rowID"] != 0 or window.children):
            raise ValueError(f"not a window of months: {query}")
        leaves = [filt] if filt.name == "Bitmap" else filt.children
        if filt.name not in ("Bitmap", "Union") or not leaves or any(
                leaf.name != "Bitmap" or leaf.children
                or set(leaf.args) != {"frame", "rowID"}
                for leaf in leaves):
            raise ValueError(f"not a filter of the taxi mix: {query}")
        frames = {leaf.args["frame"] for leaf in leaves}
        axis = self.axes.get(frames.pop()) if len(frames) == 1 else None
        if axis is None or axis == group:
            raise ValueError(f"filter and group on one dimension: {query}")
        return (group, call.args.get("n", 0), self._month(args["start"]),
                self._month(args["end"]), axis,
                sorted({leaf.args["rowID"] for leaf in leaves}))

    def per_slice(self, query, control=False):
        """(int64[slices, rows of the grouped frame]: each row's count
        under the filter in each slice, the grouped axis, n)."""
        group, n, a, b, axis, values = self.parse(query)
        if control in (True, "window"):
            b += 1
        a, b = (min(max(x, 0), self.n_months) for x in (a, b))
        cells = self.running[:, max(a, b)] - self.running[:, a]  # [S, P, D]
        picked = np.take(cells, values, axis=axis - 1).sum(axis=axis - 1)
        return picked, group, n

    def answer(self, query, control=False):
        counts, group, n = self.per_slice(query, control)
        ids = np.arange(counts.shape[1])
        phase1 = np.where(self.cached[group] & (counts >= 1), counts, 0)
        if n:
            order = np.lexsort((np.broadcast_to(ids, counts.shape),
                                -phase1), axis=-1)
            rank = np.empty_like(order)
            np.put_along_axis(rank, order, np.broadcast_to(ids, order.shape),
                              axis=-1)
            phase1 = np.where(rank < n, phase1, 0)
        candidates = np.flatnonzero(phase1.sum(axis=0))
        totals = (phase1 if control == "no_recount" else counts).sum(axis=0)
        pairs = sorted(((int(-totals[r]), int(r)) for r in candidates
                        if totals[r] > 0))
        if n:
            pairs = pairs[:n]
        return [{"id": r, "count": -c} for c, r in pairs]

    def answers(self, pqls, control=False):
        return [self.answer(q, control) for q in pqls]

    def explain(self, query, got, want):
        """The rows on which the answers differ, with both counts."""
        as_map = lambda pairs: {d["id"]: d["count"] for d in pairs} \
            if isinstance(pairs, list) else {}
        g, w = as_map(got), as_map(want)
        rows = [{"id": i, "got": g.get(i), "want": w.get(i)}
                for i in sorted(set(g) | set(w)) if g.get(i) != w.get(i)]
        return {"query": query, "got_len": len(got)
                if isinstance(got, list) else None, "want_len": len(want),
                "differing_rows": rows[:40], "order_only": not rows}
