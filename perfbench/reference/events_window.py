"""The plain reference for counts of users in a segment who did an event
inside a window of days:

    Count(Intersect(Range(frame=activity, rowID=e, start=a, end=b), ...,
                    Bitmap(frame=segment, rowID=s)))

It knows days and nothing else: no month, year or ``standard`` view, no
cover of a window by views, no bucket. A window is the days ``a <= day <
b`` (``Range`` covers ``[start, end)``, as upstream's ViewsByTimeRange
does); the users of a ``Range`` are the OR of the event's DAY bitmaps
over those days, made again from ``[seed, slice]`` by the generator's
``day_rows``; the answer is the popcount of the AND of every operand,
summed over the slices. One slice at a time and thread: 46 MB of day
bitmaps each at 181 days. Nothing of the program is imported.

The control (``answers(pqls, control=True)``) takes the end day too
(``a <= day <= b``): the reading of ``end`` that is one day off, the
nearest precision below ``[start, end)``."""
import datetime
import importlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib import pql

THREADS = 12


class Reference:
    def __init__(self, config, data):
        shape = config["shape"]
        self.config, self.seed = config, data["seed"]
        self.gen = importlib.import_module(
            f"perfbench.datagen.{config['datagen']}")
        self.activity = shape["activity_frame"]
        self.segment = shape["segment_frame"]
        self.events = set(shape["events"].values())
        self.first = datetime.date.fromisoformat(shape["first_day"])
        self.n_days = self.gen.n_days(config)

    def _day(self, text):
        """Days since the first of a PQL time that is a midnight."""
        t = datetime.datetime.strptime(text, "%Y-%m-%dT%H:%M")
        if t.time() != datetime.time(0, 0):
            raise ValueError(f"not a day boundary: {text}")
        return (t.date() - self.first).days

    def operands(self, query, control=False):
        """[("days", event, a, b) | ("segment", row)] of one query, the
        window cut to the days that have data."""
        call = pql.parse(query)
        if (call.name != "Count" or len(call.children) != 1
                or call.children[0].name != "Intersect"):
            raise ValueError(f"not a Count of an Intersect: {query}")
        out = []
        for kid in call.children[0].children:
            args = kid.args
            if kid.children:
                raise ValueError(f"not a leaf: {query}")
            if (kid.name == "Range" and args.get("frame") == self.activity
                    and set(args) == {"frame", "rowID", "start", "end"}
                    and args["rowID"] in self.events):
                a = self._day(args["start"])
                b = self._day(args["end"]) + (1 if control else 0)
                out.append(("days", args["rowID"], min(max(a, 0), self.n_days),
                            min(max(b, 0), self.n_days)))
            elif (kid.name == "Bitmap" and args.get("frame") == self.segment
                  and set(args) == {"frame", "rowID"}):
                out.append(("segment", args["rowID"]))
            else:
                raise ValueError(f"not a window or a segment row: {query}")
        if not any(op[0] == "days" for op in out):
            raise ValueError(f"no window: {query}")
        return out

    def per_slice_counts(self, pqls, control=False):
        """int64[len(pqls), slices]: each query's count in each slice."""
        trees = [self.operands(q, control) for q in pqls]
        n_slices = self.config["shape"]["slices"]
        out = np.zeros((len(trees), n_slices), dtype=np.int64)

        def one_slice(s):
            days = self.gen.day_rows(self.config, self.seed, s)
            segments = self.gen.segment_rows(self.config, self.seed, s)
            for i, tree in enumerate(trees):
                acc = None
                for op in tree:
                    if op[0] == "segment":
                        words = segments[op[1]]
                    else:
                        _, event, a, b = op
                        words = np.bitwise_or.reduce(days[event, a:b], axis=0) \
                            if b > a else np.zeros_like(segments[0])
                    acc = words if acc is None else acc & words
                out[i, s] = int(np.bitwise_count(acc).sum())

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(one_slice, range(n_slices)))
        return out

    def answers(self, pqls, control=False):
        return [int(x) for x in
                self.per_slice_counts(pqls, control).sum(axis=1)]

    def explain(self, query, got, want):
        return {"query": query, "got": got, "want": want,
                "difference": (got - want) if isinstance(got, int) else None}
