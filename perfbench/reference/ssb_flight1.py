"""The plain reference for the Star Schema Benchmark's flight 1:

    select sum(lo_extendedprice * lo_discount), count(*) from lineorder,
    date where lo_orderdate = d_datekey and <date predicates> and
    lo_discount <condition> and lo_quantity <condition>

It works from the lineorder VALUES (order day, discount, quantity,
extended price) and knows nothing of bit planes, rows or slices. The
values are reduced once, while they are generated, to the exact int64
cube day x discount x quantity of (sum of price x discount, count)
(``Cube.add``); a query, read by ``perfbench/lib/pql.py``, is a sum over
the cube's cells that its date rows and its conditions select. The date
dimension (year, year-month number, week number in the year of each
order day) is computed here with ``datetime.date``, not taken from the
generator. Nothing of the program is imported.

The control (``answers(pqls, control=True)``) accumulates each selected
day's exact partial sum and count over the days in float32, the nearest
precision below the integers the configuration states."""
import datetime

import numpy as np

from ..lib import pql

N_DISCOUNT = 11            # lo_discount 0..10
N_QUANTITY = 51            # lo_quantity 1..50 (cell 0 stays empty)

COMPARE = {
    "==": np.equal, "!=": np.not_equal, "<": np.less, "<=": np.less_equal,
    ">": np.greater, ">=": np.greater_equal,
}


class Cube:
    """int64[days, 11, 51] sums of price x discount, and counts."""

    def __init__(self, n_days):
        self.shape = (n_days, N_DISCOUNT, N_QUANTITY)
        self.sums = np.zeros(self.shape, dtype=np.int64)
        self.counts = np.zeros(self.shape, dtype=np.int64)

    def add(self, day, discount, quantity, price):
        """One batch of lineorder rows (equal-length integer arrays).
        ``bincount`` adds its weights in float64: every partial sum is
        an integer below 2^53 (a batch's whole revenue is checked), so
        each cell is exact before it joins the int64 cube."""
        size = self.sums.size
        cell = (day.astype(np.int64) * N_DISCOUNT + discount) * N_QUANTITY \
            + quantity
        revenue = price.astype(np.int64) * discount
        if int(revenue.sum()) >= 1 << 53:
            raise ValueError("batch too large for exact float64 partial sums")
        self.sums += np.bincount(cell, weights=revenue, minlength=size) \
            .astype(np.int64).reshape(self.shape)
        self.counts += np.bincount(cell, minlength=size).reshape(self.shape)


def calendar(first, n_days):
    """{date frame: int array over the order days}: the three attributes
    of the date dimension that flight 1 selects by."""
    days = [first + datetime.timedelta(d) for d in range(n_days)]
    return {
        "d_year": np.array([d.year for d in days]),
        "d_yearmonthnum": np.array([d.year * 100 + d.month for d in days]),
        "d_weeknuminyear": np.array(
            [(d.timetuple().tm_yday - 1) // 7 + 1 for d in days]),
    }


def select(cond, values):
    """bool over ``values``: which of them a ``pql.Cond`` admits."""
    if cond.op == "><":
        return (values >= cond.value[0]) & (values <= cond.value[1])
    return COMPARE[cond.op](values, cond.value)


class Reference:
    def __init__(self, config, data):
        shape = config["shape"]
        self.cube = data["cube"]
        self.frame = shape["bsi_frame"]
        self.field = shape["sum_field"]
        first = datetime.date.fromisoformat(shape["first_order_date"])
        self.calendar = calendar(first, self.cube.shape[0])
        self.axes = {"lo_discount": (1, np.arange(N_DISCOUNT)),
                     "lo_quantity": (2, np.arange(N_QUANTITY))}

    def _masks(self, query):
        """(days, discounts, quantities) as bool arrays: the cells of
        the cube that the query's filter selects."""
        call = pql.parse(query)
        if (call.name != "Sum" or call.args.get("frame") != self.frame
                or call.args.get("field") != self.field
                or len(call.children) != 1
                or call.children[0].name != "Intersect"):
            raise ValueError(f"not a flight 1 Sum: {query}")
        masks = [np.ones(n, dtype=bool) for n in self.cube.shape]
        tree = call.children[0]
        for kid in tree.children:
            if kid.name not in ("Bitmap", "Range") or kid.children:
                raise ValueError(f"not a flight 1 filter: {query}")
        for leaf in pql.leaves(tree):
            masks[0] &= self.calendar[leaf.args["frame"]] \
                == leaf.args["rowID"]
        for frame, field, cond in pql.conditions(tree):
            if frame != self.frame:
                raise ValueError(f"condition outside {self.frame}: {query}")
            axis, values = self.axes[field]
            masks[axis] &= select(cond, values)
        return masks

    def answer(self, query, control=False):
        days, discounts, quantities = self._masks(query)
        cells = np.ix_(days, discounts, quantities)
        sums, counts = self.cube.sums[cells], self.cube.counts[cells]
        if not control:
            return {"sum": int(sums.sum()), "count": int(counts.sum())}
        acc = [np.float32(0), np.float32(0)]
        for k, per_day in enumerate((sums.sum(axis=(1, 2)),
                                     counts.sum(axis=(1, 2)))):
            for x in per_day.astype(np.float32):
                acc[k] = np.float32(acc[k] + x)
        return {"sum": int(acc[0]), "count": int(acc[1])}

    def answers(self, pqls, control=False):
        return [self.answer(q, control) for q in pqls]

    def explain(self, query, got, want):
        both = isinstance(got, dict) and {"sum", "count"} <= set(got)
        return {"query": query, "got": got, "want": want,
                "sum_difference": got["sum"] - want["sum"] if both else None,
                "count_difference": (got["count"] - want["count"]
                                     if both else None)}
