"""A time ``Range``'s view cover on the batched path: the Union over the
cover's views is brought to a bucketed width (``Executor._cover_bucket``),
so that covers of 1 to 63 views reach a small fixed set of programs, and
every bucketed answer equals the serial path's and a brute-force OR of
the days (hours) the window holds."""
import itertools
from datetime import datetime, timedelta

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, querystats
from pilosa_tpu import time_quantum as tq
from pilosa_tpu.executor import TIME_FORMAT, Executor
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions

N_COLS = 3 * SLICE_WIDTH
FIRST = datetime(2016, 11, 1)
LAST = datetime(2018, 3, 1)          # 16 months: one whole year inside
H_FIRST = datetime(2017, 2, 26)
H_LAST = datetime(2017, 3, 4)


def _fmt(t):
    return t.strftime(TIME_FORMAT)


def _range(frame, row, a, b):
    return (f'Range(frame="{frame}", rowID={row}, start="{_fmt(a)}", '
            f'end="{_fmt(b)}")')


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """Index ``i`` over three slices: frame ``ymd`` (rows 0 and 1, a few
    bits a day over 16 months), frame ``ymdh`` (row 0, a few bits an hour
    over six days across a month's end), frame ``seg`` (rows 0-2, dense
    enough to meet them, spanning every slice's whole width) and a BSI
    field. Returns (executor, {(frame, row): [(time, column), ...]},
    {segment row: set of columns}, {column: value})."""
    holder = Holder(str(tmp_path_factory.mktemp("cover") / "d")).open()
    idx = holder.create_index("i")
    ymd = idx.create_frame("ymd", FrameOptions(time_quantum="YMD"))
    ymdh = idx.create_frame("ymdh", FrameOptions(time_quantum="YMDH"))
    seg = idx.create_frame("seg", FrameOptions())
    rng = np.random.default_rng(20170101)
    pool = rng.choice(N_COLS, 600, replace=False)    # the active users
    bits = {}
    t = FIRST
    while t < LAST:
        for row in (0, 1):
            for c in rng.choice(pool, 3, replace=False).tolist():
                ymd.set_bit("standard", row, c, t=t + timedelta(hours=7))
                bits.setdefault(("ymd", row), []).append((t, c))
        t += timedelta(days=1)
    t = H_FIRST
    while t < H_LAST:
        for c in rng.choice(pool, 2, replace=False).tolist():
            ymdh.set_bit("standard", 0, c, t=t)
            bits.setdefault(("ymdh", 0), []).append((t, c))
        t += timedelta(hours=1)
    segments = {}
    for row in range(3):
        cols = set(rng.choice(pool, 400, replace=False).tolist())
        cols |= {s * SLICE_WIDTH for s in range(3)}
        cols |= {(s + 1) * SLICE_WIDTH - 1 for s in range(3)}
        for c in cols:
            seg.set_bit("standard", row, c)
        segments[row] = cols
    bsi = idx.create_frame("bsi", FrameOptions(range_enabled=True))
    bsi.create_field(Field("v", min=0, max=1000))
    values = {int(c): int(v) for c, v in zip(pool, rng.integers(0, 1001, 600))}
    for c, v in values.items():
        bsi.set_field_value(c, "v", v)
    e = Executor(holder)
    yield e, bits, segments, values
    holder.close()


def _brute(bits, key, a, b, unit):
    """Columns with a bit of ``key`` at a time unit the window holds
    whole: a unit u is in the cover iff floor(a) <= u and u + 1 <= b,
    which is what ViewsByTimeRange selects whatever views it picks."""
    lo = a.replace(minute=0) if unit == timedelta(hours=1) \
        else a.replace(hour=0, minute=0)
    return {c for t, c in bits.get(key, ()) if lo <= t and t + unit <= b}


def _both_paths(e, query):
    """(batched answer, serial answer), each with its path pinned."""
    out = []
    for path in ("batched", "serial"):
        e._force_path = path
        out.append(e.execute("i", query)[0])
    e._force_path = None
    return out


# ------------------------------------------------------------- the bucket

def test_cover_bucket_has_eight_widths_up_to_63_views():
    widths = [Executor._cover_bucket(n) for n in range(1, 64)]
    assert sorted(set(widths)) == [2, 4, 8, 16, 24, 32, 48, 64]
    assert all(w >= n for n, w in zip(range(1, 64), widths))
    assert widths == sorted(widths)
    # Past 64 the same two steps an octave: never more than a half over.
    for n in range(64, 2000):
        w = Executor._cover_bucket(n)
        assert n <= w < 1.5 * n + 1, (n, w)
    assert Executor._cover_bucket(7) == 8
    assert Executor._cover_bucket(30) == 32


def test_the_plan_of_a_cover_is_padded_with_its_own_views(events):
    e = events[0]
    from pilosa_tpu.pql.parser import parse

    call = parse(_range("ymd", 0, datetime(2017, 1, 3),
                        datetime(2017, 1, 10))).calls[0]
    leaves = []
    plan = e._batched_plan("i", call, leaves)
    views = tq.views_by_time_range("standard", datetime(2017, 1, 3),
                                   datetime(2017, 1, 10), "YMD")
    assert len(views) == 7 and len(leaves) == 8
    assert plan == ("Union", [("leaf", k) for k in range(8)])
    assert [lf[3] for lf in leaves[:7]] == views
    assert leaves[7] == leaves[0]
    assert {lf[:3] for lf in leaves} == {("row", "ymd", 0)}
    # Another week: other leaves, the same plan text, hence one program.
    leaves2 = []
    call2 = parse(_range("ymd", 1, datetime(2017, 5, 29),
                         datetime(2017, 6, 5))).calls[0]
    assert str(e._batched_plan("i", call2, leaves2)) == str(plan)
    assert leaves2 != leaves


# ------------------------------------------- answers: YMD and YMDH windows

def _ymd_windows():
    rng = np.random.default_rng(7)
    days = (LAST - FIRST).days
    out = [
        (datetime(2017, 3, 1), datetime(2017, 4, 1)),        # a whole month
        (datetime(2017, 1, 1), datetime(2018, 1, 1)),        # a whole year
        (datetime(2016, 12, 15), datetime(2018, 2, 10)),     # days-months-year
        (datetime(2017, 5, 5), datetime(2017, 5, 5)),        # empty cover
        (datetime(2017, 5, 6), datetime(2017, 5, 5)),        # end before start
        (datetime(2017, 5, 5), datetime(2017, 5, 6)),        # one day
        (datetime(2019, 1, 1), datetime(2019, 2, 1)),        # past the data
    ]
    for _ in range(10):                                      # day-aligned
        a, b = sorted(rng.integers(0, days, 2).tolist())
        out.append((FIRST + timedelta(days=a), FIRST + timedelta(days=b + 1)))
    for _ in range(8):                                       # unaligned ends
        a, b = sorted(rng.integers(0, days * 24, 2).tolist())
        out.append((FIRST + timedelta(hours=a), FIRST + timedelta(hours=b)))
    return out


@pytest.mark.parametrize("a, b", _ymd_windows(),
                         ids=lambda t: t.strftime("%Y%m%dT%H"))
def test_ymd_window_count_batched_serial_and_brute_force_agree(events, a, b):
    e, bits, segments, _ = events
    row, s = (a.day + b.day) % 2, (a.day * b.day) % 3
    want = len(_brute(bits, ("ymd", row), a, b, timedelta(days=1))
               & segments[s])
    q = (f'Count(Intersect({_range("ymd", row, a, b)}, '
         f'Bitmap(frame="seg", rowID={s})))')
    assert _both_paths(e, q) == [want, want], q
    bare = f'Count({_range("ymd", row, a, b)})'
    want = len(_brute(bits, ("ymd", row), a, b, timedelta(days=1)))
    assert _both_paths(e, bare) == [want, want], bare


def _ymdh_windows():
    rng = np.random.default_rng(11)
    hours = int((H_LAST - H_FIRST) / timedelta(hours=1))
    out = [(datetime(2017, 2, 27), datetime(2017, 3, 2)),    # whole days
           (datetime(2017, 2, 28, 23), datetime(2017, 3, 1, 1)),
           (datetime(2017, 3, 1, 5), datetime(2017, 3, 1, 5))]  # empty
    for _ in range(9):
        a, b = sorted(rng.integers(0, hours, 2).tolist())
        out.append((H_FIRST + timedelta(hours=a),
                    H_FIRST + timedelta(hours=b + 1)))
    return out


@pytest.mark.parametrize("a, b", _ymdh_windows(),
                         ids=lambda t: t.strftime("%m%dT%H"))
def test_ymdh_window_count_batched_serial_and_brute_force_agree(events, a, b):
    e, bits, segments, _ = events
    s = a.hour % 3
    want = len(_brute(bits, ("ymdh", 0), a, b, timedelta(hours=1))
               & segments[s])
    q = (f'Count(Intersect({_range("ymdh", 0, a, b)}, '
         f'Bitmap(frame="seg", rowID={s})))')
    assert _both_paths(e, q) == [want, want], q


# ----------------------------------------------------- how many programs

def _window_with_cover(n, taken):
    """A day-aligned window inside the first half of 2017 whose YMD
    cover has exactly ``n`` views."""
    first = datetime(2017, 1, 1)
    for a, length in itertools.product(range(0, 120), range(1, 160)):
        if a + length > 181 or (a, length) in taken:
            continue
        lo, hi = first + timedelta(days=a), first + timedelta(days=a + length)
        if len(tq.views_by_time_range("standard", lo, hi, "YMD")) == n:
            taken.add((a, length))
            return lo, hi
    raise AssertionError(f"no window with a cover of {n} views")


def test_covers_of_1_to_63_views_and_retention_leave_nine_programs(events):
    e, bits, segments, _ = events
    e._force_path = "batched"
    before = set(e._batched_cache)
    taken = set()
    try:
        for n in range(1, 64):
            a, b = _window_with_cover(n, taken)
            q = (f'Count(Intersect({_range("ymd", n % 2, a, b)}, '
                 f'Bitmap(frame="seg", rowID={n % 3})))')
            stats = querystats.QueryStats()
            with querystats.scope(stats):
                got = e.execute("i", q)[0]
            # What the cover asked for against what the plan reads: once
            # a walk of the tree (the planner's reordered copy of a call
            # is walked again).
            res = stats.to_dict()
            walks, rest = divmod(res["rangeCoverViews"], n)
            assert walks >= 1 and rest == 0, res
            assert res["rangeCoverOperands"] \
                == walks * Executor._cover_bucket(n), res
            want = len(_brute(bits, ("ymd", n % 2), a, b, timedelta(days=1))
                       & segments[n % 3])
            assert got == want, q
        one_range = set(e._batched_cache) - before
        assert len(one_range) <= 8, sorted(map(str, one_range))
        weeks = [(datetime(2017, 1, 1) + timedelta(days=d),
                  datetime(2017, 1, 8) + timedelta(days=d))
                 for d in (0, 3, 30, 100, 174)]
        for (a1, b1), (a2, b2) in itertools.product(weeks, weeks):
            q = (f'Count(Intersect({_range("ymd", 0, a1, b1)}, '
                 f'{_range("ymd", 1, a2, b2)}, Bitmap(frame="seg", rowID=1)))')
            want = len(
                _brute(bits, ("ymd", 0), a1, b1, timedelta(days=1))
                & _brute(bits, ("ymd", 1), a2, b2, timedelta(days=1))
                & segments[1])
            assert e.execute("i", q)[0] == want, q
    finally:
        e._force_path = None
    new = set(e._batched_cache) - before
    assert len(new) <= 9, sorted(map(str, new))
    assert len(new - one_range) == 1
    assert e.range_cover["rangeCoverOperands"] \
        > e.range_cover["rangeCoverViews"] >= 63 * 64 // 2


# --------------------------------------- the other trees that plan a Range

def test_two_ranges_sum_and_topn_src_answer_as_before(events):
    e, bits, segments, values = events
    a, b = datetime(2017, 2, 10), datetime(2017, 4, 20)
    c, d = datetime(2017, 4, 1), datetime(2017, 6, 1)
    in_ab = _brute(bits, ("ymd", 0), a, b, timedelta(days=1))
    in_cd = _brute(bits, ("ymd", 1), c, d, timedelta(days=1))
    for op, want in (("Union", in_ab | in_cd), ("Intersect", in_ab & in_cd),
                     ("Difference", in_ab - in_cd), ("Xor", in_ab ^ in_cd)):
        q = f'Count({op}({_range("ymd", 0, a, b)}, {_range("ymd", 1, c, d)}))'
        assert _both_paths(e, q) == [len(want)] * 2, q
        # The same tree as a bitmap result: the columns themselves.
        got = [bm.columns().tolist() for bm in _both_paths(e, q[6:-1])]
        assert got == [sorted(want)] * 2, q
    q = f'Sum({_range("ymd", 0, a, b)}, frame="bsi", field="v")'
    got = _both_paths(e, q)
    assert [(g.sum, g.count) for g in got] \
        == [(sum(values[c] for c in in_ab), len(in_ab))] * 2
    q = f'TopN({_range("ymd", 0, a, b)}, frame="seg", n=3)'
    want = sorted(((r, len(cols & in_ab)) for r, cols in segments.items()),
                  key=lambda p: (-p[1], p[0]))
    for got in _both_paths(e, q):
        assert [tuple(p) for p in got] == want


def test_range_cover_span_and_counters_of_a_profiled_request(events):
    from pilosa_tpu import tracing

    e = events[0]
    window = _range("ymd", 0, datetime(2017, 1, 9), datetime(2017, 2, 8))
    q = f'Count(Intersect({window}, Bitmap(frame="seg", rowID=2)))'
    stats = querystats.QueryStats()
    root = tracing.Tracer(ring_size=2).start("query", index="i")
    e._force_path = "batched"
    try:
        with root, querystats.scope(stats):
            e.execute("i", q)
    finally:
        e._force_path = None
    spans = {s["name"]: s for s in root.trace.to_dict()["spans"]}
    cover = spans["range.cover"]
    assert cover["tags"] == {"frame": "ymd", "views": 30, "operands": 32}
    # The walk that plans the tree: the planner's, under ``count.plan``;
    # ``plan.tree`` then finds the plan memoised, and walks again only a
    # tree that the planner reordered.
    assert cover["parentId"] in (spans["count.plan"]["spanId"],
                                 spans["plan.tree"]["spanId"])
    res = stats.to_dict()
    assert res["rangeCoverOperands"] * 30 == res["rangeCoverViews"] * 32 > 0
