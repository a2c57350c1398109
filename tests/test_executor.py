"""Single-node executor tests: PQL string in → asserted results out
(analog of executor_test.go:31-892)."""
import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu import errors as perr
from pilosa_tpu.executor import Executor, ExecOptions, SumCount
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_frame("general")
    e = Executor(holder)
    yield holder, idx, e
    holder.close()


def cols(bm):
    return bm.columns().tolist()


def test_set_and_bitmap(env):
    holder, idx, e = env
    res = e.execute("i", 'SetBit(frame="general", rowID=10, columnID=3)')
    assert res == [True]
    res = e.execute("i", 'SetBit(frame="general", rowID=10, columnID=3)')
    assert res == [False]  # unchanged
    e.execute("i", f'SetBit(frame="general", rowID=10, columnID={SLICE_WIDTH + 5})')
    bm = e.execute("i", 'Bitmap(frame="general", rowID=10)')[0]
    assert cols(bm) == [3, SLICE_WIDTH + 5]


def test_clear_bit(env):
    holder, idx, e = env
    e.execute("i", 'SetBit(frame="general", rowID=1, columnID=3)')
    assert e.execute("i", 'ClearBit(frame="general", rowID=1, columnID=3)') == [True]
    assert e.execute("i", 'ClearBit(frame="general", rowID=1, columnID=3)') == [False]
    assert cols(e.execute("i", 'Bitmap(frame="general", rowID=1)')[0]) == []


def test_set_ops(env):
    holder, idx, e = env
    for col in (1, 2, 3):
        e.execute("i", f'SetBit(frame="general", rowID=10, columnID={col})')
    for col in (2, 3, 4):
        e.execute("i", f'SetBit(frame="general", rowID=11, columnID={col})')
    q = 'Bitmap(frame="general", rowID=10)', 'Bitmap(frame="general", rowID=11)'
    assert cols(e.execute("i", f"Intersect({q[0]}, {q[1]})")[0]) == [2, 3]
    assert cols(e.execute("i", f"Union({q[0]}, {q[1]})")[0]) == [1, 2, 3, 4]
    assert cols(e.execute("i", f"Difference({q[0]}, {q[1]})")[0]) == [1]
    assert cols(e.execute("i", f"Xor({q[0]}, {q[1]})")[0]) == [1, 4]
    assert e.execute("i", f"Count(Intersect({q[0]}, {q[1]}))") == [2]


def test_count_cross_slice(env):
    holder, idx, e = env
    frame = idx.frame("general")
    # bits in 3 different slices
    frame.import_bits([7] * 6, [0, 1, SLICE_WIDTH, SLICE_WIDTH + 1,
                                2 * SLICE_WIDTH, 2 * SLICE_WIDTH + 9])
    assert e.execute("i", 'Count(Bitmap(frame="general", rowID=7))') == [6]


def test_topn(env):
    holder, idx, e = env
    frame = idx.frame("general")
    frame.import_bits([0] * 5 + [10] * 10 + [20] * 3,
                      list(range(5)) + list(range(10)) + list(range(3)))
    # make row 10 span another slice too
    e.execute("i", f'SetBit(frame="general", rowID=10, columnID={SLICE_WIDTH})')
    pairs = e.execute("i", 'TopN(frame="general", n=2)')[0]
    assert pairs == [(10, 11), (0, 5)]


def test_topn_with_src_and_attr_filter(env):
    holder, idx, e = env
    frame = idx.frame("general")
    frame.import_bits([1] * 4 + [2] * 2 + [3] * 5,
                      [0, 1, 2, 3, 0, 1, 0, 1, 2, 3, 4])
    e.execute("i", 'SetRowAttrs(frame="general", rowID=1, cat="x")')
    e.execute("i", 'SetRowAttrs(frame="general", rowID=3, cat="y")')
    pairs = e.execute(
        "i", 'TopN(Bitmap(frame="general", rowID=3), frame="general", n=5, '
             'field="cat", filters=["x"])')[0]
    assert pairs == [(1, 4)]  # only row 1 has cat=x; |r1 ∩ r3| = 4


def test_topn_tanimoto_batched_matches_serial(env):
    """Tanimoto TopN over multiple slices: the batched phase-2 re-query
    (fused intersect/row/src popcounts) returns exactly what the serial
    per-slice path returns (ref tanimoto semantics fragment.go:908-918)."""
    holder, idx, e = env
    frame = idx.frame("general")
    W = SLICE_WIDTH
    # src = row 3: {0..3} in slice 0, {0,1} in slice 1.
    frame.import_bits([3] * 6, [0, 1, 2, 3, W + 0, W + 1])
    # row 0 identical to src → tanimoto 100 in both slices.
    frame.import_bits([0] * 6, [0, 1, 2, 3, W + 0, W + 1])
    # row 1: half-overlap → tanimoto exactly 50 in both slices.
    frame.import_bits([1] * 3, [0, 1, W + 0])
    # row 2: disjoint from src.
    frame.import_bits([2] * 2, [4, 5])

    q50 = ('TopN(Bitmap(frame="general", rowID=3), frame="general", n=5, '
           'tanimotoThreshold=50)')
    q40 = ('TopN(Bitmap(frame="general", rowID=3), frame="general", n=5, '
           'tanimotoThreshold=40)')
    for q, expect in ((q50, [(0, 6), (3, 6)]),
                      (q40, [(0, 6), (3, 6), (1, 3)])):
        batched = e.execute("i", q)[0]
        orig = e._batched_topn_ids
        e._batched_topn_ids = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_topn_ids = orig
        assert batched == serial == expect, q


def test_setbit_burst_fast_path(env):
    """All-SetBit query strings take the regex burst path: identical
    changed flags and state to per-call serial execution, including
    within-batch duplicates, inverse views, and cross-slice writes."""
    import numpy as np

    from pilosa_tpu.storage.index import FrameOptions

    holder, idx, e = env
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 20, 400).tolist()
    cols = rng.integers(0, 2 * SLICE_WIDTH, 400).tolist()
    pairs = list(zip(rows, cols)) + [(rows[0], cols[0])] * 3  # dups

    engaged = []
    orig = e._execute_setbit_burst
    e._execute_setbit_burst = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    q = "\n".join(f'SetBit(frame="inv", rowID={r}, columnID={c})'
                  for r, c in pairs)
    burst_res = e.execute("i", q)
    assert engaged and engaged[0] is not None, "burst path did not engage"
    e._execute_setbit_burst = orig

    # Serial reference on a fresh holder.
    from pilosa_tpu.storage.holder import Holder as _H
    import tempfile
    with tempfile.TemporaryDirectory() as d2:
        h2 = _H(d2).open()
        i2 = h2.create_index("i")
        i2.create_frame("inv", FrameOptions(inverse_enabled=True))
        e2 = Executor(h2)
        serial_res = [
            e2.execute("i", f'SetBit(frame="inv", rowID={r}, columnID={c})')[0]
            for r, c in pairs]
        assert burst_res == serial_res
        for probe in ('Count(Bitmap(frame="inv", rowID=7))',
                      'Count(Bitmap(frame="inv", columnID=%d))' % cols[0]):
            assert e.execute("i", probe) == e2.execute("i", probe), probe
        h2.close()

    # Mixed / malformed strings fall back to the full parser.
    res = e.execute("i", 'SetBit(frame="inv", rowID=1, columnID=1)\n'
                         'Count(Bitmap(frame="inv", rowID=1))')
    assert res[1] == e.execute("i", 'Count(Bitmap(frame="inv", rowID=1))')[0]
    with pytest.raises(Exception):
        e.execute("i", 'SetBit(frame="inv", rowID=1)\n'
                       'SetBit(frame="inv", rowID=2, columnID=2)')


def test_burst_recognizes_any_arg_order(env):
    """Clients disagree on arg order (ours emits frame last; str(Call)
    sorts alphabetically): every ordering takes the burst path with
    identical results."""
    holder, idx, e = env
    engaged = []
    orig = e._execute_setbit_burst
    e._execute_setbit_burst = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    variants = [
        'SetBit(frame="general", rowID={r}, columnID={c})',
        'SetBit(rowID={r}, columnID={c}, frame="general")',
        'SetBit(columnID={c}, frame="general", rowID={r})',
    ]
    for i, tmpl in enumerate(variants):
        q = "\n".join(tmpl.format(r=20 + i, c=c) for c in (1, 2, 3))
        res = e.execute("i", q)
        assert engaged and engaged[-1] is not None, tmpl
        assert res == [True, True, True], tmpl
    e._execute_setbit_burst = orig
    for i in range(3):
        assert e.execute(
            "i", f'Count(Bitmap(frame="general", rowID={20 + i}))') == [3]
    # negative id anywhere → serial path raises the conversion error
    # (deliberate deviation from the reference's silent uint64 wrap)
    with pytest.raises(ValueError, match="could not convert"):
        e.execute("i", 'SetBit(rowID=-1, columnID=5, frame="general")\n'
                       'SetBit(rowID=1, columnID=5, frame="general")')


def test_clearbit_burst_fast_path(env):
    """All-ClearBit strings take the burst path: same changed flags and
    state as serial, clears never allocate rows/fragments, and the
    inverse view clears too."""
    import numpy as np

    from pilosa_tpu.storage.index import FrameOptions

    holder, idx, e = env
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    rng = np.random.default_rng(13)
    rows = rng.integers(0, 12, 300).tolist()
    cols = rng.integers(0, 2 * SLICE_WIDTH, 300).tolist()
    setq = "\n".join(f'SetBit(frame="inv", rowID={r}, columnID={c})'
                     for r, c in zip(rows, cols))
    e.execute("i", setq)
    # Clear a mix of set and never-set bits, including duplicates.
    pairs = list(zip(rows[:150], cols[:150]))
    pairs += [(99, 5), (0, 2 * SLICE_WIDTH - 1)] + pairs[:3]
    clearq = "\n".join(f'ClearBit(frame="inv", rowID={r}, columnID={c})'
                       for r, c in pairs)
    engaged = []
    orig = e._execute_setbit_burst
    e._execute_setbit_burst = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    burst_res = e.execute("i", clearq)
    assert engaged and engaged[0] is not None, "burst did not engage"
    e._execute_setbit_burst = orig

    import tempfile
    from pilosa_tpu.storage.holder import Holder as _H
    with tempfile.TemporaryDirectory() as d2:
        h2 = _H(d2).open()
        i2 = h2.create_index("i")
        i2.create_frame("inv", FrameOptions(inverse_enabled=True))
        e2 = Executor(h2)
        e2.execute("i", setq)
        serial_res = [
            e2.execute("i",
                       f'ClearBit(frame="inv", rowID={r}, columnID={c})')[0]
            for r, c in pairs]
        assert burst_res == serial_res
        for r in (0, 3, 7, 99):
            probe = f'Count(Bitmap(frame="inv", rowID={r}))'
            assert e.execute("i", probe) == e2.execute("i", probe), r
        probe = f'Count(Bitmap(frame="inv", columnID={cols[0]}))'
        assert e.execute("i", probe) == e2.execute("i", probe)
        h2.close()


def test_setfield_burst_fast_path(env):
    """All-SetFieldValue strings take the burst path: same nil results
    and final BSI state as serial execution; duplicates, out-of-range
    values, and unknown fields fall back to the serial path (which
    raises/apply-orders exactly as the reference does)."""
    import numpy as np

    holder, idx, e = env
    idx.create_frame("g", FrameOptions(
        range_enabled=True, fields=[Field("v", min=-10, max=1000)]))
    rng = np.random.default_rng(3)
    cols = rng.choice(2 * SLICE_WIDTH, 300, replace=False).tolist()
    vals = rng.integers(-10, 1001, 300).tolist()
    q = "\n".join(f'SetFieldValue(frame="g", columnID={c}, v={v})'
                  for c, v in zip(cols, vals))
    engaged = []
    orig = e._execute_setfield_burst
    e._execute_setfield_burst = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    res = e.execute("i", q)
    assert engaged and engaged[0] is not None, "burst did not engage"
    assert res == [None] * len(cols)  # ref: SetFieldValue yields nil
    e._execute_setfield_burst = orig

    import tempfile
    from pilosa_tpu.storage.holder import Holder as _H
    with tempfile.TemporaryDirectory() as d2:
        h2 = _H(d2).open()
        i2 = h2.create_index("i")
        i2.create_frame("g", FrameOptions(
            range_enabled=True, fields=[Field("v", min=-10, max=1000)]))
        e2 = Executor(h2)
        for c, v in zip(cols, vals):
            e2.execute("i", f'SetFieldValue(frame="g", columnID={c}, v={v})')
        for probe in ('Sum(frame="g", field="v")',
                      'Min(frame="g", field="v")',
                      'Max(frame="g", field="v")'):
            assert e.execute("i", probe) == e2.execute("i", probe), probe
        h2.close()

    # Duplicate columns fall back to serial ordering (last wins).
    e.execute("i", 'SetFieldValue(frame="g", columnID=9, v=4)\n'
                   'SetFieldValue(frame="g", columnID=9, v=7)')
    assert idx.frame("g").field_value(9, "v") == (7, True)
    # Out-of-range falls back to the serial raise.
    with pytest.raises(perr.PilosaError):
        e.execute("i", 'SetFieldValue(frame="g", columnID=1, v=2000)\n'
                       'SetFieldValue(frame="g", columnID=2, v=1)')


def test_topn_duplicate_ids(env):
    """Explicit duplicate ids yield one pair each on both paths (the
    serial walk checks membership in set(row_ids))."""
    holder, idx, e = env
    frame = idx.frame("general")
    frame.import_bits([5] * 3 + [6] * 1, [0, 1, SLICE_WIDTH + 2, 4])
    q = 'TopN(frame="general", ids=[5, 5, 6])'
    batched = e.execute("i", q)[0]
    orig = e._batched_topn_ids
    e._batched_topn_ids = lambda *a, **k: None
    serial = e.execute("i", q)[0]
    e._batched_topn_ids = orig
    assert batched == serial == [(5, 3), (6, 1)]


def test_topn_src_phase1_batched_matches_serial(env):
    """TopN with a src tree: batched phase 1 (fused candidate counts
    over the cache-entry union) must reproduce the serial per-fragment
    walk exactly, including per-slice top-n truncation before the
    cross-slice merge."""
    holder, idx, e = env
    frame = idx.frame("general")
    W = SLICE_WIDTH
    # src row 9: cols 0-3 in slice 0, cols 0-3 in slice 1.
    frame.import_bits([9] * 8, [0, 1, 2, 3, W + 0, W + 1, W + 2, W + 3])
    # slice 0 overlaps: row0=3, row1=2, row2=1 → top-2 truncation drops row2.
    frame.import_bits([0] * 3, [0, 1, 2])
    frame.import_bits([1] * 2, [0, 1])
    frame.import_bits([2] * 1, [0])
    # slice 1 overlaps: row2=3, row1=1, row0=0 → top-2 keeps rows 2,1.
    frame.import_bits([2] * 3, [W + 0, W + 1, W + 2])
    frame.import_bits([1] * 1, [W + 0])

    q = ('TopN(Bitmap(frame="general", rowID=9), frame="general", n=2)')
    engaged = []
    orig_p1 = e._batched_topn_phase1
    e._batched_topn_phase1 = lambda *a, **k: (
        engaged.append(orig_p1(*a, **k)), engaged[-1])[1]
    batched = e.execute("i", q)[0]
    assert engaged and engaged[0] is not None, \
        "batched phase 1 did not produce the result"
    e._batched_topn_phase1 = lambda *a, **k: None
    orig_p2 = e._batched_topn_ids
    e._batched_topn_ids = lambda *a, **k: None
    serial = e.execute("i", q)[0]
    e._batched_topn_phase1 = orig_p1
    e._batched_topn_ids = orig_p2
    # Per-slice top-2 keeps {9,0} in slice 0 and {9,2} in slice 1 (row 9
    # is the src itself: |9∩9| = 4 per slice); the phase-2 exact
    # re-query then restores row2's truncated slice-0 count (1+3 = 4)
    # and trims to n=2.
    assert batched == serial == [(9, 8), (2, 4)]


def test_sum_and_range(env):
    holder, idx, e = env
    idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=100)]))
    e.execute("i", 'SetFieldValue(frame="f", columnID=1, v=10)')
    e.execute("i", 'SetFieldValue(frame="f", columnID=2, v=20)')
    e.execute("i", 'SetFieldValue(frame="f", columnID=3, v=70)')
    assert e.execute("i", 'Sum(frame="f", field="v")') == [SumCount(100, 3)]

    # filtered sum
    idx.create_frame("g")
    e.execute("i", 'SetBit(frame="g", rowID=1, columnID=1)')
    e.execute("i", 'SetBit(frame="g", rowID=1, columnID=3)')
    assert e.execute(
        "i", 'Sum(Bitmap(frame="g", rowID=1), frame="f", field="v")'
    ) == [SumCount(80, 2)]

    assert cols(e.execute("i", 'Range(frame="f", v > 15)')[0]) == [2, 3]
    assert cols(e.execute("i", 'Range(frame="f", v == 70)')[0]) == [3]
    assert cols(e.execute("i", 'Range(frame="f", v >< [10, 20])')[0]) == [1, 2]
    assert cols(e.execute("i", 'Range(frame="f", v != null)')[0]) == [1, 2, 3]
    # fully-encompassing range returns all not-null
    assert cols(e.execute("i", 'Range(frame="f", v < 1000)')[0]) == [1, 2, 3]
    assert cols(e.execute("i", 'Range(frame="f", v > 1000)')[0]) == []


def test_min_max(env):
    holder, idx, e = env
    idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=-10, max=100)]))
    for col, val in [(1, -10), (2, 50), (3, 100), (4, 100)]:
        e.execute("i", f'SetFieldValue(frame="f", columnID={col}, v={val})')
    assert e.execute("i", 'Max(frame="f", field="v")') == [SumCount(100, 2)]
    assert e.execute("i", 'Min(frame="f", field="v")') == [SumCount(-10, 1)]


def test_min_max_batched_matches_serial(env):
    """Cross-slice Min/Max: the batched global bit-descent equals the
    serial per-slice descents + host reduce, with and without a filter
    bitmap, including when one slice's local extremum loses globally."""
    holder, idx, e = env
    idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=-10, max=1000)]))
    idx.create_frame("g")
    W = SLICE_WIDTH
    # slice 0: values {-10, 50}; slice 1: {700, 700}; slice 2: {3}.
    for col, val in [(1, -10), (2, 50),
                     (W + 1, 700), (W + 2, 700),
                     (2 * W + 5, 3)]:
        e.execute("i", f'SetFieldValue(frame="f", columnID={col}, v={val})')
    # filter row covers cols {2, W+1, 2W+5} → filtered max 700 (count 1),
    # filtered min 3.
    for col in (2, W + 1, 2 * W + 5):
        e.execute("i", f'SetBit(frame="g", rowID=1, columnID={col})')

    queries = [
        ('Max(frame="f", field="v")', SumCount(700, 2)),
        ('Min(frame="f", field="v")', SumCount(-10, 1)),
        ('Max(Bitmap(frame="g", rowID=1), frame="f", field="v")',
         SumCount(700, 1)),
        ('Min(Bitmap(frame="g", rowID=1), frame="f", field="v")',
         SumCount(3, 1)),
    ]
    engaged = []
    orig = e._batched_min_max
    e._batched_min_max = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    for q, expect in queries:
        batched = e.execute("i", q)[0]
        e._batched_min_max = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_min_max = lambda *a, **k: (
            engaged.append(orig(*a, **k)), engaged[-1])[1]
        assert batched == serial == expect, q
    assert engaged and all(r is not None for r in engaged), \
        "batched min/max did not produce results"

    # Empty filter: the batched kernel reports BATCH_EMPTY (no serial
    # recompute) and the query answers the serial empty result.
    from pilosa_tpu.executor import BATCH_EMPTY
    e._batched_min_max = lambda *a, **k: (
        engaged.append(orig(*a, **k)), engaged[-1])[1]
    empty_q = 'Max(Bitmap(frame="g", rowID=99), frame="f", field="v")'
    assert e.execute("i", empty_q)[0] == SumCount(0, 0)
    assert engaged[-1] is BATCH_EMPTY


def test_time_range(env):
    holder, idx, e = env
    idx.create_frame("t", FrameOptions(time_quantum="YMDH"))
    e.execute("i", 'SetBit(frame="t", rowID=1, columnID=9, '
                   'timestamp="2017-03-05T10:00")')
    e.execute("i", 'SetBit(frame="t", rowID=1, columnID=10, '
                   'timestamp="2018-01-01T00:00")')
    bm = e.execute("i", 'Range(frame="t", rowID=1, start="2017-01-01T00:00", '
                        'end="2017-12-31T23:00")')[0]
    assert cols(bm) == [9]
    bm = e.execute("i", 'Range(frame="t", rowID=1, start="2016-01-01T00:00", '
                        'end="2019-01-01T00:00")')[0]
    assert cols(bm) == [9, 10]


def test_inverse_bitmap(env):
    holder, idx, e = env
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    e.execute("i", 'SetBit(frame="inv", rowID=5, columnID=100)')
    e.execute("i", 'SetBit(frame="inv", rowID=6, columnID=100)')
    bm = e.execute("i", 'Bitmap(frame="inv", columnID=100)')[0]
    assert cols(bm) == [5, 6]
    with pytest.raises(ValueError, match="inverse storage"):
        e.execute("i", 'Bitmap(frame="general", columnID=1)')


def test_inverse_batched_matches_serial(env):
    """Inverse-orientation (columnID) leaves batch through inverse-view
    stacks; mixed-orientation trees resolve each leaf by its own args,
    exactly like executeBitmapSlice."""
    holder, idx, e = env
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    W = SLICE_WIDTH
    # Rows above SLICE_WIDTH give the inverse view two slices.
    for row, col in [(5, 100), (6, 100), (W + 7, 100), (5, 200), (6, 300)]:
        e.execute("i", f'SetBit(frame="inv", rowID={row}, columnID={col})')

    # Note: only top-level Bitmap/TopN switch to the inverse slice
    # list (ref: SupportsInverse ast.go:181-183); Count always maps
    # the STANDARD slice range (here just slice 0), so the inverse
    # row W+7 — which lives in inverse slice 1 — is not counted.
    # Top-level Bitmap over the inverse list sees all three.
    assert cols(e.execute("i", 'Bitmap(frame="inv", columnID=100)')[0]) \
        == [5, 6, W + 7]
    queries = [
        ('Count(Bitmap(frame="inv", columnID=100))', 2),
        ('Count(Intersect(Bitmap(frame="inv", columnID=100), '
         'Bitmap(frame="inv", columnID=200)))', 1),
    ]
    for q, expect in queries:
        engaged = []
        orig = e._batched_count
        e._batched_count = lambda index, child, ns: (
            engaged.append(orig(index, child, ns)), engaged[-1])[1]
        batched = e.execute("i", q)[0]
        e._batched_count = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_count = orig
        assert engaged and engaged[0] is not None, q
        assert batched == serial == expect, q

    # Mixed orientation: standard row-5 bitmap ∪ inverse col-300 bitmap.
    mixed = ('Union(Bitmap(frame="inv", rowID=5), '
             'Bitmap(frame="inv", columnID=300))')
    e._force_path = "batched"  # pin the batched arm (model is adaptive)
    engaged = []
    orig_bm = e._batched_bitmap
    e._batched_bitmap = lambda *a, **k: (
        engaged.append(orig_bm(*a, **k)), engaged[-1])[1]
    batched = cols(e.execute("i", mixed)[0])
    assert engaged and engaged[0] is not None, \
        "batched mixed-orientation materialization did not engage"
    e._batched_bitmap = lambda *a, **k: None
    serial = cols(e.execute("i", mixed)[0])
    e._batched_bitmap = orig_bm
    assert batched == serial == [6, 100, 200]


def test_attrs_attach(env):
    holder, idx, e = env
    e.execute("i", 'SetBit(frame="general", rowID=1, columnID=2)')
    e.execute("i", 'SetRowAttrs(frame="general", rowID=1, name="foo", n=7)')
    bm = e.execute("i", 'Bitmap(frame="general", rowID=1)')[0]
    assert bm.attrs == {"name": "foo", "n": 7}
    e.execute("i", 'SetColumnAttrs(columnID=2, tag="bar")')
    assert idx.column_attr_store.attrs(2) == {"tag": "bar"}


def test_errors(env):
    holder, idx, e = env
    with pytest.raises(perr.ErrIndexNotFound):
        e.execute("nope", 'Bitmap(frame="general", rowID=1)')
    with pytest.raises(perr.ErrFrameNotFound):
        e.execute("i", 'Bitmap(frame="nope", rowID=1)')
    with pytest.raises(ValueError, match="must specify either"):
        e.execute("i", 'Bitmap(frame="general")')
    with pytest.raises(ValueError, match="cannot specify both"):
        e.execute("i", 'Bitmap(frame="general", rowID=1, columnID=2)')
    with pytest.raises(perr.ErrTooManyWrites):
        Executor(holder, max_writes_per_request=1).execute(
            "i", 'SetBit(frame="general", rowID=1, columnID=1) '
                 'SetBit(frame="general", rowID=1, columnID=2)')


def test_exclude_options(env):
    holder, idx, e = env
    e.execute("i", 'SetBit(frame="general", rowID=1, columnID=2)')
    e.execute("i", 'SetRowAttrs(frame="general", rowID=1, a="b")')
    bm = e.execute("i", 'Bitmap(frame="general", rowID=1)',
                   opt=ExecOptions(exclude_attrs=True))[0]
    assert bm.attrs == {}
    bm = e.execute("i", 'Bitmap(frame="general", rowID=1)',
                   opt=ExecOptions(exclude_bits=True))[0]
    assert bm.segments == {}


def test_bulk_set_row_attrs(env):
    """All-SetRowAttrs queries take the grouped bulk path
    (ref: hasOnlySetRowAttrs executor.go:117-120,
    executeBulkSetRowAttrs :1222-1308)."""
    holder, idx, e = env
    idx.create_frame("other")
    res = e.execute("i", '''
        SetRowAttrs(frame="general", rowID=1, cat="x", n=7)
        SetRowAttrs(frame="general", rowID=2, cat="y")
        SetRowAttrs(frame="general", rowID=1, extra=true)
        SetRowAttrs(frame="other", rowID=1, cat="z")
    ''')
    assert res == [None] * 4
    gen = idx.frame("general").row_attr_store
    assert gen.attrs(1) == {"cat": "x", "n": 7, "extra": True}
    assert gen.attrs(2) == {"cat": "y"}
    assert idx.frame("other").row_attr_store.attrs(1) == {"cat": "z"}
    # mixed queries do NOT take the bulk path and still work
    res = e.execute("i", '''
        SetRowAttrs(frame="general", rowID=5, a="b")
        SetBit(frame="general", rowID=5, columnID=1)
    ''')
    assert res == [None, True]
    assert gen.attrs(5) == {"a": "b"}


def test_topn_inverse(env):
    """TopN(inverse=true) ranks columns of the inverse view over the
    inverse slice list (ref: executeTopNSlice executor.go:433,
    Call.IsInverse ast.go:190-193)."""
    holder, idx, e = env
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    # column 7 appears in 3 rows, column 8 in 1
    for row, col in [(0, 7), (1, 7), (2, 7), (0, 8)]:
        e.execute("i", f'SetBit(frame="inv", rowID={row}, columnID={col})')
    pairs = e.execute("i", 'TopN(frame="inv", n=2, inverse=true)')[0]
    assert pairs == [(7, 3), (8, 1)]


def test_bitmap_defer_stack_lazy():
    """A batched materialization result stays one device stack until a
    caller touches segment words; count() never fetches."""
    import jax.numpy as jnp

    from pilosa_tpu.bitmap import Bitmap

    stack = jnp.asarray(np.array(
        [[1, 0], [0, 0], [3, 4]], dtype=np.uint32))
    counts = np.array([1, 0, 3])
    bm = Bitmap()
    bm.defer_stack(stack, [0, 1, 5], counts)
    assert bm._stack is not None
    assert bm.count() == 4          # from counts, no fetch
    assert bm._stack is not None    # still deferred
    segs = bm.segments              # first touch materializes
    assert bm._stack is None
    assert sorted(segs) == [0, 5]   # zero-count slice dropped
    # A narrower-than-slice (column-windowed) stack rebases to full
    # slice width at materialization so segment algebra stays aligned.
    from pilosa_tpu import WORDS_PER_SLICE

    seg5 = np.asarray(segs[5])
    assert seg5.shape == (WORDS_PER_SLICE,)
    np.testing.assert_array_equal(seg5[:2], [3, 4])
    assert not seg5[2:].any()

    # word_base places the windowed words at the window's offset.
    bmw = Bitmap()
    bmw.defer_stack(stack, [0, 1, 5], counts, word_base=128)
    segw = np.asarray(bmw.segments[5])
    np.testing.assert_array_equal(segw[128:130], [3, 4])
    assert not segw[:128].any() and not segw[130:].any()

    # Empty target adopts a deferred stack without fetching it.
    bm2 = Bitmap()
    bm2.defer_stack(stack, [0, 1, 5], counts)
    target = Bitmap()
    target.merge(bm2)
    assert target.count() == 4

    # segments assignment (exclude_bits strip) clears the deferral.
    bm3 = Bitmap()
    bm3.defer_stack(stack, [0, 1, 5], counts)
    bm3.segments = {}
    assert bm3.count() == 0


def test_adaptive_path_selection():
    """The cost model converges on whichever path is faster and keeps
    the other as a rarely-probed fallback."""
    import threading
    import time as _t

    from pilosa_tpu.pql import parse

    e = Executor.__new__(Executor)  # _local_exec never touches the holder
    e._path_stats = {}
    e._path_mu = threading.Lock()
    e._force_path = None
    call = parse('Count(Bitmap(frame="f", rowID=1))').calls[0]
    used = []

    def batch_fn(ns):
        used.append("b")
        _t.sleep(0.02)
        return len(ns)

    def map_fn(s):
        _t.sleep(0.0005)
        return 1

    def reduce_fn(prev, v):
        return (prev or 0) + v

    for _ in range(30):
        out = e._local_exec(call, list(range(8)), map_fn, reduce_fn,
                            batch_fn)
        assert out == 8
    # Serial (8 * 0.5ms) beats batched (20ms): the tail must be serial.
    assert used.count("b") < 12

    # Opposite economics: batched must win. (Same call text maps to
    # the same shape key — the model keys on structure, not literals —
    # so reset the stats to model a fresh shape.)
    e._path_stats = {}
    call2 = parse('Count(Bitmap(frame="g", rowID=1))').calls[0]
    used2 = []

    def batch_fn2(ns):
        used2.append("b")
        return len(ns)

    def map_fn2(s):
        _t.sleep(0.01)
        return 1

    for _ in range(30):
        out = e._local_exec(call2, list(range(8)), map_fn2, reduce_fn,
                            batch_fn2)
        assert out == 8
    assert used2.count("b") > 18


def test_serial_probe_cost_bounded():
    """Exploration-phase serial probes abort once they've provably
    lost (5x the batched minimum): on a backend where each per-slice
    dispatch is expensive, the model must converge without ever paying
    a full serial pass (5 unbounded probes x 64 slices x one dispatch
    each, per query shape)."""
    import threading
    import time as _t

    from pilosa_tpu.pql import parse

    e = Executor.__new__(Executor)
    e._path_stats = {}
    e._path_mu = threading.Lock()
    e._force_path = None
    call = parse('Count(Bitmap(frame="h", rowID=1))').calls[0]
    n_slices = 64
    map_calls = [0]

    def batch_fn(ns):
        _t.sleep(0.001)
        return len(ns)

    def map_fn(s):
        map_calls[0] += 1
        _t.sleep(0.01)  # full serial pass would be 640 ms
        return 1

    def reduce_fn(prev, v):
        return (prev or 0) + v

    t0 = _t.perf_counter()
    for _ in range(20):
        out = e._local_exec(call, list(range(n_slices)), map_fn,
                            reduce_fn, batch_fn)
        assert out == n_slices  # aborted probes still answer correctly
    elapsed = _t.perf_counter() - t0

    # Unbounded exploration would pay ~5 full serial probes = ~3.2 s.
    # Bounded: each probe aborts after max(5 x 1 ms, 50 ms) ≈ 6 slices.
    assert elapsed < 1.6, elapsed
    assert map_calls[0] < 120, map_calls[0]  # vs 320 for 5 full passes

    (st,) = e._path_stats.values()
    # Aborted probes still recorded a (pessimistic) serial sample, so
    # the steady-state chooser has both minima to compare.
    assert st.get("s") is not None and st.get("b") is not None
    assert st["s"] > st["b"]


def test_epoch_scoped_per_index(tmp_path):
    """A write to one index must not invalidate the epoch-validated
    prelude memos of ANOTHER index (scoped mutation epochs) — while an
    index-blind bump (attr stores) still invalidates everything."""
    from pilosa_tpu.storage import fragment as frag_mod
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    for name in ("a", "b"):
        idx = holder.create_index(name)
        idx.create_frame("f")
        idx.frame("f").import_bits([1, 2], [3, 3])
    e = Executor(holder)
    e._force_path = "batched"
    q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))')
    assert e.execute("a", q)[0] == 1
    with e._cache_mu:
        (pkey,) = [k for k in e._prelude_cache if k[1] == "a"]
    assert e._prelude_memo_get(pkey) is not None

    # Write to the OTHER index: index a's memo survives.
    holder.index("b").frame("f").import_bits([1], [9])
    assert e._prelude_memo_get(pkey) is not None

    # Index-blind bump (attr-store path): every memo goes stale.
    frag_mod._bump_epoch()
    assert e._prelude_memo_get(pkey) is None

    # Rebuild, then a write to index a itself invalidates again.
    assert e.execute("a", q)[0] == 1
    assert e._prelude_memo_get(pkey) is not None
    holder.index("a").frame("f").import_bits([2], [11])
    assert e._prelude_memo_get(pkey) is None
    holder.close()


def test_topn_whole_result_memo(tmp_path):
    """Repeated identical src-less TopN replays from the
    epoch-validated result memo; any write to the index invalidates."""
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    idx.create_frame("f")
    idx.frame("f").import_bits([1] * 5 + [2] * 3, list(range(5)) * 1
                               + list(range(3)))
    e = Executor(holder)
    q = 'TopN(frame="f", n=5)'
    first = e.execute("i", q)[0]
    assert first == [(1, 5), (2, 3)]
    # Memoized: the slice executor must not run again.
    calls = []
    orig = e._execute_topn_slices
    e._execute_topn_slices = lambda *a, **k: (calls.append(1),
                                              orig(*a, **k))[1]
    assert e.execute("i", q)[0] == first
    assert not calls, "memo miss: slice walk re-ran"
    # A write invalidates; the next run recomputes and reflects it.
    e._execute_topn_slices = orig
    idx.frame("f").import_bits([2] * 3, [10, 11, 12])
    assert e.execute("i", q)[0] == [(2, 6), (1, 5)]
    holder.close()


def test_scalar_result_memos(tmp_path):
    """Warm repeated Count/Sum/Min/Max replay from the epoch-validated
    result memo; writes to the index invalidate immediately."""
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    idx.create_frame("f")
    bsi = idx.create_frame("g", FrameOptions(range_enabled=True))
    bsi.create_field(Field("v", min=0, max=1000))
    idx.frame("f").import_bits([1, 1, 2], [1, 2, 1])
    bsi.import_value("v", [1, 2, 3], [10, 20, 30])
    e = Executor(holder)

    queries = {
        'Count(Bitmap(frame="f", rowID=1))': 2,
        'Sum(frame="g", field="v")': SumCount(60, 3),
        'Min(frame="g", field="v")': SumCount(10, 1),
        'Max(frame="g", field="v")': SumCount(30, 1),
    }
    for q, want in queries.items():
        assert e.execute("i", q)[0] == want, q
    # All four replay without re-running map_reduce.
    calls = []
    orig = e._map_reduce
    e._map_reduce = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    for q, want in queries.items():
        assert e.execute("i", q)[0] == want, q
    assert not calls, "memo miss re-ran map_reduce"
    e._map_reduce = orig

    # Writes invalidate: bit changes Count, value changes Sum/Min/Max.
    idx.frame("f").import_bits([1], [9])
    bsi.import_value("v", [4], [5])
    assert e.execute("i", 'Count(Bitmap(frame="f", rowID=1))')[0] == 3
    assert e.execute("i", 'Sum(frame="g", field="v")')[0] == SumCount(65, 4)
    assert e.execute("i", 'Min(frame="g", field="v")')[0] == SumCount(5, 1)
    holder.close()


def test_topn_memo_uint64_row_ids(tmp_path):
    """Row ids use the full uint64 space; the TopN result memo must
    round-trip ids >= 2**63 (int64 encoding would overflow)."""
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    idx.create_frame("f")
    big = 2 ** 63 + 7
    idx.frame("f").import_bits([big, big, 1], [0, 1, 0])
    e = Executor(holder)
    q = 'TopN(frame="f", n=3)'
    want = [(big, 2), (1, 1)]
    assert e.execute("i", q)[0] == want
    assert e.execute("i", q)[0] == want  # memo replay, same ids
    holder.close()


def test_result_memo_disabled_on_clusters():
    """The whole-result memos validate against the LOCAL mutation
    epoch, which writes applied on peers never bump — so on a
    multi-node cluster they must not engage at all: a query through
    node A reflects a write that went through node B immediately."""
    import json
    import urllib.request

    from pilosa_tpu.testing import ServerCluster

    def post(host, path, body):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read() or b"{}")

    with ServerCluster(2, replica_n=2) as servers:
        a, b = servers[0].host, servers[1].host
        post(a, "/index/i", "{}")
        post(a, "/index/i/frame/f", "{}")
        post(a, "/index/i/query", 'SetBit(frame="f", rowID=1, columnID=2)')
        q = 'Count(Bitmap(frame="f", rowID=1))'
        # Warm the query on A (would memoize if wrongly enabled), then
        # write THROUGH B, then re-read through A.
        assert post(a, "/index/i/query", q)["results"] == [1]
        assert post(a, "/index/i/query", q)["results"] == [1]
        post(b, "/index/i/query", 'SetBit(frame="f", rowID=1, columnID=9)')
        assert post(a, "/index/i/query", q)["results"] == [2]
        # TopN through A reflects it too.
        tn = post(a, "/index/i/query", 'TopN(frame="f", n=2)')
        assert tn["results"][0][0]["count"] == 2


def test_result_memo_budget_evicts_with_key_cost(tmp_path):
    """Entries charge key footprint + value bytes; exceeding the budget
    evicts FIFO and the byte ledger stays consistent."""
    import numpy as np

    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    e = Executor(holder)
    e.RESULT_MEMO_BYTES = 4000
    e.RESULT_MEMO_ENTRY_MAX = 4000
    big_slices = tuple(range(40))  # sizable key cost per entry
    for i in range(20):
        key = ("count_res", "i", f"Count(q{i})", big_slices)
        e._topn_counts_memoize(key, np.asarray([i], dtype=np.int64), 0)
    with e._cache_mu:
        total = sum(v[2] for v in e._result_memo.values())
        assert total == e._result_memo_bytes
        assert total <= e.RESULT_MEMO_BYTES
        assert 0 < len(e._result_memo) < 20  # evictions happened
    holder.close()


def test_path_model_persists_across_restart(tmp_path):
    """The batched-vs-serial cost model warm-starts from the previous
    process's learned minima: a restarted server must skip the
    ~12-query exploration phase (deliberately-losing probes that cost
    seconds on big indexes) for shapes it served before — while live
    measurements still override a stale seed (minimum-takes-all with
    inflated seeding + aging)."""
    import json as _json
    import os

    from pilosa_tpu.server.server import Server

    d = str(tmp_path / "data")
    server = Server(d, bind="127.0.0.1:0")
    server.open()
    try:
        idx = server.holder.create_index("i")
        idx.create_frame("f")
        idx.frame("f").import_bits([1, 2], [5, 9])
        from pilosa_tpu.pql import parse

        for k in range(16):  # distinct rowIDs: one SHAPE, but each
            # query misses the whole-result memo and actually executes
            server.executor.execute("i", parse(
                f'Count(Bitmap(frame="f", rowID={k}))'))
        snap = server.executor.save_path_model()
        assert snap["entries"], "model learned nothing"
    finally:
        server.close()
    assert os.path.exists(os.path.join(d, ".path_model.json"))
    with open(os.path.join(d, ".path_model.json")) as f:
        on_disk = _json.load(f)
    assert on_disk["v"] == 1 and on_disk["entries"]

    server = Server(d, bind="127.0.0.1:0")
    server.open()
    try:
        from pilosa_tpu.pql import parse

        server.executor.execute("i", parse(
            'Count(Bitmap(frame="f", rowID=101))'))
        # The (shape, bucket) stat must exist pre-warmed: n past the
        # exploration horizon after ONE query, with seeded minima.
        stats = server.executor._path_stats
        (key,) = [k for k in stats if k[0][0] == "Count"]
        st = stats[key]
        assert st["n"] >= server.executor.PATH_SEED_N + 1, st
        assert "b" in st or "s" in st, st
        # A live sample must be able to beat the inflated seed.
        # Live samples must RECORD into the seeded entry (a regression
        # that stops recording would park every seeded shape on its
        # seed forever). Deterministic wiring check — comparing
        # before/after minima is timing-jitter-flaky because the first
        # query's sample may already be the all-time minimum.
        recorded = []
        orig_record = server.executor._record_path

        def spy(st_, arm, elapsed, probe=False):
            recorded.append((id(st_), arm))
            return orig_record(st_, arm, elapsed, probe)

        server.executor._record_path = spy
        try:
            for k in range(8):
                server.executor.execute("i", parse(
                    f'Count(Bitmap(frame="f", rowID={200 + k}))'))
        finally:
            server.executor._record_path = orig_record
        assert any(sid == id(st) for sid, _ in recorded), \
            "live samples never recorded into the seeded entry"
    finally:
        server.close()


def test_path_model_ignores_corrupt_file(tmp_path):
    """A corrupt/foreign .path_model.json must not break boot."""
    import os

    from pilosa_tpu.server.server import Server

    d = str(tmp_path / "data")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, ".path_model.json"), "w") as f:
        f.write('{"v": 99, "entries": "nope"}')
    server = Server(d, bind="127.0.0.1:0")
    server.open()
    try:
        assert getattr(server.executor, "_path_seed", None) in (None, {})
    finally:
        server.close()
    with open(os.path.join(d, ".path_model.json"), "w") as f:
        f.write("not json at all")
    server = Server(d, bind="127.0.0.1:0")
    server.open()
    server.close()
    # Valid envelope, garbage VALUES: must sanitize to no-seed and
    # never raise at query time.
    with open(os.path.join(d, ".path_model.json"), "w") as f:
        f.write('{"v": 1, "entries": {"Count[frame,rowID]|1": '
                '{"b": "garbage", "s": null, "inel": "x"}, '
                '"ok|2": {"b": 0.001}}}')
    server = Server(d, bind="127.0.0.1:0")
    server.open()
    try:
        seed = server.executor._path_seed
        assert "Count[frame,rowID]|1" not in seed  # nothing usable
        assert seed["ok|2"] == {"b": 0.001}
        idx = server.holder.create_index("i2")
        idx.create_frame("f")
        from pilosa_tpu.pql import parse

        out = server.executor.execute("i2", parse(
            'Count(Bitmap(frame="f", rowID=1))'))
        assert out == [0]
    finally:
        server.close()
