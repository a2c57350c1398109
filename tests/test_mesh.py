"""Sharded kernels on the 8-device virtual CPU mesh — the JAX analog of
the reference's multi-node distribution tests (executor_test.go remote
suite): results must equal the single-device reference computation."""
import numpy as np
import jax

from pilosa_tpu.parallel.mesh import MeshQueryEngine, full_query_step, make_mesh

W = 512  # words per slice-row for tests (kernels are width-polymorphic)


def np_count(a):
    return int(np.bitwise_count(a).sum())


def mk(rng, shape, density=0.3):
    return (rng.random(shape + (W * 32,)) < density).astype(np.uint8)


def pack(bits):
    return np.packbits(bits, axis=-1, bitorder="little").view(np.uint32)


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_count_and(rng):
    engine = MeshQueryEngine(make_mesh())
    S = 16
    a = pack(mk(rng, (S,)))
    b = pack(mk(rng, (S,)))
    got = int(engine.count_and(engine.shard_rows(a), engine.shard_rows(b)))
    assert got == np_count(a & b)


def test_sharded_count_padding(rng):
    """13 slices over 8 devices: zero-padding must not change counts."""
    engine = MeshQueryEngine(make_mesh())
    a = pack(mk(rng, (13,)))
    b = pack(mk(rng, (13,)))
    got = int(engine.count_and(engine.shard_rows(a), engine.shard_rows(b)))
    assert got == np_count(a & b)


def test_nary_count(rng):
    engine = MeshQueryEngine(make_mesh())
    rows = pack(mk(rng, (8, 3)))
    got = int(engine.nary_count(engine.shard_rows(rows), "and"))
    want = np_count(rows[:, 0] & rows[:, 1] & rows[:, 2])
    assert got == want
    got = int(engine.nary_count(engine.shard_rows(rows), "or"))
    assert got == np_count(rows[:, 0] | rows[:, 1] | rows[:, 2])


def test_sharded_topn_counts(rng):
    engine = MeshQueryEngine(make_mesh())
    S, R = 8, 5
    m = pack(mk(rng, (S, R)))
    counts = np.asarray(engine.topn_counts(engine.shard_rows(m)))
    want = [np_count(m[:, r]) for r in range(R)]
    assert counts.tolist() == want

    src = pack(mk(rng, (S,)))
    counts = np.asarray(engine.topn_counts_src(
        engine.shard_rows(m), engine.shard_rows(src)))
    want = [np_count(m[:, r] & src) for r in range(R)]
    assert counts.tolist() == want


def test_sharded_bsi_plane_counts(rng):
    engine = MeshQueryEngine(make_mesh())
    S, D = 8, 6
    planes = pack(mk(rng, (S, D), density=0.2))
    filt = pack(mk(rng, (S,), density=0.5))
    counts = np.asarray(engine.bsi_plane_counts(
        engine.shard_rows(planes), engine.shard_rows(filt)))
    want = [np_count(planes[:, d] & filt) for d in range(D)]
    assert counts.tolist() == want


def test_union_gather(rng):
    engine = MeshQueryEngine(make_mesh())
    rows = pack(mk(rng, (16,), density=0.1))
    got = np.asarray(engine.union_gather(engine.shard_rows(rows)))
    want = np.bitwise_or.reduce(rows, axis=0)
    assert np.array_equal(got, want)


def test_full_query_step(rng):
    """The multi-chip dry-run path: one jitted program, all collectives."""
    engine = MeshQueryEngine(make_mesh())
    S, R, D = 8, 4, 5
    frag = pack(mk(rng, (S, R)))
    src = pack(mk(rng, (S,)))
    planes = pack(mk(rng, (S, D)))
    filt = pack(mk(rng, (S,)))
    c, t, b, u = full_query_step(
        engine, engine.shard_rows(frag), engine.shard_rows(src),
        engine.shard_rows(planes), engine.shard_rows(filt))
    assert int(c) == np_count(src & filt)
    assert np.asarray(t).tolist() == [np_count(frag[:, r]) for r in range(R)]
    assert np.asarray(b).tolist() == [np_count(planes[:, d] & filt)
                                      for d in range(D)]
    assert np.array_equal(np.asarray(u), np.bitwise_or.reduce(src, axis=0))


def test_batched_count_matches_serial(tmp_path):
    """The executor's batched mesh fast path returns bit-identical
    counts to the per-slice serial path on random expression trees,
    and invalidates its stack cache on writes."""
    import random

    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    rng = np.random.default_rng(3)
    for r in range(5):
        for s in range(3):
            cols = rng.choice(SLICE_WIDTH, 200, replace=False) + s * SLICE_WIDTH
            fr.import_bits([r] * len(cols), cols.tolist())
    e = Executor(holder)

    pyrng = random.Random(5)

    def tree(depth):
        if depth == 0 or pyrng.random() < 0.3:
            return f'Bitmap(frame="f", rowID={pyrng.randrange(5)})'
        op = pyrng.choice(["Union", "Intersect", "Difference", "Xor"])
        n = 2 if op in ("Difference", "Xor") else pyrng.randrange(1, 4)
        return f"{op}({', '.join(tree(depth - 1) for _ in range(n))})"

    for i in range(15):
        q = f"Count({tree(3)})"
        batched = e.execute("i", q)[0]
        orig = e._batched_count
        e._batched_count = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_count = orig
        assert batched == serial, (i, q)

    # a write invalidates the cached stacks
    before = e.execute("i", 'Count(Bitmap(frame="f", rowID=0))')[0]
    e.execute("i", f'SetBit(frame="f", rowID=0, columnID={SLICE_WIDTH + 7})')
    after = e.execute("i", 'Count(Bitmap(frame="f", rowID=0))')[0]
    assert after == before + 1
    holder.close()


def test_budget_windowed_batching(tmp_path):
    """Slice lists too large for the device budget stream through
    halved windows (SURVEY §5.7) — results identical to serial, for
    Count / Sum / Min / TopN."""
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    bsi = idx.create_frame("g", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=100)]))
    rng = np.random.default_rng(17)
    S = 40
    for s in range(S):
        cols = rng.choice(SLICE_WIDTH, 120, replace=False) + s * SLICE_WIDTH
        for r in (1, 2):
            fr.import_bits([r] * len(cols), cols.tolist())
        vcols = rng.choice(SLICE_WIDTH, 30, replace=False) + s * SLICE_WIDTH
        bsi.import_value("v", vcols.tolist(),
                         rng.integers(0, 101, size=30).tolist())
    e = Executor(holder)

    # Prove sub-window batches actually run for EVERY kind (engagement,
    # not silent serial fallback).
    window_hits = {}

    from pilosa_tpu.executor import BATCH_OVER_BUDGET

    def probe(kind, orig):
        def inner(*a, **kw):
            out = orig(*a, **kw)
            ns = a[2]  # every _batched_* signature: (index, call, ns, ...)
            if (out is not None and out is not BATCH_OVER_BUDGET
                    and len(ns) < S):
                window_hits[kind] = True
            return out
        return inner

    e._batched_count = probe("count", e._batched_count)
    e._batched_sum = probe("sum", e._batched_sum)
    e._batched_min_max = probe("minmax", e._batched_min_max)
    e._batched_topn_phase1 = probe("topn", e._batched_topn_phase1)
    e._batched_topn_ids = probe("topn_ids", e._batched_topn_ids)

    # (query, rows its stacks need) → budget sized so the full list
    # exceeds it but ≥8-slice windows fit: rows × 20-slice windows.
    word32 = SLICE_WIDTH // 32
    cases = [
        ('Count(Intersect(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))', 2),
        ('Sum(frame="g", field="v")', 8),      # depth 7 + exists
        ('Min(frame="g", field="v")', 8),
        ('TopN(Bitmap(frame="f", rowID=1), frame="f", n=2)', 4),
        # phase 1's windows fetched every candidate's counts above, so
        # only an explicit-ids call runs the re-query's windows
        ('TopN(Bitmap(frame="f", rowID=1), frame="f", ids=[1, 2])', 4),
    ]
    for q, rows in cases:
        e.STACK_CACHE_BYTES = rows * 20 * word32 * 4
        windowed = e.execute("i", q)[0]
        e2 = Executor(holder)  # default budget: single fused program
        full = e2.execute("i", q)[0]
        e3 = Executor(holder)
        for a in ("_batched_count", "_batched_sum", "_batched_min_max",
                  "_batched_topn_ids", "_batched_topn_phase1",
                  "_batched_bitmap"):
            setattr(e3, a, lambda *ar, **kw: None)
        serial = e3.execute("i", q)[0]
        assert windowed == full == serial, q
    assert set(window_hits) == {"count", "sum", "minmax", "topn",
                                "topn_ids"}, \
        f"sub-window batches engaged only for {sorted(window_hits)}"
    holder.close()


def test_incremental_stack_update_parity(tmp_path):
    """Interleaved writes and batched reads on the 8-device mesh: the
    incremental scatter path (only mutated slices' rows re-uploaded
    into the resident sharded stack) stays bit-identical to a fresh
    full rebuild, for both row and BSI plane stacks."""
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    bsi = idx.create_frame("g", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=100)]))
    rng = np.random.default_rng(7)
    S = 9  # uneven vs 8 devices → padding exercised
    for s in range(S):
        cols = rng.choice(SLICE_WIDTH, 300, replace=False) + s * SLICE_WIDTH
        for r in (1, 2):
            fr.import_bits([r] * len(cols), cols.tolist())
        vcols = rng.choice(SLICE_WIDTH, 50, replace=False) + s * SLICE_WIDTH
        bsi.import_value("v", vcols.tolist(),
                         rng.integers(0, 101, size=50).tolist())
    e = Executor(holder)
    qc = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
          'Bitmap(frame="f", rowID=2)))')
    qs = 'Sum(frame="g", field="v")'
    e.execute("i", qc), e.execute("i", qs)  # populate stack caches
    for i in range(6):
        s = int(rng.integers(0, S))
        c = int(rng.integers(0, SLICE_WIDTH)) + s * SLICE_WIDTH
        e.execute("i", f'SetBit(frame="f", rowID=1, columnID={c})\n'
                       f'SetBit(frame="f", rowID=2, columnID={c})')
        e.execute("i", f'SetFieldValue(frame="g", columnID={c}, '
                       f'v={int(rng.integers(0, 101))})')
        fresh = Executor(holder)  # no caches: full rebuild reference
        assert e.execute("i", qc) == fresh.execute("i", qc), i
        assert e.execute("i", qs) == fresh.execute("i", qs), i
    holder.close()


def test_batched_sum_matches_serial(tmp_path):
    """Batched BSI Sum (stacked planes, sharded) equals the per-slice
    serial path, with and without a filter."""
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f", FrameOptions(range_enabled=True))
    fr.create_field(Field("v", min=-10, max=500))
    rng = np.random.default_rng(9)
    cols = rng.choice(3 * SLICE_WIDTH, 150, replace=False)
    vals = rng.integers(-10, 501, size=150)
    for c, v in zip(cols.tolist(), vals.tolist()):
        fr.set_field_value(c, "v", v)
    filt = idx.create_frame("g")
    filt_cols = cols[: 70]
    filt.import_bits([1] * len(filt_cols), filt_cols.tolist())

    e = Executor(holder)
    for q in ('Sum(frame="f", field="v")',
              'Sum(Bitmap(frame="g", rowID=1), frame="f", field="v")'):
        batched = e.execute("i", q)[0]
        orig = e._batched_sum
        e._batched_sum = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_sum = orig
        assert batched == serial, q
    assert batched.sum == int(vals[np.isin(cols, filt_cols)].sum())
    holder.close()


def test_batched_cache_not_stale_after_frame_recreate(tmp_path):
    """Deleting and recreating a frame must never serve stale cached
    stacks (fragment uid+version tokens, not bare version counters)."""
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    fr.import_bits([1, 1, 1], [10, 20, 30])
    e = Executor(holder)
    q = 'Count(Bitmap(frame="f", rowID=1))'
    assert e.execute("i", q)[0] == 3  # populates the stack cache

    idx.delete_frame("f")
    fr2 = idx.create_frame("f")
    fr2.import_bits([1], [10])
    assert e.execute("i", q)[0] == 1
    holder.close()


def test_batched_topn_matches_serial(tmp_path):
    """Batched TopN phase-2 exact counts equal the serial per-slice
    path, including src filters, thresholds, and attr filters."""
    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu import SLICE_WIDTH

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    rng = np.random.default_rng(12)
    for r in range(8):
        n = rng.integers(20, 300)
        cols = rng.choice(2 * SLICE_WIDTH, n, replace=False)
        fr.import_bits([r] * n, cols.tolist())
    fr.row_attr_store.set_attrs(2, {"cat": "x"})
    fr.row_attr_store.set_attrs(5, {"cat": "x"})
    e = Executor(holder)

    queries = [
        'TopN(frame="f", n=4)',
        'TopN(frame="f", n=8, threshold=50)',
        'TopN(Bitmap(frame="f", rowID=0), frame="f", n=5)',
        'TopN(frame="f", n=5, field="cat", filters=["x"])',
    ]
    for q in queries:
        batched = e.execute("i", q)[0]
        orig = e._batched_topn_ids
        e._batched_topn_ids = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_topn_ids = orig
        assert batched == serial, (q, batched, serial)
    holder.close()


def test_batched_bitmap_matches_serial(tmp_path):
    """Batched compound-bitmap materialization equals the serial
    merge, including empty-slice dropping and the cached count."""
    import random

    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    rng = np.random.default_rng(21)
    for r in range(4):
        # leave slice 1 empty for some rows
        cols = np.concatenate([
            rng.choice(SLICE_WIDTH, 50, replace=False),
            rng.choice(SLICE_WIDTH, 50, replace=False) + 2 * SLICE_WIDTH])
        fr.import_bits([r] * len(cols), cols.tolist())
    e = Executor(holder)
    e._force_path = "batched"  # pin the batched arm (model is adaptive)

    pyrng = random.Random(8)
    for _ in range(10):
        op = pyrng.choice(["Union", "Intersect", "Difference", "Xor"])
        a, b = pyrng.sample(range(4), 2)
        q = (f'{op}(Bitmap(frame="f", rowID={a}), '
             f'Bitmap(frame="f", rowID={b}))')
        batched = e.execute("i", q)[0]
        orig = e._batched_bitmap
        e._batched_bitmap = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_bitmap = orig
        assert batched.columns().tolist() == serial.columns().tolist(), q
        assert batched.count() == serial.count(), q
        # batched drops all-zero segments; serial keeps them where a
        # fragment existed — externally invisible, so compare content
        import numpy as np_
        for s_ in set(batched.segments) | set(serial.segments):
            bseg = batched.segments.get(s_)
            sseg = serial.segments.get(s_)
            bz = bseg is None or not np_.asarray(bseg).any()
            sz = sseg is None or not np_.asarray(sseg).any()
            if bz and sz:
                continue
            assert np_.array_equal(np_.asarray(bseg), np_.asarray(sseg)), q
    holder.close()


def test_batched_time_range_matches_serial(tmp_path):
    """Range(time) expands to a Union over the time-view cover inside
    the batched planner — equal to the serial per-slice path."""
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f", FrameOptions(time_quantum="YMD"))
    rng = np.random.default_rng(33)
    from datetime import datetime
    days = ["2017-06-%02dT00:00" % d for d in range(1, 20)]
    for i, day in enumerate(days):
        cols = rng.choice(2 * SLICE_WIDTH, 30, replace=False)
        t = datetime.strptime(day, "%Y-%m-%dT%H:%M")
        for c in cols.tolist():
            fr.set_bit("standard", 3, c, t=t)
    e = Executor(holder)

    for q in (
        'Count(Range(frame="f", rowID=3, start="2017-06-03T00:00", '
        'end="2017-06-11T00:00"))',
        'Count(Union(Range(frame="f", rowID=3, start="2017-06-01T00:00", '
        'end="2017-06-05T00:00"), Bitmap(frame="f", rowID=3)))',
    ):
        batched = e.execute("i", q)[0]
        orig = e._batched_count
        e._batched_count = lambda *a, **k: None
        serial = e.execute("i", q)[0]
        e._batched_count = orig
        assert batched == serial, (q, batched, serial)
    holder.close()


def test_batched_bsi_conditions_match_serial(tmp_path):
    """BSI condition leaves (vmapped descents over the planes stack)
    equal the serial per-slice path inside Count and Sum filters."""
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f", FrameOptions(range_enabled=True))
    fr.create_field(Field("v", min=-20, max=300))
    rng = np.random.default_rng(44)
    cols = rng.choice(3 * SLICE_WIDTH, 200, replace=False)
    vals = rng.integers(-20, 301, size=200)
    for c, v in zip(cols.tolist(), vals.tolist()):
        fr.set_field_value(c, "v", v)
    e = Executor(holder)

    queries = [
        'Count(Range(frame="f", v > 50))',
        'Count(Range(frame="f", v <= -5))',
        'Count(Range(frame="f", v == %d))' % int(vals[0]),
        'Count(Range(frame="f", v != %d))' % int(vals[0]),
        'Count(Range(frame="f", v >< [0, 100]))',
        'Count(Range(frame="f", v > 9999))',      # out of range -> empty
        'Count(Range(frame="f", v >= -20))',      # full range -> not null
        'Sum(Range(frame="f", v > 100), frame="f", field="v")',
        'Count(Union(Range(frame="f", v > 250), Range(frame="f", v < -10)))',
    ]
    for q in queries:
        batched = e.execute("i", q)[0]
        for attr in ("_batched_count", "_batched_sum"):
            setattr(e, "_orig" + attr, getattr(e, attr))
            setattr(e, attr, lambda *a, **k: None)
        serial = e.execute("i", q)[0]
        for attr in ("_batched_count", "_batched_sum"):
            setattr(e, attr, getattr(e, "_orig" + attr))
        assert batched == serial, (q, batched, serial)
    # ground truth spot check
    assert e.execute("i", 'Count(Range(frame="f", v > 50))')[0] == \
        int((vals > 50).sum())
    holder.close()
