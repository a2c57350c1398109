"""Randomized differential tests: the full PQL read surface against a
pure-NumPy model of the reference semantics (the analog of the
reference's exhaustive roaring container-pair matrix,
roaring/roaring_test.go), plus mid-query failover and a concurrency
smoke test (§5.2/5.3 analogs)."""
import json
import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.storage.holder import Holder


N_ROWS = 8
N_SLICES = 3
DENSITY = 0.002


@pytest.fixture(scope="module")
def corpus():
    """Random (row, col) sets spanning 3 slices + their NumPy model:
    model[row] = sorted np.array of set columns."""
    rng = np.random.default_rng(1234)
    model = {}
    for r in range(N_ROWS):
        n = rng.integers(1, int(SLICE_WIDTH * N_SLICES * DENSITY))
        cols = np.unique(rng.integers(0, SLICE_WIDTH * N_SLICES, size=n))
        model[r] = cols
    return model


@pytest.fixture(scope="module")
def env(tmp_path_factory, corpus):
    holder = Holder(str(tmp_path_factory.mktemp("diff") / "data")).open()
    idx = holder.create_index("i")
    frame = idx.create_frame("f")
    for r, cols in corpus.items():
        by_slice = {}
        for c in cols.tolist():
            by_slice.setdefault(c // SLICE_WIDTH, []).append(c)
        for s, cs in by_slice.items():
            frame.import_bits([r] * len(cs), cs)
    e = Executor(holder)
    yield holder, e
    holder.close()


def q(e, pql):
    return e.execute("i", pql)


def bm(r):
    return f'Bitmap(frame="f", rowID={r})'


def _cols(result):
    return np.asarray(result.columns(), dtype=np.int64)


# ----------------------------------------------------- binary op matrix

def _pairs():
    rng = np.random.default_rng(7)
    return [tuple(rng.choice(N_ROWS, 2, replace=False)) for _ in range(6)]


@pytest.mark.parametrize("a,b", _pairs())
def test_intersect_union_difference_xor_parity(env, corpus, a, b):
    _, e = env
    ca, cb = corpus[a], corpus[b]
    want = {
        "Intersect": np.intersect1d(ca, cb),
        "Union": np.union1d(ca, cb),
        "Difference": np.setdiff1d(ca, cb),
        "Xor": np.setxor1d(ca, cb),
    }
    for op, expect in want.items():
        got = _cols(q(e, f"{op}({bm(a)}, {bm(b)})")[0])
        assert np.array_equal(got, expect), (op, a, b)
        # Count parity through the count-only fast path too
        cnt = q(e, f"Count({op}({bm(a)}, {bm(b)}))")[0]
        assert cnt == len(expect), (op, a, b)


def test_nested_compound_parity(env, corpus):
    _, e = env
    c = corpus
    want = np.setdiff1d(
        np.union1d(np.intersect1d(c[0], c[1]), c[2]),
        np.setxor1d(c[3], c[4]))
    got = _cols(q(
        e,
        f"Difference(Union(Intersect({bm(0)}, {bm(1)}), {bm(2)}),"
        f" Xor({bm(3)}, {bm(4)}))")[0])
    assert np.array_equal(got, want)


def test_nary_ops_parity(env, corpus):
    _, e = env
    c = corpus
    want_u = np.union1d(np.union1d(c[0], c[1]), c[2])
    got_u = _cols(q(e, f"Union({bm(0)}, {bm(1)}, {bm(2)})")[0])
    assert np.array_equal(got_u, want_u)
    want_i = np.intersect1d(np.intersect1d(c[0], c[1]), c[2])
    got_i = _cols(q(e, f"Intersect({bm(0)}, {bm(1)}, {bm(2)})")[0])
    assert np.array_equal(got_i, want_i)


def test_topn_parity_with_brute_force(env, corpus):
    _, e = env
    counts = sorted(((len(c), -r, r) for r, c in corpus.items()),
                    reverse=True)
    want = [(r, n) for n, _, r in counts[:4]]
    got = list(q(e, 'TopN(frame="f", n=4)')[0])
    # ties may order differently; compare as count multiset + id validity
    assert [c for _, c in got] == [c for _, c in want]
    by_row = {r: len(c) for r, c in corpus.items()}
    for rid, cnt in got:
        assert by_row[rid] == cnt


def test_topn_src_parity(env, corpus):
    _, e = env
    src = corpus[0]
    want = {r: len(np.intersect1d(c, src)) for r, c in corpus.items()}
    pairs = q(e, f'TopN({bm(0)}, frame="f", n={N_ROWS})')[0]
    for rid, cnt in pairs:
        assert want[rid] == cnt


# ----------------------------------------------------- failover remap

from pilosa_tpu.testing import free_ports as _free_ports  # noqa: E402


def test_failover_remap_to_replica(tmp_path):
    """With replicas=2, killing one node mid-stream must not fail reads:
    the coordinator remaps the dead node's slices to the surviving
    replica (ref: executor.go:1487-1500 retry loop)."""
    import urllib.request

    from pilosa_tpu.server.server import Server

    ports = _free_ports(2)
    hosts = [f"localhost:{p}" for p in ports]
    servers = [
        Server(str(tmp_path / f"n{i}"), bind=hosts[i], cluster_hosts=hosts,
               replica_n=2, anti_entropy_interval=0,
               polling_interval=0).open()
        for i in range(2)
    ]

    def post(host, path, body):
        req = urllib.request.Request(f"http://{host}{path}", data=body,
                                     method="POST")
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())

    try:
        post(hosts[0], "/index/i", b"{}")
        post(hosts[0], "/index/i/frame/f", b"{}")
        cols = [3, SLICE_WIDTH + 5, 2 * SLICE_WIDTH + 7, 3 * SLICE_WIDTH + 1]
        for c in cols:
            post(hosts[0], "/index/i/query",
                 f'SetBit(frame="f", rowID=1, columnID={c})'.encode())

        # kill node 1; node 0 must still answer over all 4 slices
        servers[1].close()
        out = post(hosts[0], "/index/i/query",
                   b'Count(Bitmap(frame="f", rowID=1))')
        assert out["results"] == [len(cols)]
        out = post(hosts[0], "/index/i/query", b'Bitmap(frame="f", rowID=1)')
        assert out["results"][0]["bits"] == sorted(cols)
    finally:
        for s in servers:
            s.close()


# ----------------------------------------------------- concurrency smoke

def test_concurrent_writers_and_readers(tmp_path):
    """Threaded set_bit + queries on one holder: no exceptions, and the
    final state contains every written bit (the Go-race-detector analog
    for our RWMutex'd storage objects, SURVEY §5.2)."""
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_frame("f")
    e = Executor(holder)
    errors = []

    def writer(tid):
        try:
            for k in range(60):
                e.execute("i", f'SetBit(frame="f", rowID={tid}, '
                               f'columnID={tid * 1000 + k})')
        except Exception as ex:  # pragma: no cover
            errors.append(ex)

    def reader():
        try:
            for _ in range(30):
                e.execute("i", 'Count(Union(Bitmap(frame="f", rowID=0), '
                               'Bitmap(frame="f", rowID=1)))')
        except Exception as ex:  # pragma: no cover
            errors.append(ex)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(3)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for tid in range(3):
        res = e.execute("i", f'Count(Bitmap(frame="f", rowID={tid}))')
        assert res[0] == 60, tid
    holder.close()


# ----------------------------------------------------- BSI differential

def test_bsi_sum_range_minmax_parity(tmp_path):
    """Random column->value map vs NumPy for Sum / every Range op /
    Min / Max (bit-plane loops vs direct arithmetic)."""
    from pilosa_tpu.executor import SumCount
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    rng = np.random.default_rng(99)
    lo, hi = -50, 1000
    cols = np.unique(rng.integers(0, 2 * SLICE_WIDTH, size=300))
    vals = rng.integers(lo, hi + 1, size=len(cols))

    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=lo, max=hi)]))
    frame = idx.frame("f")
    for s in np.unique(cols // SLICE_WIDTH):
        m = cols // SLICE_WIDTH == s
        frame.import_value("v", cols[m].tolist(), vals[m].tolist())
    e = Executor(holder)

    assert e.execute("i", 'Sum(frame="f", field="v")') == [
        SumCount(int(vals.sum()), len(cols))]
    assert e.execute("i", 'Max(frame="f", field="v")') == [
        SumCount(int(vals.max()), int((vals == vals.max()).sum()))]
    assert e.execute("i", 'Min(frame="f", field="v")') == [
        SumCount(int(vals.min()), int((vals == vals.min()).sum()))]

    pivots = [int(vals.min()), -1, 0, 17, 500, int(vals.max())]
    for p in pivots:
        checks = {
            f"v > {p}": cols[vals > p],
            f"v >= {p}": cols[vals >= p],
            f"v < {p}": cols[vals < p],
            f"v <= {p}": cols[vals <= p],
            f"v == {p}": cols[vals == p],
            f"v != {p}": cols[vals != p],
        }
        for cond, expect in checks.items():
            got = np.asarray(
                e.execute("i", f'Range(frame="f", {cond})')[0].columns())
            assert np.array_equal(got, expect), cond
    a, b = -10, 600
    got = np.asarray(
        e.execute("i", f'Range(frame="f", v >< [{a}, {b}])')[0].columns())
    assert np.array_equal(got, cols[(vals >= a) & (vals <= b)])
    holder.close()


# ------------------------------------------- time-quantum cover property

def test_batched_vs_serial_full_surface(tmp_path):
    """Randomized batched-vs-serial differential over the whole read
    surface: every query runs once on the batched mesh path and once
    with ALL batched fast paths disabled; results must be identical.
    Guards every batched kernel (count/sum/min/max/both TopN phases/
    tanimoto/materialization/BSI conditions) at once."""
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    rng = np.random.default_rng(99)
    holder = Holder(str(tmp_path / "data")).open()
    try:
        idx = holder.create_index("i")
        frame = idx.create_frame("f")
        bsi = idx.create_frame("g", FrameOptions(
            range_enabled=True, fields=[Field("v", min=-5, max=500)]))
        n_slices = 3
        for r in range(6):
            n = int(rng.integers(50, 400))
            cols = np.unique(rng.integers(
                0, SLICE_WIDTH * n_slices, size=n))
            frame.import_bits([r] * len(cols), cols.tolist())
        vcols = np.unique(rng.integers(0, SLICE_WIDTH * n_slices, size=300))
        bsi.import_value("v", vcols.tolist(),
                         rng.integers(-5, 501, size=len(vcols)).tolist())

        e = Executor(holder)
        e._force_path = "batched"
        batched_attrs = [a for a in dir(e) if a.startswith("_batched_")
                         and callable(getattr(e, a))
                         and a not in ("_batched_plan",)]

        queries = [
            'Count(Bitmap(frame="f", rowID=0))',
            'Count(Intersect(Bitmap(frame="f", rowID=0), '
            'Bitmap(frame="f", rowID=1)))',
            'Count(Xor(Union(Bitmap(frame="f", rowID=2), '
            'Bitmap(frame="f", rowID=3)), Bitmap(frame="f", rowID=4)))',
            'Union(Bitmap(frame="f", rowID=0), Bitmap(frame="f", rowID=5))',
            'Difference(Bitmap(frame="f", rowID=1), '
            'Bitmap(frame="f", rowID=2))',
            'TopN(frame="f", n=4)',
            'TopN(Bitmap(frame="f", rowID=0), frame="f", n=4)',
            'TopN(Bitmap(frame="f", rowID=0), frame="f", n=6, '
            'tanimotoThreshold=10)',
            'TopN(frame="f", ids=[1, 3, 5])',
            'Sum(frame="g", field="v")',
            'Sum(Bitmap(frame="f", rowID=0), frame="g", field="v")',
            'Min(frame="g", field="v")',
            'Max(frame="g", field="v")',
            'Min(Bitmap(frame="f", rowID=1), frame="g", field="v")',
            'Range(frame="g", v > 100)',
            'Count(Range(frame="g", v >< [0, 250]))',
        ]

        def run_all():
            out = []
            for pql in queries:
                r = e.execute("i", pql)[0]
                if hasattr(r, "columns"):
                    r = r.columns().tolist()
                elif isinstance(r, list):
                    r = list(r)
                out.append(r)
            return out

        # Count engagements of the primary entry points so the test
        # cannot pass vacuously as serial-vs-serial.
        engaged = []
        saved = {a: getattr(e, a) for a in batched_attrs}
        # A TopN's phase 2 read from its batched phase 1's count matrix
        # (_topn_derived) is that path's answer too.
        saved["_topn_derived"] = e._topn_derived
        entry_points = ("_batched_count", "_batched_bitmap",
                        "_batched_sum", "_batched_min_max",
                        "_batched_topn_ids", "_batched_topn_phase1",
                        "_topn_derived")

        def wrap(fn):
            def inner(*args, **kw):
                r = fn(*args, **kw)
                if r is not None:
                    engaged.append(r)
                return r
            return inner

        for a in entry_points:
            setattr(e, a, wrap(saved[a]))
        batched = run_all()
        assert len(engaged) >= len(queries), \
            f"batched paths engaged only {len(engaged)} times"
        for a in batched_attrs:
            setattr(e, a, lambda *args, **kw: None)
        serial = run_all()
        for a, fn in saved.items():
            setattr(e, a, fn)

        for pql, got_b, got_s in zip(queries, batched, serial):
            assert got_b == got_s, pql
    finally:
        holder.close()


def test_tri_modal_random_trees(tmp_path):
    """Random query trees through all three execution modes — full
    batch, budget-windowed, forced serial — must agree exactly."""
    import random

    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(str(tmp_path / "d")).open()
    try:
        idx = holder.create_index("i")
        fr = idx.create_frame("f")
        bsi = idx.create_frame("g", FrameOptions(
            range_enabled=True, fields=[Field("v", min=-20, max=500)]))
        rng = np.random.default_rng(31337)
        S = 8
        for s in range(S):
            for r in range(6):
                n = int(rng.integers(20, 300))
                cols = (np.unique(rng.integers(0, SLICE_WIDTH, n))
                        + s * SLICE_WIDTH)
                fr.import_bits([r] * len(cols), cols.tolist())
            vcols = (np.unique(rng.integers(0, SLICE_WIDTH, 150))
                     + s * SLICE_WIDTH)
            bsi.import_value("v", vcols.tolist(),
                             rng.integers(-20, 501, len(vcols)).tolist())

        from pilosa_tpu import WORDS_PER_SLICE

        e_full = Executor(holder)
        e_win = Executor(holder)
        e_win.STACK_CACHE_BYTES = 3 * 20 * WORDS_PER_SLICE * 4
        e_ser = Executor(holder)
        # Force serial by nulling the shared batch_fn hook itself, so
        # renames of individual _batched_* methods can't silently turn
        # this mode back into a batched one.
        serial_runs = []
        orig_mr = e_ser._map_reduce

        def serial_map_reduce(index, slices, call, opt, map_fn, reduce_fn,
                              batch_fn=None):
            serial_runs.append(call.name)
            return orig_mr(index, slices, call, opt, map_fn, reduce_fn,
                           batch_fn=None)

        e_ser._map_reduce = serial_map_reduce
        e_full._force_path = "batched"
        e_win._force_path = "batched"

        pyrng = random.Random(99)

        def tree(d):
            if d == 0 or pyrng.random() < 0.35:
                return f'Bitmap(frame="f", rowID={pyrng.randrange(6)})'
            op = pyrng.choice(["Union", "Intersect", "Difference", "Xor"])
            n = 2 if op in ("Difference", "Xor") else pyrng.randrange(1, 4)
            return f"{op}({', '.join(tree(d - 1) for _ in range(n))})"

        def q_random():
            kind = pyrng.randrange(8)
            if kind == 0:
                return f"Count({tree(3)})"
            if kind == 1:
                return tree(2)
            if kind == 2:
                return f'TopN({tree(2)}, frame="f", n={pyrng.randrange(1, 6)})'
            if kind == 3:
                return (f'TopN({tree(1)}, frame="f", n=8, '
                        f'tanimotoThreshold={pyrng.randrange(1, 60)})')
            if kind == 4:
                return f'Sum({tree(1)}, frame="g", field="v")'
            if kind == 5:
                return pyrng.choice(['Min(frame="g", field="v")',
                                     'Max(frame="g", field="v")'])
            if kind == 6:
                return (f'Count(Range(frame="g", '
                        f'v > {pyrng.randrange(-20, 500)}))')
            return (f'TopN(frame="f", ids=[{pyrng.randrange(6)}, '
                    f'{pyrng.randrange(6)}])')

        def norm(r):
            if hasattr(r, "columns"):
                return r.columns().tolist()
            return list(r) if isinstance(r, list) else r

        for i in range(60):
            q = q_random()
            a = norm(e_full.execute("i", q)[0])
            b = norm(e_win.execute("i", q)[0])
            c = norm(e_ser.execute("i", q)[0])
            assert a == b == c, (i, q, a, b, c)
        assert serial_runs, "serial mode never executed"
    finally:
        holder.close()


def test_views_by_time_range_exact_cover_property():
    """Random [start, end) hour ranges: the view cover must partition the
    range exactly — every hour in [start, end) in exactly one view, no
    hour outside (ref: ViewsByTimeRange time.go:112-184)."""
    from datetime import datetime, timedelta

    from pilosa_tpu import time_quantum as tq

    rng = np.random.default_rng(5)
    base = datetime(2016, 1, 1)
    for _ in range(25):
        start = base + timedelta(hours=int(rng.integers(0, 24 * 700)))
        end = start + timedelta(hours=int(rng.integers(1, 24 * 90)))
        views = tq.views_by_time_range("s", start, end, "YMDH")

        def hours_of(view):
            t = view[len("s_"):]
            fmts = {4: "%Y", 6: "%Y%m", 8: "%Y%m%d", 10: "%Y%m%d%H"}
            vstart = datetime.strptime(t, fmts[len(t)])
            if len(t) == 4:
                vend = datetime(vstart.year + 1, 1, 1)
            elif len(t) == 6:
                vend = (datetime(vstart.year + 1, 1, 1) if vstart.month == 12
                        else datetime(vstart.year, vstart.month + 1, 1))
            elif len(t) == 8:
                vend = vstart + timedelta(days=1)
            else:
                vend = vstart + timedelta(hours=1)
            out = set()
            t = vstart
            while t < vend:
                out.add(t)
                t += timedelta(hours=1)
            return out

        covered = set()
        for v in views:
            hs = hours_of(v)
            assert not (covered & hs), f"overlap in {views}"
            covered |= hs
        want = set()
        t = start
        while t < end:
            want.add(t)
            t += timedelta(hours=1)
        assert covered == want, (start, end, views)


def test_topn_under_cache_pressure(tmp_path):
    """TopN in the approximation regime the reference documents —
    cacheSize SMALLER than the row count, so the ranked cache's entry
    threshold (1.1x min, cache.go:175-196) and eviction actually gate
    candidates — differentially: batched vs serial vs an independent
    NumPy oracle of the fragment.go:831-963 walk (candidates from the
    per-slice cache, exact counts, per-slice threshold + n-truncation,
    cross-slice merge, phase-2 exact re-query)."""
    import random

    from pilosa_tpu.executor import pairs_add
    from pilosa_tpu.storage.index import FrameOptions

    n_slices, n_rows, cache_size = 3, 40, 8
    rng = np.random.default_rng(77)
    pyrng = random.Random(77)

    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    fr = idx.create_frame("f", FrameOptions(cache_size=cache_size))
    model = {}  # (slice, row) -> set of absolute cols
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        # Skewed row sizes so eviction has real winners/losers; written
        # in shuffled row order so cache insertion order varies.
        rows = list(range(n_rows))
        pyrng.shuffle(rows)
        for r in rows:
            n = int(rng.integers(1, 60)) * (1 + r % 7)
            cols = base + np.unique(rng.integers(0, SLICE_WIDTH, size=n))
            fr.import_bits([r] * len(cols), cols.tolist())
            model[(s, r)] = set(cols.tolist())
    # Churn: clear some bits, then re-set one, so cached counts go
    # stale-then-updated through both mutation directions.
    for s in range(n_slices):
        for r in range(0, n_rows, 5):
            some = sorted(model[(s, r)])[:3]
            for c in some:
                fr.clear_bit("standard", r, c)
                model[(s, r)].discard(c)
            if some:
                fr.set_bit("standard", r, some[0])
                model[(s, r)].add(some[0])

    e = Executor(holder)

    def exact_count(s, r, src_row=None):
        cols = model[(s, r)]
        if src_row is not None:
            cols = cols & model[(s, src_row)]
        return len(cols)

    def oracle(n, min_threshold=1, src_row=None):
        merged = None
        for s in range(n_slices):
            frag = holder.fragment("i", "f", "standard", s)
            cand = sorted(frag.cache.entries)  # candidate semantics
            pairs = []
            for r in cand:
                c = exact_count(s, r, src_row)
                if c >= max(min_threshold, 1):
                    pairs.append((r, c))
            pairs.sort(key=lambda rc: (-rc[1], rc[0]))
            if n:
                pairs = pairs[:n]
            merged = pairs_add(merged, pairs)
        # Phase 2: exact re-query of the merged candidate id set.
        ids = sorted(r for r, _ in merged)
        final = None
        for s in range(n_slices):
            pairs = []
            for r in ids:
                c = exact_count(s, r, src_row)
                if c >= max(min_threshold, 1):
                    pairs.append((r, c))
            final = pairs_add(final, pairs)
        return final[:n] if n else final

    queries = [
        ('TopN(frame="f", n=5)', dict(n=5)),
        ('TopN(frame="f", n=3, threshold=40)',
         dict(n=3, min_threshold=40)),
        ('TopN(Bitmap(frame="f", rowID=2), frame="f", n=4)',
         dict(n=4, src_row=2)),
        ('TopN(frame="f", n=%d)' % (n_rows + 5), dict(n=n_rows + 5)),
    ]
    for q, okw in queries:
        expect = oracle(**okw)
        e._force_path = "batched"
        batched = e.execute("i", q)[0]
        e._force_path = "serial"
        serial = e.execute("i", q)[0]
        e._force_path = None
        assert batched == serial == expect, (q, batched, serial, expect)

    # The cache is genuinely under pressure: no fragment retains every
    # row (otherwise this test regressed into the big-cache regime).
    for s in range(n_slices):
        frag = holder.fragment("i", "f", "standard", s)
        assert len(frag.cache.entries) <= cache_size + 10 < n_rows
    holder.close()


def test_tri_modal_windowed_data_with_governor(tmp_path):
    """Tri-modal random trees over WINDOW-VARIED data — per (slice,
    row), columns cluster low (narrow window), high (relocated
    window), or spread full-width — with a 1 MB host governor evicting
    fragments mid-fuzz and interleaved mutations. Covers the column-
    window translation paths the full-width corpus never exercises."""
    import random

    from pilosa_tpu import WORDS_PER_SLICE
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    rng = np.random.default_rng(5)
    pyrng = random.Random(5)
    holder = Holder(str(tmp_path / "d"), host_bytes=1 << 20).open()
    try:
        idx = holder.create_index("i")
        fr = idx.create_frame("f")
        bsi = idx.create_frame("g", FrameOptions(range_enabled=True))
        bsi.create_field(Field("v", min=-20, max=500))
        n_slices = 3
        for s in range(n_slices):
            for r in range(6):
                n = int(rng.integers(20, 300))
                mode = pyrng.randrange(3)
                if mode == 0:      # narrow low window
                    cols = np.unique(rng.integers(0, 4000, n))
                elif mode == 1:    # relocated high window
                    cols = np.unique(
                        rng.integers(SLICE_WIDTH - 5000, SLICE_WIDTH, n))
                else:              # full width
                    cols = np.unique(rng.integers(0, SLICE_WIDTH, n))
                fr.import_bits([r] * len(cols),
                               (cols + s * SLICE_WIDTH).tolist())
            vcols = (np.unique(rng.integers(0, SLICE_WIDTH, 150))
                     + s * SLICE_WIDTH)
            bsi.import_value("v", vcols.tolist(),
                             rng.integers(-20, 501, len(vcols)).tolist())

        e_full = Executor(holder)
        e_full._force_path = "batched"
        e_win = Executor(holder)
        e_win._force_path = "batched"
        e_win.STACK_CACHE_BYTES = 3 * 2 * WORDS_PER_SLICE * 4
        e_ser = Executor(holder)
        e_ser._force_path = "serial"

        def tree(d):
            if d == 0 or pyrng.random() < 0.35:
                return f'Bitmap(frame="f", rowID={pyrng.randrange(6)})'
            op = pyrng.choice(["Union", "Intersect", "Difference", "Xor"])
            n = 2 if op in ("Difference", "Xor") else pyrng.randrange(1, 4)
            return f"{op}({', '.join(tree(d - 1) for _ in range(n))})"

        def q_random():
            kind = pyrng.randrange(8)
            if kind == 0:
                return f"Count({tree(3)})"
            if kind == 1:
                return tree(2)
            if kind == 2:
                return (f'TopN({tree(2)}, frame="f", '
                        f'n={pyrng.randrange(1, 6)})')
            if kind == 3:
                return (f'TopN({tree(1)}, frame="f", n=8, '
                        f'tanimotoThreshold={pyrng.randrange(1, 60)})')
            if kind == 4:
                return f'Sum({tree(1)}, frame="g", field="v")'
            if kind == 5:
                return pyrng.choice(['Min(frame="g", field="v")',
                                     'Max(frame="g", field="v")'])
            if kind == 6:
                return (f'Count(Range(frame="g", '
                        f'v >< [{pyrng.randrange(-20, 200)}, '
                        f'{pyrng.randrange(200, 500)}]))')
            return (f'TopN(frame="f", ids=[{pyrng.randrange(6)}, '
                    f'{pyrng.randrange(6)}])')

        def norm(r):
            if hasattr(r, "columns"):
                return r.columns().tolist()
            return list(r) if isinstance(r, list) else r

        def all_fragments():
            out = []
            for frame in idx.frames.values():
                for v in frame.views.values():
                    out.extend(v.fragments.values())
            return out

        for i in range(40):
            q = q_random()
            a = norm(e_full.execute("i", q)[0])
            b = norm(e_win.execute("i", q)[0])
            c = norm(e_ser.execute("i", q)[0])
            assert a == b == c, (i, q, a, b, c)
            if i % 7 == 3:  # mutate so windows/caches churn mid-fuzz
                col = pyrng.randrange(n_slices * SLICE_WIDTH)
                e_ser.execute(
                    "i", f'SetBit(frame="f", rowID={pyrng.randrange(6)}, '
                         f'columnID={col})')
            if i % 5 == 2:
                # Evict random fragments WITHOUT snapshotting first, so
                # the container-granular lazy paths serve with pending
                # op-log records (the round-3 read surface).
                for f in all_fragments():
                    if pyrng.random() < 0.5:
                        f.unload()
    finally:
        holder.close()
