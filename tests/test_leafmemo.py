"""Leaf facts memoised under the mutation epoch (PR 25): the fragment
list of a (frame, view), its stack-cache tokens, its column extent and
the rows it serves dense live in the plan cache under kind ``"leaf"``,
so a query that was never sent before composes its prelude from
O(leaves) lookups and touches no fragment. Counts, not timings: the
holder walk, ``win32`` and ``row_compressed`` are counted per call,
and ``leafMemoHits`` / ``leafMemoMisses`` per query.
"""
import sys
import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, querystats
from pilosa_tpu.executor import Executor
from pilosa_tpu.plancache import FragList
from pilosa_tpu.storage import fragment as frag_mod
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions
from pilosa_tpu.storage.frame import Field

INDEX = "users"
FRAMES = ("behavior", "device", "geo")
N_SLICES = 3
ROWS = 6          # per frame; row r of frame k is leaf (k, r)


def bm(leaf):
    frame, row = leaf
    return f'Bitmap(frame="{FRAMES[frame]}", rowID={row})'


def seed(holder, n_slices=N_SLICES):
    idx = holder.create_index(INDEX)
    rng = np.random.default_rng(25)
    for name in FRAMES:
        frame = idx.create_frame(name)
        for row in range(ROWS):
            cols = rng.choice(n_slices * SLICE_WIDTH, 6000, replace=False)
            frame.import_bits([row] * len(cols), cols.tolist())
    spend = idx.create_frame("spend", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=1000)]))
    cols = rng.choice(n_slices * SLICE_WIDTH, 60000, replace=False)
    spend.import_value("v", cols.tolist(),
                       rng.integers(0, 1000, len(cols)).tolist())
    return idx


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    idx = seed(holder)
    e = Executor(holder)
    e._force_path = "batched"
    # The index stands for segmentation-1b's 954 slices, where TopN's
    # candidates are staged a cached stack a row, not gathered afresh.
    e.TOPN_GATHER_MAX_ROWS = 0
    serial = Executor(holder)
    serial._force_path = "serial"
    yield holder, idx, e, serial
    holder.close()


class Calls:
    """Counts the three O(slices) walks of the prelude."""

    def __init__(self, monkeypatch):
        self.lists = []          # (frame, view) of each holder walk
        self.win32 = 0
        self.probes = 0
        real_fragments = Holder.fragments
        real_win32 = Fragment.win32
        real_probe = Fragment.row_compressed

        def fragments(holder, index, frame, view, slices):
            self.lists.append((frame, view))
            return real_fragments(holder, index, frame, view, slices)

        def win32(frag):
            self.win32 += 1
            return real_win32(frag)

        def row_compressed(frag, row_id):
            self.probes += 1
            return real_probe(frag, row_id)

        monkeypatch.setattr(Holder, "fragments", fragments)
        monkeypatch.setattr(Fragment, "win32", win32)
        monkeypatch.setattr(Fragment, "row_compressed", row_compressed)

    def reset(self):
        self.lists.clear()
        self.win32 = self.probes = 0

    def none(self):
        return not self.lists and self.win32 == 0 and self.probes == 0


def run(e, pql):
    """(result, resources) of one query under its own accumulator."""
    qs = querystats.QueryStats()
    with querystats.scope(qs):
        (out,) = e.execute(INDEX, pql)
    return out, qs.to_dict()


def plain(result):
    """A comparable value: TopN pairs and SumCounts as tuples."""
    if isinstance(result, list):
        return [tuple(p) for p in result]
    if hasattr(result, "sum"):
        return (result.sum, result.count)
    return result


def warm_rows(e):
    """The first compound Count of every row: builds its stack and
    probes it for the compressed tier, as the benchmark's warm-up
    does."""
    for k in range(len(FRAMES)):
        for r in range(ROWS):
            e.execute(INDEX, f"Count(Union({bm((k, r))}, {bm((k, r))}))")


# The five forms of perfbench/traffic/count-mixed-c1.json, a BSI and a
# TopN prelude. Each takes leaves (frame number, row) and gives PQL.
FORMS = {
    "intersect": lambda a, b, c: f"Count(Intersect({bm(a)}, {bm(b)}))",
    "union": lambda a, b, c: f"Count(Union({bm(a)}, {bm(b)}))",
    "difference": lambda a, b, c: f"Count(Difference({bm(a)}, {bm(b)}))",
    "xor": lambda a, b, c: f"Count(Xor({bm(a)}, {bm(b)}))",
    "segment": lambda a, b, c: (
        f"Count(Intersect({bm(a)}, Difference({bm(b)}, {bm(c)})))"),
    "bsi-sum": lambda a, b, c: (
        f'Sum(Union({bm(a)}, {bm(b)}), frame="spend", field="v")'),
    "topn": lambda a, b, c: (
        f'TopN(Union({bm(a)}, {bm(b)}), frame="geo", n=3)'),
}
WARM = ((0, 0), (1, 1), (2, 2))
UNSEEN = ((0, 3), (1, 4), (2, 5))
AFTER_WRITE = ((0, 5), (1, 0), (2, 1))
LATER = ((0, 2), (1, 3), (2, 4))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_never_seen_query_walks_nothing_and_a_write_walks_one_list(
        env, monkeypatch, form):
    holder, idx, e, serial = env
    pql = FORMS[form]
    warm_rows(e)
    e.execute(INDEX, pql(*WARM))
    calls = Calls(monkeypatch)

    got, res = run(e, pql(*UNSEEN))
    assert calls.none(), (calls.lists, calls.win32, calls.probes)
    assert res["leafMemoMisses"] == 0 and res["leafMemoHits"] >= 2
    assert res["stackBuilds"] == 0
    # (TopN's second phase is read from its first phase's count
    # matrix: it looks up no prelude.)
    assert res["planCacheHit"] == 0
    assert res["topnRecountsDerived"] == (1 if form == "topn" else 0)
    assert plain(got) == plain(serial.execute(INDEX, pql(*UNSEEN))[0])

    # One acknowledged write to one slice of one frame moves the
    # index's epoch: the next query re-reads that frame's list exactly
    # once (and, the epoch being the index's, each other list it needs
    # once), where every distinct query used to walk every leaf's.
    e.execute(INDEX, f'SetBit(frame="behavior", rowID=5, '
                     f'columnID={SLICE_WIDTH + 77})')
    calls.reset()
    got, res = run(e, pql(*AFTER_WRITE))
    assert calls.lists.count(("behavior", "standard")) == 1
    assert len(set(calls.lists)) == len(calls.lists) <= 3
    assert res["leafMemoMisses"] == len(calls.lists)
    assert calls.win32 == N_SLICES * len(calls.lists)
    assert plain(got) == plain(
        serial.execute(INDEX, pql(*AFTER_WRITE))[0])

    calls.reset()
    got, res = run(e, pql(*LATER))
    assert not calls.lists and calls.win32 == 0
    assert res["leafMemoMisses"] == 0
    assert plain(got) == plain(serial.execute(INDEX, pql(*LATER))[0])


def test_a_write_reaches_the_very_next_count(env):
    """Read-your-write through the memoised facts: the written rows'
    stacks are re-validated by their tokens and updated in place."""
    holder, idx, e, serial = env
    warm_rows(e)
    q = FORMS["segment"]((0, 1), (1, 2), (2, 3))
    (before,) = e.execute(INDEX, q)
    col = SLICE_WIDTH + 12345
    e.execute(INDEX, f'ClearBit(frame="geo", rowID=3, columnID={col})')
    for k, row in ((0, 1), (1, 2)):
        e.execute(INDEX, f'SetBit(frame="{FRAMES[k]}", rowID={row}, '
                         f'columnID={col})')
    got, res = run(e, q)
    assert got == serial.execute(INDEX, q)[0]
    assert got in (before, before + 1)
    assert res["leafMemoMisses"] == 3 and res["planCacheHit"] == 0
    assert res["stackBuilds"] >= 1      # the written rows, in place
    got, res = run(e, FORMS["segment"]((0, 1), (1, 2), (2, 4)))
    assert res["leafMemoMisses"] == 0 and res["stackBuilds"] == 0


def _first_row_list(e, idx):
    """The memoised list of frame ``behavior``."""
    slices = e.plans.slice_universe(INDEX, idx)[0]
    return e._frag_list(INDEX, "behavior", "standard", slices)


INVALIDATORS = {}


def invalidator(fn):
    INVALIDATORS[fn.__name__] = fn
    return fn


@invalidator
def new_slice(holder, idx):
    """A slice's first fragment: the slice list grows, and the lists of
    the other frames are re-read too (a fragment was opened)."""
    idx.frame("behavior").import_bits([0], [N_SLICES * SLICE_WIDTH + 3])


@invalidator
def frame_deleted(holder, idx):
    idx.delete_frame("behavior")


@invalidator
def governor_eviction(holder, idx):
    frag = holder.fragment(INDEX, "behavior", "standard", 1)
    assert frag._resident and frag.unload() is True


@invalidator
def close_and_reopen(holder, idx):
    frag = holder.fragment(INDEX, "behavior", "standard", 1)
    frag.close()
    frag.open()


@pytest.mark.parametrize("how", sorted(INVALIDATORS))
def test_what_invalidates_the_facts(env, monkeypatch, how):
    holder, idx, e, serial = env
    warm_rows(e)
    old = _first_row_list(e, idx)
    assert _first_row_list(e, idx) is old       # memoised: same object
    epoch = frag_mod.mutation_epoch(INDEX)
    INVALIDATORS[how](holder, idx)
    assert frag_mod.mutation_epoch(INDEX) > epoch
    calls = Calls(monkeypatch)
    # The serial path refuses a frame that is gone, so that case asks
    # the two frames that are left: the epoch is the index's, and
    # their lists are read again as well.
    first = 1 if how == "frame_deleted" else 0
    q = FORMS["intersect"]((first, 1), (2, 2), None)
    got, res = run(e, q)
    assert sorted(calls.lists) == sorted(
        [(FRAMES[first], "standard"), ("geo", "standard")])
    assert res["leafMemoMisses"] == 2 and res["leafMemoHits"] == 0
    assert got == serial.execute(INDEX, q)[0]
    new = _first_row_list(e, idx)
    assert new is not old and isinstance(new, FragList)
    if how == "frame_deleted":
        assert all(f is None for f in new) and new.extent is None
    elif how == "new_slice":
        assert len(new) == N_SLICES + 1 and new[-1] is not None
    else:
        assert new.tokens != old.tokens and len(new) == N_SLICES


def _evict(holder, frame, slices):
    for s in slices:
        frag = holder.fragment(INDEX, frame, "standard", s)
        frag.snapshot()
        assert frag.unload() is True


def test_compressed_outcome_is_not_memoised_and_still_routes(env):
    """Rows 100 and 101 of ``geo`` are sparse. With every fragment of
    the frame evicted they probe "compressed everywhere": the plan goes
    to the compressed tier, nothing is remembered, and the probe runs
    again next time. Their dense neighbours, memoised as dense while
    the frame was resident, are forgotten by the eviction's epoch bump;
    a dense row of a resident frame stays memoised beside them."""
    holder, idx, e, serial = env
    geo = idx.frame("geo")
    rng = np.random.default_rng(7)
    for row in (100, 101):
        cols = rng.choice(N_SLICES * SLICE_WIDTH, 300, replace=False)
        geo.import_bits([row] * len(cols), cols.tolist())
    warm_rows(e)
    slices = e.plans.slice_universe(INDEX, idx)[0]
    assert e._frag_list(INDEX, "geo", "standard", slices).dense >= {0, 1}

    _evict(holder, "geo", range(N_SLICES))
    sparse = ('Count(Intersect(Bitmap(frame="geo", rowID=100), '
              'Bitmap(frame="geo", rowID=101)))')
    for _ in range(2):
        got, res = run(e, sparse)
        assert "batched:compressed" in res["fallbackChain"]
        assert res["containerBlocksArray"] > 0
        assert got == serial.execute(INDEX, sparse)[0]
        facts = e._frag_list(INDEX, "geo", "standard", slices)
        assert facts.dense == set()             # nothing remembered
    assert not any(holder.fragment(INDEX, "geo", "standard", s)._resident
                   for s in range(N_SLICES))

    # A sparse row beside a dense row of another frame: the dense one
    # is known, so the plan is staged without probing the sparse one.
    mixed = ('Count(Intersect(Bitmap(frame="geo", rowID=100), %s))'
             % bm((0, 1)))
    got, res = run(e, mixed)
    assert "batched:compressed" not in res["fallbackChain"]
    assert got == serial.execute(INDEX, mixed)[0]
    assert 1 in e._frag_list(INDEX, "behavior", "standard", slices).dense


def test_evicted_sparse_row_routes_compressed_after_dense_neighbours(env):
    """One fragment list, some rows dense and one sparse: after the
    frame is evicted the sparse row alone still goes to the compressed
    tier although its neighbours had been memoised as dense, and a
    neighbour that is dense by count is found dense again."""
    holder, idx, e, serial = env
    geo = idx.frame("geo")
    cols = np.random.default_rng(8).choice(
        N_SLICES * SLICE_WIDTH, 200, replace=False)
    geo.import_bits([200] * len(cols), cols.tolist())
    dense_cols = np.arange(0, N_SLICES * SLICE_WIDTH, 37)  # > 4096 a slice
    geo.import_bits([201] * len(dense_cols), dense_cols.tolist())
    warm_rows(e)
    e.execute(INDEX, 'Count(Bitmap(frame="geo", rowID=200))')
    slices = e.plans.slice_universe(INDEX, idx)[0]
    assert 200 in e._frag_list(INDEX, "geo", "standard", slices).dense

    _evict(holder, "geo", range(N_SLICES))
    alone = ('Count(Union(Bitmap(frame="geo", rowID=200), '
             'Bitmap(frame="geo", rowID=200)))')
    got, res = run(e, alone)
    assert "batched:compressed" in res["fallbackChain"]
    assert got == 200
    both = ('Count(Union(Bitmap(frame="geo", rowID=200), '
            'Bitmap(frame="geo", rowID=201)))')
    got, res = run(e, both)
    assert "batched:compressed" not in res["fallbackChain"]
    assert got == serial.execute(INDEX, both)[0]
    assert e._frag_list(INDEX, "geo", "standard", slices).dense == {201}


def test_flood_of_distinct_plans_keeps_the_hot_facts(env, monkeypatch):
    """More distinct plans than the plan cache holds: every request
    puts a plan entry, every request also refreshes the lists it uses,
    so the LRU pushes out old plans and never the facts in use."""
    holder, idx, e, serial = env
    warm_rows(e)
    e.plans.set_capacity(8)     # shrinking may push the lists out
    e.execute(INDEX, FORMS["segment"]((0, 5), (1, 5), (2, 4)))
    calls = Calls(monkeypatch)
    sent = 0
    for a in range(ROWS):
        for b in range(ROWS):
            for c in range(ROWS):
                if sent == 60:
                    break
                q = FORMS["segment"]((0, a), (1, b), (2, c))
                got, res = run(e, q)
                assert res["leafMemoMisses"] == 0, (sent, q)
                assert res["leafMemoHits"] == 3
                sent += 1
    assert sent == 60 and not calls.lists and calls.win32 == 0
    # Shrinking pushed the lists out with what they knew; each row is
    # probed at most once again, whatever the number of plans.
    assert calls.probes <= len(FRAMES) * ROWS
    kinds = e.plans.snapshot()["entriesByKind"]
    assert kinds["leaf"] == 3 and sum(kinds.values()) == 8
    assert e.leaf_memo["leafMemoHits"] >= 180


def test_debug_vars_and_profile_carry_the_counters(tmp_path):
    import json
    import urllib.request

    from pilosa_tpu.server.server import Server

    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    try:
        seed(s.holder)
        s.executor._force_path = "batched"

        def call(path, body=None):
            req = urllib.request.Request(
                f"http://{s.host}{path}",
                data=body.encode() if body else None,
                method="POST" if body else "GET")
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        def profiled(pql):
            return call(f"/index/{INDEX}/query?profile=true",
                        pql)["profile"]

        res = profiled(FORMS["segment"]((0, 0), (1, 1), (2, 2)))["resources"]
        assert res["leafMemoMisses"] == 3 and res["leafMemoHits"] == 0
        res = profiled(FORMS["segment"]((0, 1), (1, 2), (2, 3)))["resources"]
        assert res["leafMemoMisses"] == 0 and res["leafMemoHits"] == 3
        prof = profiled(FORMS["segment"]((0, 2), (1, 3), (2, 4)))
        by_name = {sp["name"]: sp for sp in prof["spans"]}
        assert by_name["build.frags"]["tags"] == {"walked": 0}
        assert by_name["stacks.memo"]["tags"] == {"hit": False}
        assert {"stacks.build", "build.window", "build.args"} <= set(by_name)
        dv = call("/debug/vars")
        assert dv["leafMemoMisses"] == 3 and dv["leafMemoHits"] == 6
        assert dv["planCache"]["entriesByKind"]["leaf"] == 3
    finally:
        s.close()


def test_racing_write_and_distinct_counts_never_read_stale(env):
    """One thread sets fresh bits of row 0 of ``behavior`` and, after
    each acknowledged write, publishes how many it has set; another
    sends distinct Counts that contain that row and must never see
    fewer than were acknowledged before the Count began (nor more than
    were begun before it ended)."""
    holder, idx, e, serial = env
    warm_rows(e)
    base = e.execute(INDEX, f"Count({bm((0, 0))})")[0]
    frag_rows = serial.execute(INDEX, bm((0, 0)))[0]
    taken = set(frag_rows.columns().tolist())
    fresh = [c for c in range(SLICE_WIDTH, SLICE_WIDTH + 4000)
             if c not in taken][:120]
    acked = [0]
    begun = [0]
    errors = []
    done = threading.Event()

    def writer():
        try:
            for n, col in enumerate(fresh, 1):
                begun[0] = n
                e.execute(INDEX, f'SetBit(frame="behavior", rowID=0, '
                                 f'columnID={col})')
                acked[0] = n
        except BaseException as exc:  # noqa: BLE001 — reported below
            errors.append(exc)
        finally:
            done.set()

    def reader():
        k = 0
        try:
            while not done.is_set() or k < 40:
                # (r | o) - (o - r) = r, over changing rows o.
                k += 1
                other = bm((1 + k % 2, k % ROWS))
                q = (f"Count(Difference(Union({bm((0, 0))}, {other}), "
                     f"Difference({other}, {bm((0, 0))})))")
                lo = acked[0]
                (got,) = e.execute(INDEX, q)
                hi = begun[0]
                if not base + lo <= got <= base + hi:
                    errors.append((k, lo, got - base, hi))
                    return
                if k > 4000:
                    return
        except BaseException as exc:  # noqa: BLE001 — reported below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[:3]
    assert acked[0] == len(fresh)
    assert e.execute(INDEX, f"Count( {bm((0, 0))})") == [base + len(fresh)]
