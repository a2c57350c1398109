"""TopN on the served path (PR 26): the batched re-query costs the
same for one candidate and for fifty, so the path model's one entry a
shape holds, and a ``?profile=true`` TopN answer carries the phases'
spans, tags and counters. Over one slice phase 1's pairs are the
answer and the re-query is skipped (PR 27): every form equals the
two-phase result, and two slices still run both phases. A probe that
is a row of the fragment the TopN scans is read from the HBM mirror
inside the scan's program (PR 29): every form equals the brute-force
list and the same query served through host words."""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, querystats, tracing
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.pql import parse
from pilosa_tpu.server.server import Server
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions

TOPN = ('TopN(Bitmap(frame="f", rowID={p}), frame="f", n=50, '
        'tanimotoThreshold={t})')


def _call(pql):
    return parse(pql).calls[0]


def _bare_executor():
    e = Executor.__new__(Executor)      # the path model touches no holder
    e._path_stats, e._path_mu, e._force_path = {}, threading.Lock(), None
    return e


# ------------------------------------------------------- the path model

def test_a_count_shapes_key_is_unchanged():
    e = _bare_executor()
    call = _call('Count(Intersect(Bitmap(frame="f", rowID=1), '
                 'Bitmap(frame="f", rowID=2)))')
    _, _, st, _, _ = e._path_choice(call, list(range(954)))
    assert e._path_stats == {(e._call_shape(call), 10): st}
    st["b"] = 0.001
    assert set(e.save_path_model()["entries"]) == {
        "Count(Intersect(Bitmap[frame,rowID],Bitmap[frame,rowID]))|10"}


@pytest.mark.parametrize("n_ids, bucket", [(1, 1), (2, 2), (3, 4), (33, 64),
                                           (50, 64), (64, 64), (65, 128)])
def test_an_explicit_ids_call_keeps_one_entry_a_shape(n_ids, bucket):
    """The candidates bucket to a power of two for the program they
    compile; the path model's entry is the shape's and the slice
    bucket's whatever their number (one minimum is true: below)."""
    e = _bare_executor()
    assert e._candidate_bucket(n_ids) == bucket
    for n in (1, n_ids):
        call = _call(TOPN.format(p=1, t=70))
        call.args["ids"] = list(range(n))
        e._path_choice(call, [0])
    assert list(e._path_stats) == [(e._call_shape(call), 1)]


def test_one_and_fifty_candidates_cost_the_batched_path_the_same(tmp_path):
    """PR 23's second mode: the batched re-query staged a stack and a
    program argument a candidate (8.6 ms for one, 116-126 for fifty on
    the v5e), so one minimum a shape parked fifty-candidate calls on
    the path a one-candidate sample had won. Now the candidates are ONE
    gather a fragment and one operand whatever their number, so one
    minimum holds. Past ``TOPN_GATHER_MAX_ROWS`` slice-rows (many
    slices) it is a cached stack a candidate, as Count stages them."""
    from pilosa_tpu.storage.fragment import Fragment
    from pilosa_tpu.storage.holder import Holder

    h = Holder(str(tmp_path / "d")).open()
    try:
        h.create_index("i").create_frame("f")
        rows, cols = np.nonzero(np.tril(np.ones((64, 64), dtype=np.uint8)))
        h.index("i").frame("f").import_bits(rows + 1, cols)
        ex = Executor(h)
        ex._force_path = "batched"
        seen = {"gathers": 0, "stacks": 0, "operands": []}
        gather, leaf, run = (Fragment.device_rows_win, ex._leaf_stack,
                             ex._batched_topn_tanimoto_fn)

        def spy_gather(self, *a):
            seen["gathers"] += 1
            return gather(self, *a)

        def spy_leaf(*a, **k):
            seen["stacks"] += 1
            return leaf(*a, **k)

        def spy_fn(*a):
            fn = run(*a)
            return lambda *args: (seen["operands"].append(len(args)),
                                  fn(*args))[1]

        Fragment.device_rows_win = spy_gather
        ex._leaf_stack, ex._batched_topn_tanimoto_fn = spy_leaf, spy_fn
        q = ('TopN(Bitmap(frame="f", rowID=40), frame="f", ids={ids}, '
             'tanimotoThreshold=50)')
        for n_ids in (1, 50):
            ids = list(range(65 - n_ids, 65))
            want = [(r, min(r, 40)) for r in ids
                    if 100 * min(r, 40) > 50 * max(r, 40)]
            want.sort(key=lambda p: (-p[1], p[0]))
            before = dict(seen, operands=[])
            assert ex.execute("i", q.format(ids=ids))[0] == want
            # the probe's own leaf stack, one gather, (src, T, rows)
            assert seen["gathers"] - before["gathers"] == 1
            assert seen["stacks"] - before["stacks"] <= 1
            assert seen["operands"][-1] == 3
        ex.TOPN_GATHER_MAX_ROWS = 0
        ids = list(range(10, 60))
        before = dict(seen)
        ex.execute("i", q.format(ids=ids))
        assert seen["gathers"] == before["gathers"]
        assert seen["stacks"] - before["stacks"] == 50 + 1
        assert seen["operands"][-1] == 2 + 64
    finally:
        Fragment.device_rows_win = gather
        h.close()


# ------------------------------------------ spans, tags and counters

def _post(s, path, body):
    req = urllib.request.Request(f"http://{s.host}{path}", data=body.encode(),
                                 method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


@pytest.fixture
def server(tmp_path, request):
    """Forty rows of sixty columns, rows 0..19 near copies of row 0;
    ``indirect`` parametrisation gives the number of slices that hold
    them (the same rows again, a slice further on)."""
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    _post(s, "/index/i", "{}")
    _post(s, "/index/i/frame/f", "{}")
    rng = np.random.default_rng(7)
    frame = s.holder.index("i").frame("f")
    for row in range(40):             # rows 0..19 near copies of row 0
        cols = (np.arange(60) if row < 20
                else rng.choice(4096, 60, replace=False))
        cols = cols[rng.permutation(60)[:58]]
        for sl in range(getattr(request, "param", 1)):
            frame.import_bits([row] * 58, cols + sl * SLICE_WIDTH)
    yield s
    s.close()


PHASE_SPANS = {"serial": {"top.src", "top.kernel", "top.wait", "top.fetch",
                          "top.select"},
               "batched": {"kernel.dispatch", "kernel.wait", "kernel.fetch"}}


def _profiled(server, path):
    """One profiled TopN under a pinned path: (pairs, spans, the two
    phase spans by name, an ``under(span, phase)`` test, resources)."""
    server.executor._force_path = path
    doc = _post(server, "/index/i/query?profile=true", TOPN.format(p=0, t=70))
    pairs = doc["results"][0]
    assert 1 < len(pairs) <= 20 and pairs[0]["id"] == 0
    spans = doc["profile"]["spans"]
    by_id = {sp["spanId"]: sp for sp in spans}
    phases = {sp["name"]: sp for sp in spans if sp["name"].startswith("topn.")
              and sp["name"] not in ("topn.stacks", "topn.mask")}
    assert set(phases) == {"topn.phase1", "topn.phase2"}
    call = next(sp for sp in spans if sp["name"] == "call:TopN")
    for sp in phases.values():
        assert sp["parentId"] == call["spanId"]
    assert phases["topn.phase1"]["tags"] == {
        "path": path, "candidates": 0, "bucket": 1}

    def under(sp, phase):
        while sp is not None and sp is not phase:
            sp = by_id.get(sp["parentId"])
        return sp is phase

    return pairs, spans, phases, under, doc["profile"]["resources"]


@pytest.mark.parametrize("server", [2], indirect=True)
@pytest.mark.parametrize("path", ["serial", "batched"])
def test_a_profiled_topn_carries_phases_spans_and_counters(server, path):
    """Two slices: phase 1 runs tagged with the path that served it.
    The serial phase 2 runs the recount; after the batched phase 1 the
    recount is read from the count matrix phase 1 fetched, and its span
    stays where the second pass would have been, tagged ``derived``,
    with nothing under it."""
    pairs, spans, phases, under, res = _profiled(server, path)
    n = len(pairs)
    assert phases["topn.phase2"]["tags"] == {
        "path": "derived" if path == "batched" else path,
        "candidates": n, "bucket": Executor._candidate_bucket(n)}
    names = {sp["name"] for sp in spans if under(sp, phases["topn.phase1"])}
    assert PHASE_SPANS[path] <= names, names
    names = {sp["name"] for sp in spans if under(sp, phases["topn.phase2"])}
    if path == "serial":
        assert PHASE_SPANS[path] <= names, names
        assert (res["topnRecountsRun"], res["topnRecountsDerived"]) == (1, 0)
    else:
        assert names == {"topn.phase2"}
        assert (res["topnRecountsRun"], res["topnRecountsDerived"]) == (0, 1)
    if path == "serial":
        assert all(sp["tags"]["rows"] == 40 for sp in spans
                   if sp["name"] == "top.src")
        # Phase 1 (n set, no ids) selects inside the scan's program and
        # the host orders the pairs that came back; the explicit-ids
        # re-query selects over a count a row, on the host.
        for name, where, rows in (("topn.phase1", "device", n),
                                  ("topn.phase2", "host", 40)):
            tags = [sp["tags"] for sp in spans if sp["name"] == "top.select"
                    and under(sp, phases[name])]
            assert tags == [{"rows": rows, "where": where}] * 2, name
        assert (res["topnSelectDevice"], res["topnSelectHost"],
                res["topnSelectOverflow"]) == (2, 2, 0)
    else:
        stacks = [sp for sp in spans if sp["name"] == "topn.stacks"]
        assert [sp["tags"]["candidates"] for sp in stacks] == [40]
        # Phase 1's host mask over both slices' 40 cached rows; n = 50
        # cuts no slice.
        masks = [sp for sp in spans if sp["name"] == "topn.mask"]
        assert [sp["tags"] for sp in masks] == [
            {"slices": 2, "candidates": 40, "cut": 0}]
        assert under(masks[0], phases["topn.phase1"])
        assert (res["topnSlicesScanned"], res["topnSlicesLive"]) == (2, 2)
    assert res["topnCandidates"] == n and res["topnKept"] == n
    assert res["topnRecountsSkipped"] == 0
    # A scan reads every row of a fragment; the batched program the
    # candidates it was given, a slice each, in phase 1 alone.
    assert res["topnRowsScanned"] == 2 * (80 if path == "serial" else 40)
    # A derived phase 2 is the batched matrix's answer, and counts so.
    assert res["servedBy"] == {path: 2}


@pytest.mark.parametrize("path", ["serial", "batched"])
def test_a_profiled_one_slice_topn_skips_the_recount(server, path):
    """One slice: phase 1's pairs are the answer. The ``topn.phase2``
    span stays where the second pass would have been, tagged
    ``skipped``, with nothing under it; a pinned path changes nothing
    about that."""
    pairs, spans, phases, under, res = _profiled(server, path)
    n = len(pairs)
    skipped = phases["topn.phase2"]
    assert skipped["tags"] == {"path": "skipped", "candidates": n,
                               "bucket": Executor._candidate_bucket(n)}
    assert [sp["name"] for sp in spans if under(sp, skipped)] \
        == ["topn.phase2"]
    names = {sp["name"] for sp in spans
             if under(sp, phases["topn.phase1"])}
    assert PHASE_SPANS[path] <= names
    stacks = [sp for sp in spans if sp["name"] == "topn.stacks"]
    assert [sp["tags"]["candidates"] for sp in stacks] \
        == ([40] if path == "batched" else [])
    assert res["topnRecountsSkipped"] == 1 and res["topnCandidates"] == 0
    assert res["topnKept"] == n and res["topnRowsScanned"] == 40
    assert res["servedBy"] == {path: 1}


@pytest.mark.parametrize("server", [1, 2], indirect=True)
def test_unprofiled_topn_pays_for_none_of_it(server, monkeypatch):
    """With no trace active every span of the TopN path is the shared
    no-op, the fragment's launch cut into ``top.kernel`` / ``top.wait``
    / ``top.fetch`` included, and no counter is kept: whether the
    second phase runs (two slices) or is skipped (one)."""
    made, split = [], []
    real_span, real_init = tracing.span, tracing.Span.__init__

    def spy(name, **tags):
        sp = real_span(name, **tags)
        made.append((name, sp is tracing.NOP_SPAN))
        return sp

    monkeypatch.setattr(tracing, "span", spy)
    monkeypatch.setattr(
        tracing.Span, "__init__",
        lambda self, *a, **k: (split.append(a), real_init(self, *a, **k))[1])
    monkeypatch.setattr(querystats.QueryStats, "add",
                        lambda *a, **k: split.append(a))
    server.executor._force_path = "serial"
    out = _post(server, "/index/i/query", TOPN.format(p=0, t=70))
    assert out["results"][0][0]["id"] == 0 and "profile" not in out
    names = [n for n, _ in made]
    assert {"topn.phase1", "topn.phase2", "top.src", "top.select"} \
        <= set(names)
    assert {"top.kernel", "top.wait", "top.fetch"} <= set(names)
    assert all(nop for _, nop in made) and not split


def test_the_selection_memos_follow_rows_and_cache(tmp_path):
    """``Fragment.top`` keeps the rows' id array and their cache
    membership between queries: a row that arrives, and a row the
    ranked cache drops, change the next answer."""
    import os

    from pilosa_tpu.storage.fragment import Fragment, TopOptions

    f = Fragment(os.path.join(str(tmp_path), "frag"), "i", "f", "standard",
                 0).open()
    try:
        f.import_bits([0, 0, 0, 1, 1, 2], [1, 2, 3, 1, 2, 1])
        src = np.array(f.row_words(0))
        top = lambda: f.top(TopOptions(src=src))
        assert top() == [(0, 3), (1, 2), (2, 1)]
        ids, mask = f._phys_arr[1], f._cache_mask[2]
        assert top() == [(0, 3), (1, 2), (2, 1)]
        assert f._phys_arr[1] is ids and f._cache_mask[2] is mask
        for col in (1, 2, 3):                       # a new row, a copy of 0
            f.set_bit(7, col)
        assert top() == [(0, 3), (7, 3), (1, 2), (2, 1)]
        f.cache.bulk_add(1, 0)                      # row 1 leaves the cache
        assert top() == [(0, 3), (7, 3), (2, 1)]
        assert f._cache_mask[2] is not mask
    finally:
        f.close()


# ------------------------- one slice: phase 1's pairs are the answer

ROWS = 64          # row d of frame "f" holds columns 0..d-1
PROBE = 20         # so against the probe: inter min(d, 20), denom max(d, 20)
SRC = f'Bitmap(frame="f", rowID={PROBE})'
INV_SRC = 'Bitmap(frame="inv", columnID=5)'


def _prefix_rows(path, slices=1):
    """(holder, executor, {frame: {row: columns}}, {row: cat}): prefix
    rows 1..64, rows 100 and 101 copies of row 64 (a count tie at the
    top of a src-less ranking), row 200 a copy of row 20 (the probe's
    own twin), an attribute on every third row, and an inverse-enabled
    frame whose inverse view has rows 0..63; with ``slices`` > 1 the
    same columns again in each further slice of the standard view."""
    h = Holder(str(path / "d")).open()
    idx = h.create_index("i")
    idx.create_frame("f")
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    rows = {d: set(range(d)) for d in range(1, ROWS + 1)}
    rows.update({100: set(range(64)), 101: set(range(64)),
                 200: set(range(PROBE))})
    rows = {r: {c + s * SLICE_WIDTH for c in cs for s in range(slices)}
            for r, cs in rows.items()}
    for name in ("f", "inv"):
        idx.frame(name).import_bits(
            [r for r, cs in rows.items() for _ in cs],
            [c for cs in rows.values() for c in cs])
    ex = Executor(h)
    cats = {}
    for r in rows:
        if r % 3 == 0:
            cats[r] = "x" if r % 2 else "y"
            ex.execute("i", f'SetRowAttrs(frame="f", rowID={r}, '
                            f'cat="{cats[r]}")')
    inverse = {}
    for r, cs in rows.items():
        for c in cs:
            inverse.setdefault(c, set()).add(r)
    assert idx.max_slice() == slices - 1 and idx.max_inverse_slice() == 0
    return h, ex, {"f": rows, "inv": inverse}, cats


@pytest.fixture(scope="module")
def one_slice(tmp_path_factory):
    h, *rest = _prefix_rows(tmp_path_factory.mktemp("one_slice"))
    yield rest
    h.close()


def brute_topn(rows, src=None, n=0, tanimoto=0, threshold=0, allowed=None):
    """TopN's semantics on Python sets: exact counts, the integer
    Tanimoto gate, ``threshold``, the attribute filter, ``(-count,
    id)`` order, cut at ``n``."""
    pairs = []
    for rid, cs in rows.items():
        cnt = len(cs & src) if src is not None else len(cs)
        if cnt < max(threshold, 1):
            continue
        if allowed is not None and rid not in allowed:
            continue
        if tanimoto and src is not None and not (
                100 * cnt > tanimoto * (len(cs) + len(src) - cnt)):
            continue
        pairs.append((rid, cnt))
    pairs.sort(key=lambda rc: (-rc[1], rc[0]))
    return pairs[:n] if n else pairs


# (id, PQL with {ids} where an ``ids=[...], `` argument goes, n, the
# brute-force arguments, a row that must NOT be in the answer though it
# lies exactly on the gate, the ids of the count tie the cut at n splits)
FORMS = [
    ("tanimoto50", f'TopN({SRC}, frame="f", {{ids}}n=10, '
                   'tanimotoThreshold=50)',
     10, dict(src=PROBE, tanimoto=50), (10, 40), range(20, 40)),
    ("tanimoto70", f'TopN({SRC}, frame="f", {{ids}}n=5, '
                   'tanimotoThreshold=70)',
     5, dict(src=PROBE, tanimoto=70), (14,), range(20, 29)),
    ("tanimoto90", f'TopN({SRC}, frame="f", {{ids}}n=2, '
                   'tanimotoThreshold=90)',
     2, dict(src=PROBE, tanimoto=90), (18,), (20, 21, 22, 200)),
    ("src", f'TopN({SRC}, frame="f", {{ids}}n=7)',
     7, dict(src=PROBE), (), range(20, 65)),
    ("srcless", 'TopN(frame="f", {ids}n=2)', 2, dict(), (), (64, 100, 101)),
    ("threshold", 'TopN(frame="f", {ids}n=4, threshold=60)',
     4, dict(threshold=60), (59,), ()),
    ("src_threshold", f'TopN({SRC}, frame="f", {{ids}}n=40, threshold=18)',
     40, dict(src=PROBE, threshold=18), (17,), ()),
    ("filters", f'TopN({SRC}, frame="f", {{ids}}n=6, field="cat", '
                'filters=["x"])',
     6, dict(src=PROBE, allowed="x"), (24,), (21, 27, 33, 39, 45, 51, 57)),
    ("n0", f'TopN({SRC}, frame="f", {{ids}}tanimotoThreshold=50)',
     0, dict(src=PROBE, tanimoto=50), (10, 40), ()),
    ("inverse", 'TopN(frame="inv", {ids}n=3, inverse=true)',
     3, dict(frame="inv"), (), ()),
    ("inverse_src", f'TopN({INV_SRC}, frame="inv", {{ids}}n=4, '
                    'inverse=true)',
     4, dict(frame="inv", src="inv5"), (), range(6)),
    ("empty", f'TopN({SRC}, frame="f", {{ids}}n=5, tanimotoThreshold=100, '
              'threshold=21)',
     5, dict(src=PROBE, tanimoto=100, threshold=21), (20, 200), ()),
    ("empty_probe", 'TopN(Bitmap(frame="f", rowID=999), frame="f", '
                    '{ids}n=5)',
     5, dict(src=999), (), ()),
]


@pytest.mark.parametrize("path", [None, "serial", "batched"])
@pytest.mark.parametrize("name, pql, n, ref, on_gate, tie", FORMS,
                         ids=[f[0] for f in FORMS])
def test_one_slice_answers_equal_the_two_phase_result(
        one_slice, name, pql, n, ref, on_gate, tie, path):
    """Every form TopN takes, on one slice: the answer (phase 1's
    pairs) equals the brute-force list, and equals what the same call
    followed by an explicit ``ids=`` re-query of its ids gives (the
    two-phase result, which ran until PR 27), as lists."""
    ex, data, cats = one_slice
    ex._force_path = path
    ref = dict(ref)
    rows = data[ref.pop("frame", "f")]
    if "src" in ref:
        # A probe row of "f"; or, on the inverse view, its row 5 (the
        # standard rows that hold column 5).
        ref["src"] = (data["inv"][5] if ref["src"] == "inv5"
                      else data["f"].get(ref["src"], set()))
    if "allowed" in ref:
        ref["allowed"] = {r for r, c in cats.items() if c == ref["allowed"]}
    want = brute_topn(rows, n=n, **ref)

    with querystats.scope(querystats.QueryStats()) as qs:
        got = ex.execute("i", pql.format(ids=""))[0]
        stats = qs.to_dict()
    assert got == want
    assert stats["topnCandidates"] == 0
    assert stats["topnRecountsSkipped"] == (1 if want else 0)
    assert stats["topnKept"] == len(want)

    ids = sorted(rid for rid, _ in got)
    two_phase = []
    if ids:
        two_phase = ex.execute("i", pql.format(ids=f"ids={ids}, "))[0]
        two_phase = two_phase[:n] if n else two_phase
    assert got == two_phase

    # What the case is there for: rows exactly on the gate are out, the
    # cut at n falls inside a count tie, the empty cases are empty.
    got_ids = [rid for rid, _ in got]
    assert not set(on_gate) & set(got_ids)
    if tie:
        kept = set(tie) & set(got_ids)
        assert kept and kept != set(tie)
        assert got_ids[-1] == sorted(tie)[len(kept) - 1]
    assert (got == []) == name.startswith("empty")


def test_two_slices_keep_the_exact_requery(tmp_path):
    """The condition is the slice count: over two slices a row cut from
    one slice's top ``n`` comes back with its whole total, which only
    phase 2 can know. Row 2 is fourth of slice 0 (cut at n=3) and
    second of slice 1: 1 + 3 puts it ahead of row 0's 3 + 0. The
    batched phase 1 fetched that total before its cut, so phase 2 is
    read from its matrix (the default path's first query is batched);
    after the serial phase 1 the recount runs."""
    h = Holder(str(tmp_path / "d")).open()
    try:
        h.create_index("i").create_frame("f")
        frame = h.index("i").frame("f")
        w = SLICE_WIDTH
        frame.import_bits([9] * 8, [0, 1, 2, 3, w, w + 1, w + 2, w + 3])
        frame.import_bits([0] * 3, [0, 1, 2])
        frame.import_bits([1] * 2, [0, 1])
        frame.import_bits([2] * 4, [0, w, w + 1, w + 2])
        ex = Executor(h)
        q = 'TopN(Bitmap(frame="f", rowID=9), frame="f", n=3)'
        for path in ("serial", "batched", None):
            ex._force_path = path
            with querystats.scope(querystats.QueryStats()) as qs:
                got = ex.execute("i", q)[0]
                stats = qs.to_dict()
            assert got == [(9, 8), (2, 4), (0, 3)], path
            assert stats["topnRecountsSkipped"] == 0
            # slice 0 gave {9, 0, 1}, slice 1 {9, 2}
            assert stats["topnCandidates"] == 4
            assert (stats["topnRecountsDerived"],
                    stats["topnRecountsRun"]) == (
                (0, 1) if path == "serial" else (1, 0)), path
        # Phase 1 alone has row 2 short (3 of its 4): what a skip here
        # would have answered.
        ex._force_path = "serial"
        short = ex.execute("i", q, opt=ExecOptions(remote=True))[0]
        assert dict(short)[2] == 3 and dict(short)[1] == 2
        # The slice list is the one of the view the call reads: the
        # same index's inverse view has one slice (rows 3 and 4 as its
        # columns), so an inverse TopN is answered by phase 1.
        h.index("i").create_frame("inv", FrameOptions(inverse_enabled=True))
        h.index("i").frame("inv").import_bits([3, 3, 4], [0, w + 7, w + 7])
        for pql, want, skipped in (
                ('TopN(frame="inv", n=2, inverse=true)',
                 [(w + 7, 2), (0, 1)], 1),
                ('TopN(frame="inv", n=2)', [(3, 2), (4, 1)], 0)):
            with querystats.scope(querystats.QueryStats()) as qs:
                assert ex.execute("i", pql)[0] == want
                stats = qs.to_dict()
            assert stats["topnRecountsSkipped"] == skipped
            # No src: phase 1 reads the caches' counts, fetches no
            # matrix, and the recount over two slices runs.
            assert stats["topnRecountsRun"] == 1 - skipped
            assert stats["topnRecountsDerived"] == 0
    finally:
        h.close()


# ------------- the probe is a row of the matrix the scan reads (PR 29)

@pytest.fixture(scope="module", params=[1, 3], ids=["1slice", "3slices"])
def prefix_index(request, tmp_path_factory):
    """``_prefix_rows`` on one fragment and on three slices."""
    h, *rest = _prefix_rows(tmp_path_factory.mktemp("prefix_index"),
                            request.param)
    yield (*rest, request.param)
    h.close()


def _host_twin(pql, other_frame=None):
    """The same TopN with its child dressed so that it is executed to
    host words: a ``Union`` of the one ``Bitmap``, or the ``Bitmap`` of
    another frame that holds the same row."""
    child = re.match(r"TopN\((Bitmap\([^)]*\))", pql).group(1)
    twin = (f"Union({child})" if other_frame is None
            else child.replace('frame="f"', f'frame="{other_frame}"'))
    assert twin != child
    return pql.replace(child, twin, 1)


def _run(ex, pql):
    with querystats.scope(querystats.QueryStats()) as qs:
        return ex.execute("i", pql)[0], qs.to_dict()


def _check_probe_counters(path, scans, new, twin, present=True):
    """``scans`` per-fragment scans a query: on the serial path each
    took its probe from the mirror (the twin: from host words) and read
    as many row blocks either way; a probe row the fragment lacks is
    looked up and nothing is scanned for it (through host words: every
    row against zeros). No query of the new shape ever builds host
    words, whatever path served it."""
    assert new["topnProbeFromHost"] == 0 and twin["topnProbeFromMirror"] == 0
    if path == "serial":
        assert new["topnProbeFromMirror"] == twin["topnProbeFromHost"] == scans
        assert new["blocks"] == twin["blocks"] == scans
        assert twin["topnRowsScanned"] > 0
        assert new["topnRowsScanned"] == (twin["topnRowsScanned"]
                                          if present else 0)
    elif path == "batched":
        assert new["topnProbeFromMirror"] == twin["topnProbeFromHost"] == 0


SRC_FORMS = [f for f in FORMS if "Bitmap(" in f[1]]


@pytest.mark.parametrize("path", [None, "serial", "batched"])
@pytest.mark.parametrize("name, pql, n, ref, on_gate, tie", SRC_FORMS,
                         ids=[f[0] for f in SRC_FORMS])
def test_a_probe_from_the_mirror_equals_the_host_path(
        prefix_index, name, pql, n, ref, on_gate, tie, path):
    """Every form of TopN with a src whose child is a ``Bitmap`` of the
    fragment it scans: the answer equals the brute-force list, the same
    query with the child wrapped in a ``Union`` and, where a second
    frame holds the same row, with that frame's ``Bitmap`` (both
    executed to host words, as every child was until PR 29)."""
    ex, data, cats, slices = prefix_index
    ex._force_path = path
    ref = dict(ref)
    inverse = ref.pop("frame", "f") == "inv"
    if inverse:
        # One inverse slice, whose rows are the columns of every slice.
        rows, copies = data["inv"], 1
        ref["src"] = data["inv"][5]
    else:
        # The slices hold the same columns, so each gives the same
        # pairs under its own gate and threshold, and the totals are
        # those pairs' counts times the slices.
        rows = {r: {c for c in cs if c < SLICE_WIDTH}
                for r, cs in data["f"].items()}
        copies = slices
        ref["src"] = rows.get(ref["src"], set())
    if "allowed" in ref:
        ref["allowed"] = {r for r, c in cats.items() if c == ref["allowed"]}
    want = [(r, c * copies) for r, c in brute_topn(rows, n=n, **ref)]

    pql = pql.format(ids="")
    got, new = _run(ex, pql)
    assert got == want
    via_host, twin = _run(ex, _host_twin(pql))
    assert via_host == want
    # phase 1 scans each fragment once; over several slices the exact
    # re-query scans each again
    scans = copies * (2 if want and copies > 1 else 1)
    _check_probe_counters(path, scans, new, twin,
                          present=name != "empty_probe")
    if 'frame="f", rowID=20' in pql:
        other, stats = _run(ex, _host_twin(pql, other_frame="inv"))
        assert other == want
        _check_probe_counters(path, scans, new, stats)

    got_ids = [rid for rid, _ in got]
    assert not set(on_gate) & set(got_ids)
    if tie:
        kept = set(tie) & set(got_ids)
        assert kept and kept != set(tie)


def _small_index(tmp_path, cols_of):
    h = Holder(str(tmp_path / "d")).open()
    h.create_index("i").create_frame("f")
    h.index("i").frame("f").import_bits(
        [r for r, cs in cols_of.items() for _ in cs],
        [c for cs in cols_of.values() for c in cs])
    return h, Executor(h)


def _write(ex, rows, kind, row, col):
    ex.execute("i", f'{kind}(frame="f", rowID={row}, columnID={col})')
    (rows.setdefault(row, set()).add if kind == "SetBit"
     else rows[row].discard)(col)


def _probe_grows(ex, rows, frag):
    _write(ex, rows, "SetBit", 20, 40)


def _probe_shrinks(ex, rows, frag):
    _write(ex, rows, "ClearBit", 20, 3)


def _probe_is_new(ex, rows, frag):
    for col in range(12):
        _write(ex, rows, "SetBit", 77, col)


def _probe_widens_the_window(ex, rows, frag):
    # the last column of the slice: the window grows to full width
    assert frag._w64 < SLICE_WIDTH // 64
    _write(ex, rows, "SetBit", 20, SLICE_WIDTH - 1)
    assert (frag._w64_base, frag._w64) == (0, SLICE_WIDTH // 64)


def _fragment_is_evicted(ex, rows, frag):
    frag.unload()
    assert not frag._resident


@pytest.mark.parametrize("path", [None, "serial", "batched"])
@pytest.mark.parametrize("change, probe", [
    (_probe_grows, 20), (_probe_shrinks, 20), (_probe_is_new, 77),
    (_probe_widens_the_window, 20), (_fragment_is_evicted, 20)],
    ids=["setbit", "clearbit", "new_row", "full_width", "evicted"])
def test_the_mirror_probe_follows_the_fragments_state(tmp_path, change,
                                                      probe, path):
    """What the scan's program reads is the row as it stands: a probe
    written or cleared a moment ago (dirty in the mirror until
    ``device_matrix`` refreshes it), a probe row that did not exist, a
    window grown from narrow to the slice's full width, a fragment the
    governor had evicted. Before and after, mirror and host words give
    the brute-force list, gated and ungated."""
    rows = {d: set(range(d)) for d in range(1, 41)}
    h, ex = _small_index(tmp_path, rows)
    try:
        ex._force_path = path
        frag = h.fragment("i", "f", "standard", 0)
        for step in (None, change):
            if step is not None:
                step(ex, rows, frag)
            for tail, t in (("n=6", 0), ("n=6, tanimotoThreshold=60", 60)):
                pql = (f'TopN(Bitmap(frame="f", rowID={probe}), '
                       f'frame="f", {tail})')
                want = brute_topn(rows, src=rows.get(probe, set()), n=6,
                                  tanimoto=t)
                assert bool(want) == (probe in rows)
                got, new = _run(ex, pql)
                via_host, twin = _run(ex, _host_twin(pql))
                assert got == via_host == want, (step, tail)
                _check_probe_counters(path, 1, new, twin,
                                      present=probe in rows)
    finally:
        h.close()


def test_one_program_for_every_probe_and_threshold(one_slice):
    """The probe's physical index, the threshold, ``min_threshold`` and
    ``n`` are traced, the selection's size is ``n``'s bucket: after the
    first scan of a fragment no probe, no threshold (0, no gate,
    among them) and no ``n`` up to 32 compiles again; nor, with
    explicit ids (the host's selection), after the first gated and the
    first ungated one."""
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.ops import topn as topn_ops

    ex, data, _ = one_slice
    ex._force_path = "serial"
    programs = (topn_ops.tanimoto_select_at,
                topn_ops.tanimoto_masked_counts_at,
                bitops._count_and_rows_at_impl)
    q = 'TopN(Bitmap(frame="f", rowID={p}), frame="f", n={n}{t})'
    every = ", ids=[%s]" % ", ".join(str(r) for r in sorted(data["f"]))
    ex.execute("i", q.format(p=PROBE, n=5, t=", tanimotoThreshold=50"))
    ex.execute("i", q.format(p=PROBE, n=5,
                             t=", tanimotoThreshold=50" + every))
    ex.execute("i", q.format(p=PROBE, n=5, t=every))
    sizes = [fn._cache_size() for fn in programs]
    assert all(sizes)
    for p, t, n in ((7, 70, 5), (33, 90, 1), (64, 1, 32), (200, 50, 17)):
        want = brute_topn(data["f"], src=data["f"][p], n=n, tanimoto=t)
        gated = f", tanimotoThreshold={t}"
        assert ex.execute("i", q.format(p=p, n=n, t=gated))[0] == want
        assert ex.execute("i", q.format(p=p, n=n, t=gated + every))[0][:n] \
            == want
        want = brute_topn(data["f"], src=data["f"][p], n=n)
        assert ex.execute("i", q.format(p=p, n=n, t=""))[0] == want
        assert ex.execute("i", q.format(p=p, n=n, t=every))[0][:n] == want
    assert [fn._cache_size() for fn in programs] == sizes
    assert topn_ops.select_k(32) == 64 < topn_ops.select_k(33) == 128


def test_a_profile_says_where_the_probe_came_from(server):
    """``top.src`` is tagged ``probe``, the profile's ``resources`` and
    ``/debug/vars`` count the scans by it."""
    server.executor._force_path = "serial"
    seen = {}
    for kind, child in (("mirror", 'Bitmap(frame="f", rowID=0)'),
                        ("host", 'Union(Bitmap(frame="f", rowID=0))')):
        doc = _post(server, "/index/i/query?profile=true",
                    f'TopN({child}, frame="f", n=50, tanimotoThreshold=70)')
        seen[kind] = doc["results"][0]
        tags = [sp["tags"] for sp in doc["profile"]["spans"]
                if sp["name"] == "top.src"]
        assert tags == [{"rows": 40, "probe": kind}]
        res = doc["profile"]["resources"]
        assert (res["topnProbeFromMirror"], res["topnProbeFromHost"]) \
            == ((1, 0) if kind == "mirror" else (0, 1))
    assert seen["mirror"] == seen["host"] and len(seen["host"]) > 1
    with urllib.request.urlopen(f"http://{server.host}/debug/vars",
                                timeout=30) as resp:
        totals = json.loads(resp.read())
    assert (totals["topnProbeFromMirror"], totals["topnProbeFromHost"]) \
        == (1, 1)


# ------- the scan's operands have the mirror's shape, not the rows' (PR 34)

# Row ``100 + w`` holds columns 0..w-1; row 7 is a copy of the probe
# (row 110, ten columns). Against the probe a row reads inter min(w, 10)
# over denom max(w, 10): widths 5, 7 and 9 lie exactly on the gates of
# 50, 70 and 90 (100*w == T*10, not kept), and the five rows of ten
# columns or more tie at a count of 10, so a cut at 2 falls inside a tie
# and is decided by id. Nine rows leave 7 of the mirror's 16 padded with
# zeros; sixteen fill it.
MIRROR_PROBE = 110
MIRROR_WIDTHS = {9: (3, 5, 7, 9, 10, 12, 14, 20),
                 16: (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 14, 15, 20)}


def _mirror_rows(n_rows):
    rows = {100 + w: set(range(w)) for w in MIRROR_WIDTHS[n_rows]}
    rows[7] = set(range(10))
    assert len(rows) == n_rows
    return rows


def _open_fragment(path, rows):
    import os

    from pilosa_tpu.storage.fragment import Fragment

    f = Fragment(os.path.join(str(path), "frag"), "i", "f", "standard",
                 0).open()
    f.import_bits([r for r, cs in rows.items() for _ in cs],
                  [c for cs in rows.values() for c in cs])
    return f


@pytest.fixture(scope="module", params=[9, 16], ids=["9of16", "16of16"])
def mirror_fragment(request, tmp_path_factory):
    rows = _mirror_rows(request.param)
    f = _open_fragment(tmp_path_factory.mktemp("mirror_fragment"), rows)
    assert (len(f._phys_rows), f._cap) == (request.param, 16)
    yield f, rows
    f.close()


@pytest.fixture
def scan_operands(monkeypatch):
    """What ``Fragment.top`` hands ``fetch_counts`` and gets back, a
    call: (matrix, the other operands, the counts)."""
    from pilosa_tpu.ops import topn as topn_ops

    calls, real = [], topn_ops.fetch_counts

    def spy(fn, matrix, *args, **kw):
        counts = real(fn, matrix, *args, **kw)
        calls.append((matrix, args, counts))
        return counts

    monkeypatch.setattr(topn_ops, "fetch_counts", spy)
    return calls


# (id, TopOptions' arguments, brute_topn's): ``n`` below and above the
# survivors; explicit row ids are never cut at ``n`` (a padded row can
# be asked for by no id); ``min_threshold`` over the counts.
MIRROR_ASKS = [
    ("n2", dict(n=2), dict(n=2)),
    ("n50", dict(n=50), dict(n=50)),
    ("row_ids", dict(n=2, row_ids=[7, 105, 107, 109, 112, 120, 999]),
     dict(allowed={7, 105, 107, 109, 112, 120, 999})),
    ("min_threshold", dict(n=50, min_threshold=9), dict(n=50, threshold=9)),
]


@pytest.mark.parametrize("name, ask, ref", MIRROR_ASKS,
                         ids=[a[0] for a in MIRROR_ASKS])
@pytest.mark.parametrize("gate", [0, 50, 70, 90])
@pytest.mark.parametrize("probe", ["src_row", "src"])
def test_a_scan_of_the_whole_mirror_equals_brute_force(
        mirror_fragment, scan_operands, probe, gate, name, ask, ref):
    """Every form ``Fragment.top`` takes with a src, on a mirror with
    zero rows past the last physical one and on a full one: the list
    equals brute force, the program was given the mirror itself and
    ``_cap`` row counts, and ``topnRowsScanned`` counts the physical
    rows."""
    from pilosa_tpu.storage.fragment import TopOptions

    f, rows = mirror_fragment
    src = ({"src_row": MIRROR_PROBE} if probe == "src_row"
           else {"src": np.array(f.row_words(MIRROR_PROBE))})
    with querystats.scope(querystats.QueryStats()) as qs:
        got = f.top(TopOptions(tanimoto_threshold=gate, **src, **ask))
        stats = qs.to_dict()
    want = brute_topn(rows, src=rows[MIRROR_PROBE], tanimoto=gate, **ref)
    assert got == want and want
    on_gate = 100 + gate // 10
    assert on_gate not in dict(got) and (not gate or on_gate in rows)
    if name == "n2":
        assert [r for r, _ in got] == [7, MIRROR_PROBE]

    (matrix, args, out), = scan_operands
    assert matrix is f._dev
    assert matrix.shape == (f._cap, 2 * f._w64) == (16, 2 * f._w64)
    if name == "row_ids":
        # Explicit ids are selected on the host, over a count a row.
        assert out.shape == (16,) and not out[len(rows):].any()
        if gate:
            assert args[1].shape == (16,) and args[1] is f._rc_dev[1]
        assert stats["topnRowsScanned"] == len(rows)
        return
    # The program selected: 16 counts (a mirror of 16 has no more), 16
    # physical rows, n_ge; the request's scalars in ONE host operand;
    # no row past the last physical one is eligible or returned.
    scalars, row_n, elig = args[-3:]
    assert out.shape == (33,) and out[32] <= 16
    assert isinstance(scalars, np.ndarray) and scalars.dtype == np.int32
    assert list(scalars[1:]) == [gate, ask.get("min_threshold", 0), ask["n"]]
    assert row_n is f._rc_dev[1] and row_n.shape == (16,)
    assert elig is f._elig_dev[1] and elig.shape == (16,)
    assert not np.asarray(elig)[len(rows):].any()
    kept = out[:16] > 0
    assert kept.sum() >= len(got) and (out[16:32][kept] < len(rows)).all()
    assert stats["topnRowsScanned"] == len(rows)


def test_an_appended_row_is_scanned_by_the_program_already_compiled(
        tmp_path, scan_operands):
    """The scan's jit signature is the mirror's capacity: a tenth row
    in a mirror of 16 is seen by the next ``top`` and compiles nothing;
    the row that doubles the mirror to 32 is answered exactly too."""
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.ops import topn as topn_ops
    from pilosa_tpu.storage.fragment import TopOptions

    rows = _mirror_rows(9)
    f = _open_fragment(tmp_path, rows)
    programs = (topn_ops.tanimoto_select_at, topn_ops.tanimoto_select,
                topn_ops.tanimoto_masked_counts_at,
                topn_ops.tanimoto_masked_counts,
                bitops._count_and_rows_at_impl, bitops._count_and_rows_impl)

    def check(cap):
        for gate in (0, 70):
            want = brute_topn(rows, src=rows[MIRROR_PROBE], n=4,
                              tanimoto=gate)
            for src in ({"src_row": MIRROR_PROBE},
                        {"src": np.array(f.row_words(MIRROR_PROBE))}):
                # the device's selection, then the host's over all the
                # counts (an id list that names every row)
                for ids, size in ((None, 2 * cap + 1), (list(rows), cap)):
                    got = f.top(TopOptions(n=4, tanimoto_threshold=gate,
                                           row_ids=ids, **src))
                    assert got[:4] == want
                    matrix, _, out = scan_operands[-1]
                    assert matrix is f._dev and matrix.shape[0] == cap
                    assert out.shape == (size,)

    def append(row, n_cols):
        for col in range(n_cols):
            f.set_bit(row, col)
        rows[row] = set(range(n_cols))

    try:
        check(16)
        sizes = [fn._cache_size() for fn in programs]
        assert all(sizes)
        append(3, 10)                 # a third copy of the probe, lowest id
        assert (len(f._phys_rows), f._cap) == (10, 16)
        check(16)
        assert f.top(TopOptions(n=1, src_row=MIRROR_PROBE)) == [(3, 10)]
        assert [fn._cache_size() for fn in programs] == sizes
        for row in range(300, 307):   # seven more: the 17th doubles it
            append(row, row - 295)
        assert (len(f._phys_rows), f._cap) == (17, 32)
        check(32)
    finally:
        f.close()


def test_a_profile_says_how_many_rows_the_scan_was_given(server,
                                                         monkeypatch):
    """Forty rows in a mirror of 64: ``top.kernel`` is tagged
    ``scanned`` 64, ``top.src`` and the counter still say 40, whether
    the probe came from the mirror or from host words; an unprofiled
    request builds no span for any of it."""
    from pilosa_tpu.ops import topn as topn_ops

    server.executor._force_path = "serial"
    frag = server.holder.fragment("i", "f", "standard", 0)
    for kind, child, program in (
            ("mirror", 'Bitmap(frame="f", rowID=0)',
             topn_ops.tanimoto_select_at.__name__),
            ("host", 'Union(Bitmap(frame="f", rowID=0))',
             topn_ops.tanimoto_select.__name__)):
        doc = _post(server, "/index/i/query?profile=true",
                    f'TopN({child}, frame="f", n=50, tanimotoThreshold=70)')
        tags = {sp["name"]: sp["tags"] for sp in doc["profile"]["spans"]
                if sp["name"].startswith("top.")}
        assert (len(frag._phys_rows), frag._cap) == (40, 64)
        assert tags["top.kernel"] == {"scanned": 64, "program": program}
        assert tags["top.src"] == {"rows": 40, "probe": kind}
        assert program.startswith("pilosa_topn_tanimoto_frag")
        assert tags["top.select"] == {"rows": len(doc["results"][0]),
                                      "where": "device"}
        assert doc["profile"]["resources"]["topnRowsScanned"] == 40
    built = []
    real_init = tracing.Span.__init__
    monkeypatch.setattr(
        tracing.Span, "__init__",
        lambda self, *a, **k: (built.append(a), real_init(self, *a, **k))[1])
    out = _post(server, "/index/i/query", TOPN.format(p=0, t=70))
    assert out["results"][0][0]["id"] == 0 and not built


# ---------- the selection inside the scan's program (PR 36): a kilobyte
# back where a count a row came, and the same LIST as the host's cut

def _scattered_fragment(path, seed, n_rows, bits=24, width=96, **kw):
    """(fragment, {row id: columns}): random rows of a few columns each
    out of ``width`` (so counts tie in droves), with families of copies
    of row 0's columns, written in an order that is not id order."""
    import os

    from pilosa_tpu.storage.fragment import Fragment

    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, 4 * n_rows, 3))[:n_rows]
    rows = {}
    for k, rid in enumerate(ids.tolist()):
        cols = rng.choice(width, rng.integers(1, bits), replace=False)
        if k % 5 == 0 and k:                 # near copies of the first row
            first = sorted(rows[int(ids[0])])
            cols = first[:rng.integers(1, len(first) + 1)]
        rows[rid] = set(int(c) for c in cols)
    f = Fragment(os.path.join(str(path), "frag"), "i", "f", "standard", 0,
                 **kw).open()
    for part in np.array_split(ids, 3):       # an import sorts its rows
        f.import_bits([r for r in part.tolist() for _ in rows[r]],
                      [c for r in part.tolist() for c in rows[r]])
    return f, rows


def _on_the_host(monkeypatch, f, **ask):
    """The same request with the device's selection switched off
    underneath: ``_top_select`` over a count a row."""
    from pilosa_tpu.ops import topn as topn_ops
    from pilosa_tpu.storage.fragment import TopOptions

    with monkeypatch.context() as m:
        m.setattr(topn_ops, "SELECT_MAX_K", 0)
        opt = TopOptions(**ask)
        pairs = f.top(opt)
    assert opt.selected == "host"
    return pairs


@pytest.fixture(scope="module", params=[401, 2_147_484_001],
                ids=["seed401", "seed2147484001"])
def scattered(request, tmp_path_factory):
    f, rows = _scattered_fragment(tmp_path_factory.mktemp("scattered"),
                                  request.param, 700)
    assert f._phys_rows != sorted(f._phys_rows) and f._cap == 1024
    yield f, rows
    f.close()


# (id, TopOptions' arguments, brute_topn's): the cut inside count ties,
# the three gates, ``min_threshold`` above 1, more asked for than
# survive.
SELECT_ASKS = [
    ("n1", dict(n=1), dict(n=1)),
    ("n7", dict(n=7), dict(n=7)),
    ("n32-t50", dict(n=32, tanimoto_threshold=50), dict(n=32, tanimoto=50)),
    ("n50-t70", dict(n=50, tanimoto_threshold=70), dict(n=50, tanimoto=70)),
    ("n50-t90", dict(n=50, tanimoto_threshold=90), dict(n=50, tanimoto=90)),
    ("n20-min5", dict(n=20, min_threshold=5), dict(n=20, threshold=5)),
    ("n9-min3-t50", dict(n=9, min_threshold=3, tanimoto_threshold=50),
     dict(n=9, threshold=3, tanimoto=50)),
    ("n512", dict(n=512), dict(n=512)),
]


@pytest.mark.parametrize("name, ask, ref", SELECT_ASKS,
                         ids=[a[0] for a in SELECT_ASKS])
@pytest.mark.parametrize("probe", ["src_row", "src"])
def test_the_devices_selection_is_the_hosts_list(scattered, monkeypatch,
                                                 probe, name, ask, ref):
    """Physical order is not id order, counts tie across every cut:
    the pairs the device selected, ordered and cut by the host, equal
    ``_top_select``'s list over all the counts and brute force, for a
    probe from the mirror and from host words alike."""
    from pilosa_tpu.storage.fragment import TopOptions

    f, rows = scattered
    for p in [r for r in rows if len(rows[r]) >= 6][:6]:
        src = ({"src_row": p} if probe == "src_row"
               else {"src": np.array(f.row_words(p))})
        opt = TopOptions(**src, **ask)
        got = f.top(opt)
        assert opt.selected in ("device", "overflow")
        assert got == _on_the_host(monkeypatch, f, **src, **ask) \
            == brute_topn(rows, src=rows[p], **ref)
        assert got and got[0][0] in rows
    if name == "n7":
        # a cut that falls inside a tie is decided by id
        counts = [c for _, c in brute_topn(rows, src=rows[p])]
        assert counts[6] == counts[7]


def test_ties_beyond_the_bucket_take_the_hosts_path(tmp_path, monkeypatch):
    """n = 5 selects into 64: with a hundred copies of the probe, 101
    rows tie at the cut, ``n_ge`` says so and the request is answered
    over all the counts, the same list; with sixty the device's 64 hold
    every row of the tie."""
    from pilosa_tpu.ops import topn as topn_ops
    from pilosa_tpu.storage.fragment import TopOptions

    rows = {1000 - r: set(range(12)) for r in range(100)}
    rows.update({r: set(range(r % 11)) | {50 + r} for r in range(1, 300)})
    f = _open_fragment(tmp_path, rows)
    try:
        assert topn_ops.select_k(5) == 64
        for t in (0, 70):
            opt = TopOptions(n=5, src_row=1000, tanimoto_threshold=t)
            assert f.top(opt) == [(r, 12) for r in range(901, 906)] \
                == brute_topn(rows, src=rows[1000], n=5, tanimoto=t)
            assert opt.selected == "overflow"
        for r in range(901, 941):               # forty leave the tie
            f.clear_bit(r, 0)
            rows[r].discard(0)
        opt = TopOptions(n=5, src_row=1000, tanimoto_threshold=70)
        assert f.top(opt) == [(r, 12) for r in range(941, 946)] \
            == brute_topn(rows, src=rows[1000], n=5, tanimoto=70)
        assert opt.selected == "device"
    finally:
        f.close()


@pytest.mark.parametrize("ask", [
    dict(n=0), dict(n=3, row_ids=[7, 105, 110, 120]),
    dict(n=3, filter_row_ids=[7, 105, 110, 120]), dict(n=513),
], ids=["n0", "row_ids", "filter_row_ids", "n513"])
def test_what_the_device_may_not_cut_stays_on_the_host(mirror_fragment,
                                                       scan_operands, ask):
    """No ``n``, explicit ids (never truncated a slice), an attribute
    filter, an ``n`` past the largest bucket: a count a row comes back
    and ``_top_select`` selects."""
    from pilosa_tpu.storage.fragment import TopOptions

    f, rows = mirror_fragment
    opt = TopOptions(src_row=MIRROR_PROBE, tanimoto_threshold=50, **ask)
    got = f.top(opt)
    allowed = ask.get("row_ids") or ask.get("filter_row_ids")
    want = brute_topn(rows, src=rows[MIRROR_PROBE], tanimoto=50,
                      allowed=allowed and set(allowed),
                      n=0 if "row_ids" in ask else ask["n"])
    assert got == want and opt.selected == "host"
    (_, _, counts), = scan_operands
    assert counts.shape == (16,)


def test_a_cache_that_holds_some_rows_names_the_eligible(tmp_path,
                                                         monkeypatch):
    """Only rows in the ranked cache may be returned: the ``elig``
    operand is the host's mask, padded with False to the mirror."""
    from pilosa_tpu.storage.fragment import TopOptions

    f, rows = _scattered_fragment(tmp_path, 77, 300)
    try:
        dropped = sorted(rows)[::3]
        for r in dropped:
            f.cache.bulk_add(r, 0)
        cached = set(rows) - set(dropped)
        for p in (dropped[0], sorted(cached)[0]):
            for t in (0, 50):
                ask = dict(n=40, src_row=p, tanimoto_threshold=t)
                opt = TopOptions(**ask)
                got = f.top(opt)
                assert opt.selected == "device"
                assert got == _on_the_host(monkeypatch, f, **ask) \
                    == brute_topn(rows, src=rows[p], n=40, tanimoto=t,
                                  allowed=cached)
                assert (got or t) and not set(dict(got)) & set(dropped)
        elig = np.asarray(f._elig_dev[1])
        assert elig.shape == (f._cap,) and elig.sum() == len(cached)
        assert not elig[len(rows):].any()
    finally:
        f.close()


def test_elig_follows_the_rows_the_mirror_and_the_cache(tmp_path):
    """The device's copy of the eligibility mask is kept between scans
    and built anew when a row is appended (same mirror), when the
    mirror doubles, and when the cache's membership changes; a bit set
    a moment ago is in the next answer."""
    from pilosa_tpu.storage.fragment import TopOptions

    rows = _mirror_rows(9)
    f = _open_fragment(tmp_path, rows)

    def top(n=4):
        opt = TopOptions(n=n, src_row=MIRROR_PROBE, tanimoto_threshold=70)
        pairs = f.top(opt)
        assert opt.selected == "device"
        assert pairs == brute_topn(rows, src=rows[MIRROR_PROBE], n=n,
                                   tanimoto=70)
        return pairs

    def append(row, n_cols):
        for col in range(n_cols):
            f.set_bit(row, col)
        rows[row] = set(range(n_cols))

    try:
        top()
        elig = f._elig_dev[1]
        top()
        assert f._elig_dev[1] is elig and elig.shape == (16,)
        assert np.asarray(elig).sum() == 9
        append(3, 10)                   # a copy of the probe, lowest id
        assert top(1) == [(3, 10)]
        assert f._elig_dev[1] is not elig and f._elig_dev[1].shape == (16,)
        assert np.asarray(f._elig_dev[1]).sum() == 10
        f.set_bit(114, 50)              # an acknowledged bit, no new row
        rows[114].add(50)
        elig = f._elig_dev[1]
        top()
        assert f._elig_dev[1] is elig
        for row in range(300, 307):     # the 17th row doubles the mirror
            append(row, row - 295)
        assert (len(f._phys_rows), f._cap) == (17, 32)
        top(50)
        assert f._elig_dev[1].shape == (32,)
        assert np.asarray(f._elig_dev[1]).sum() == 17
        f.cache.bulk_add(3, 0)          # row 3 leaves the cache
        elig, gone = f._elig_dev[1], rows.pop(3)
        assert top(1) == [(7, 10)]
        assert f._elig_dev[1] is not elig
        rows[3] = gone
    finally:
        f.close()


def test_the_chunked_selection_on_a_mirror_of_32768(tmp_path, monkeypatch):
    """More than ``k`` chunks of 128 rows: the program takes the
    chunks' maxima first and sorts ``k`` chunks only. 20,000 rows in id
    order reversed, families across chunk boundaries, the cut inside
    ties: the host's list."""
    from pilosa_tpu.ops import topn as topn_ops
    from pilosa_tpu.storage.fragment import TopOptions

    rng = np.random.default_rng(5)
    rows = {}
    for k in range(20_000):
        rid = 40_000 - k
        width = int(rng.integers(1, 9))
        start = int(rng.integers(0, 40))
        rows[rid] = set(range(start, start + width))
        if k % 97 == 0:
            rows[rid] = set(range(8, 20))          # copies of the probe
    rows[5] = set(range(8, 20))
    f = _open_fragment(tmp_path, rows)
    try:
        assert f._cap == 32_768 > topn_ops.SELECT_CHUNK * 128
        for ask, ref in ((dict(n=50), dict(n=50)),
                         (dict(n=50, tanimoto_threshold=50),
                          dict(n=50, tanimoto=50)),
                         (dict(n=3, tanimoto_threshold=90),
                          dict(n=3, tanimoto=90)),
                         (dict(n=64, min_threshold=8),
                          dict(n=64, threshold=8))):
            opt = TopOptions(src_row=5, **ask)
            got = f.top(opt)
            assert got == _on_the_host(monkeypatch, f, src_row=5, **ask) \
                == brute_topn(rows, src=rows[5], **ref)
            # 208 rows tie with the probe, more than any of the buckets
            # (64, 128) holds
            assert opt.selected == "overflow"
        for rid in [r for r in rows if r != 5 and rows[r] == rows[5]][60:]:
            f.clear_bit(rid, 8)
            rows[rid].discard(8)
        opt = TopOptions(src_row=5, n=50, tanimoto_threshold=50)
        assert f.top(opt) == brute_topn(rows, src=rows[5], n=50, tanimoto=50)
        assert opt.selected == "device"
    finally:
        f.close()


def test_a_profile_says_where_the_selection_ran(server):
    """``top.select`` is tagged ``where`` and ``rows`` = what the host
    sorted; ``resources`` and ``/debug/vars`` count the scans by it; a
    bit set a moment ago is in the device's answer."""
    server.executor._force_path = "serial"
    probe = 'Bitmap(frame="f", rowID=0)'
    asks = (("device", f'TopN({probe}, frame="f", n=50, '
                       'tanimotoThreshold=70)'),
            ("host", f'TopN({probe}, frame="f", n=50, ids=[0, 1, 2, 30], '
                     'tanimotoThreshold=70)'),
            ("host", f'TopN({probe}, frame="f", tanimotoThreshold=70)'),
            ("device", f'TopN({probe}, frame="f", n=1)'))
    seen = {"device": 0, "host": 0, "overflow": 0}
    for where, pql in asks:
        doc = _post(server, "/index/i/query?profile=true", pql)
        pairs = doc["results"][0]
        tags = [sp["tags"] for sp in doc["profile"]["spans"]
                if sp["name"] == "top.select"]
        (tag,) = tags
        assert tag["where"] == where, pql
        assert (len(pairs) <= tag["rows"] <= 40 if where == "device"
                else tag["rows"] == 40), pql
        res = doc["profile"]["resources"]
        assert {k: res["topnSelect" + k.capitalize()] for k in seen} \
            == {**dict.fromkeys(seen, 0), where: 1}
        seen[where] += 1
    _post(server, "/index/i/query",
          "".join(f'SetBit(frame="f", rowID=77, columnID={c})'
                  for c in range(60)))
    doc = _post(server, "/index/i/query?profile=true", asks[0][1])
    assert 77 in [p["id"] for p in doc["results"][0]]
    seen["device"] += 1
    with urllib.request.urlopen(f"http://{server.host}/debug/vars",
                                timeout=30) as resp:
        totals = json.loads(resp.read())
    assert {k: totals["topnSelect" + k.capitalize()] for k in seen} == seen


# --------- many slices: phase 2 read from phase 1's count matrix

MANY = 16          # slices of the standard view; two of the inverse view
HALF = MANY // 2


def _many_slices(path):
    """(holder, executor): over ``MANY`` slices frame ``f`` (the default
    ranked cache) and frame ``c`` (a ranked cache of 4) hold the same 24
    rows a slice, each in 1-12 random columns of the slice's first 64
    (counts tie), rows 30 and 31 near copies of frame ``g``'s row 0
    (one and one-to-four columns short: the Tanimoto gate keeps or drops
    them slice by slice); every third row carries ``cat``. Frame ``h``
    holds rows 0..23 in the first half of the slices and rows no other
    slice holds in each of the second (a candidate set that only the
    first half fits). Frame ``inv`` has rows on both sides of
    ``SLICE_WIDTH``: its inverse view has two slices."""
    h = Holder(str(path / "d")).open()
    rng = np.random.default_rng(20261018)
    idx = h.create_index("i")
    for name in ("f", "g", "h"):
        idx.create_frame(name)
    idx.create_frame("c", FrameOptions(cache_type="ranked", cache_size=4))
    idx.create_frame("inv", FrameOptions(inverse_enabled=True))
    for sl in range(MANY):
        base = sl * SLICE_WIDTH
        src = rng.choice(64, 32, replace=False)
        rows, cols = [0] * 32, (base + src).tolist()
        idx.frame("g").import_bits(rows, cols)
        rows, cols = [], []
        for row in range(24):
            k = int(rng.integers(1, 13))
            rows += [row] * k
            cols += (base + rng.choice(64, k, replace=False)).tolist()
        short = int(rng.integers(1, 5))
        for row, drop in ((30, 1), (31, short)):
            rows += [row] * (32 - drop)
            cols += (base + src[drop:]).tolist()
        for name in ("f", "c"):
            idx.frame(name).import_bits(rows, cols)
        hrows = [r if sl < HALF else 100 * sl + r for r in rows]
        idx.frame("h").import_bits(hrows, cols)
    rows, cols = [], []
    for row in list(range(12)) + list(range(SLICE_WIDTH, SLICE_WIDTH + 12)):
        k = int(rng.integers(4, 24))
        rows += [row] * k
        cols += rng.choice(32, k, replace=False).tolist()
    idx.frame("inv").import_bits(rows, cols)
    ex = Executor(h)
    for row in range(0, 32, 3):
        ex.execute("i", f'SetRowAttrs(frame="f", rowID={row}, '
                        f'cat="{"x" if row % 2 else "y"}")')
    assert idx.max_slice() == MANY - 1 and idx.max_inverse_slice() == 1
    return h, ex


@pytest.fixture(scope="module")
def many_slices(tmp_path_factory):
    h, ex = _many_slices(tmp_path_factory.mktemp("many_slices"))
    yield ex
    h.close()


G0 = 'Bitmap(frame="g", rowID=0)'
# (id, PQL with {ids} where an ``ids=[...], `` argument goes, n, what
# the case is there for)
MANY_FORMS = [
    ("cut_in_tie", f'TopN({G0}, frame="f", {{ids}}n=8)', 8, "tie"),
    ("threshold", f'TopN({G0}, frame="f", {{ids}}n=6, threshold=3)', 6,
     "threshold"),
    ("tanimoto50", f'TopN({G0}, frame="f", {{ids}}n=3, '
                   'tanimotoThreshold=50)', 3, "gate"),
    ("tanimoto90", f'TopN({G0}, frame="f", {{ids}}tanimotoThreshold=90)',
     0, "gate"),
    ("filters", f'TopN({G0}, frame="f", {{ids}}n=3, field="cat", '
                'filters=["x"])', 3, "filter"),
    ("small_cache", f'TopN({G0}, frame="c", {{ids}})', 0, "mask"),
    ("inverse", 'TopN(Bitmap(frame="inv", columnID=5), frame="inv", '
                '{ids}n=3, inverse=true)', 3, "inverse"),
]


def _two_phase(ex, pql, n):
    """Phase 1 alone (a remote subquery skips the recount), then the
    explicit-ids re-query of its ids, then the cut at ``n``: the
    two-phase TopN the derived phase 2 stands in for, and phase 1's
    pairs."""
    phase1 = ex.execute("i", pql.format(ids=""),
                        opt=ExecOptions(remote=True))[0]
    ids = sorted(rid for rid, _ in phase1)
    if not ids:
        return [], phase1
    out = ex.execute("i", pql.format(ids=f"ids={ids}, "))[0]
    return (out[:n] if n else out), phase1


@pytest.mark.parametrize("path", ["batched", "serial"])
@pytest.mark.parametrize("name, pql, n, what", MANY_FORMS,
                         ids=[f[0] for f in MANY_FORMS])
def test_a_derived_phase_2_equals_the_two_phase_result(
        many_slices, name, pql, n, what, path):
    """Over many slices the batched phase 1's count matrix holds the
    exact count of every id it returns in every slice, before its mask
    and cut: the phase 2 read from it equals the two-phase result, as
    lists, for every form. A serial phase 1 fetches no matrix and runs
    the recount."""
    ex = many_slices
    ex._force_path = path
    with querystats.scope(querystats.QueryStats()) as qs:
        got = ex.execute("i", pql.format(ids=""))[0]
        stats = qs.to_dict()
    want, phase1 = _two_phase(ex, pql, n)
    assert got == want and got
    assert (stats["topnRecountsDerived"], stats["topnRecountsRun"]) == (
        (1, 0) if path == "batched" else (0, 1))
    assert stats["topnCandidates"] == len(phase1)
    # What the case is there for.
    short = {rid: c for rid, c in phase1 if c < dict(got).get(rid, 0)}
    counts = [c for _, c in got]
    if what == "tie":
        # A row cut from some slice's top n comes back whole, and the
        # cut at n falls inside a count tie of the whole list.
        full, _ = _two_phase(ex, pql.replace("n=8", "n=0"), 0)
        assert short and full[n - 1][1] == full[n][1]
    elif what == "threshold":
        assert min(counts) >= 3
    elif what == "gate":
        assert {30, 31} & set(dict(got))
    elif what == "filter":
        assert all(rid % 3 == 0 and rid % 2 for rid, _ in got)
    elif what == "mask":
        # No cut: every short count is a slice's nonzero count the
        # cache mask dropped.
        assert short


def _fresh(ex):
    """An executor over the same holder with no memo yet: a memoised
    prelude of the whole slice list would skip the checks that make
    windows."""
    fresh = Executor(ex.holder)
    fresh._force_path = "batched"
    return fresh


def _phase1_passes(ex, monkeypatch):
    """The slices of each batched phase-1 pass that fetched its
    matrix."""
    seen = []
    real = ex._batched_topn_phase1

    def spy(index, call, slices, passes=None):
        out = real(index, call, slices, passes)
        if isinstance(out, list):
            seen.append(len(slices))
        return out

    monkeypatch.setattr(ex, "_batched_topn_phase1", spy)
    return seen


def _half_budget(ex, monkeypatch):
    """A device budget that holds half the slices."""
    real = ex._fits_device_budget
    monkeypatch.setattr(
        ex, "_fits_device_budget",
        lambda rows, padded, width32=None:
        padded <= HALF and real(rows, padded, width32))


@pytest.mark.parametrize("frame, derived", [("f", True), ("c", False),
                                            ("h", False)])
def test_halved_windows_add_their_totals_where_every_id_is_in_both(
        many_slices, monkeypatch, frame, derived):
    """Phase 1 runs as two windows, each fetching its own matrix. Where
    every candidate phase 1 returns is a row of both matrices (frame
    f's caches hold every row with a bit), the recount is the windows'
    totals added. Where one is not, its counts in that half are in no
    matrix (a cache of 4 left it out; frame h's halves hold other rows)
    and the recount runs: the totals added would be short."""
    pql = f'TopN({G0}, frame="{frame}", {{ids}}n=4)'
    want, _ = _two_phase(many_slices, pql, 4)
    ex = _fresh(many_slices)
    _half_budget(ex, monkeypatch)
    seen = _phase1_passes(ex, monkeypatch)
    with querystats.scope(querystats.QueryStats()) as qs:
        got = ex.execute("i", pql.format(ids=""))[0]
        stats = qs.to_dict()
    assert got == want
    assert seen == [HALF, HALF]
    # Read from the caches alone: whether each half's candidates hold
    # every id phase 1 gave.
    ids = {rid for rid, _ in ex.execute(
        "i", pql.format(ids=""), opt=ExecOptions(remote=True))[0]}
    halves = [set().union(*(
        ex.holder.fragment("i", frame, "standard", sl).cache_entry_ids()
        for sl in part)) for part in (range(HALF), range(HALF, MANY))]
    assert all(ids <= half for half in halves) == derived
    assert (stats["topnRecountsDerived"], stats["topnRecountsRun"]) == (
        (1, 0) if derived else (0, 1))


def test_a_fallback_to_serial_mid_list_runs_the_recount(many_slices,
                                                       monkeypatch):
    """Frame h's candidates fit the program over the first half of the
    slices and not over any window of the second: the first window's
    matrix is fetched, the list falls back to the serial walk, and the
    matrix covers half the slices, so the recount runs."""
    pql = f'TopN({G0}, frame="h", {{ids}}n=4)'
    want, _ = _two_phase(many_slices, pql, 4)
    ex = _fresh(many_slices)
    monkeypatch.setattr(ex, "TOPN_CANDIDATES_MAX", 32)
    seen = _phase1_passes(ex, monkeypatch)
    with querystats.scope(querystats.QueryStats()) as qs:
        got = ex.execute("i", pql.format(ids=""))[0]
        stats = qs.to_dict()
    assert got == want
    assert seen == [HALF]
    assert stats["servedBy"]["serial"] >= 1
    assert (stats["topnRecountsDerived"], stats["topnRecountsRun"]) == (0, 1)
