"""TopN on the served path (PR 26): the batched re-query costs the
same for one candidate and for fifty, so the path model's one entry a
shape holds, and a ``?profile=true`` TopN answer carries the phases'
spans, tags and counters."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import querystats, tracing
from pilosa_tpu.executor import Executor
from pilosa_tpu.pql import parse
from pilosa_tpu.server.server import Server

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
    _, st, _, _ = e._path_choice(call, list(range(954)))
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
            fn, hit = run(*a)
            return (lambda *args: (seen["operands"].append(len(args)),
                                   fn(*args))[1]), hit

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
def server(tmp_path):
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    _post(s, "/index/i", "{}")
    _post(s, "/index/i/frame/f", "{}")
    rng = np.random.default_rng(7)
    frame = s.holder.index("i").frame("f")
    for row in range(40):             # rows 0..19 near copies of row 0
        cols = (np.arange(60) if row < 20
                else rng.choice(4096, 60, replace=False))
        frame.import_bits([row] * 58, cols[rng.permutation(60)[:58]])
    yield s
    s.close()


PHASE_SPANS = {"serial": {"top.src", "top.kernel", "top.wait", "top.fetch",
                          "top.select"},
               "batched": {"kernel.dispatch", "kernel.wait", "kernel.fetch"}}


@pytest.mark.parametrize("path", ["serial", "batched"])
def test_a_profiled_topn_carries_phases_spans_and_counters(server, path):
    server.executor._force_path = path
    doc = _post(server, "/index/i/query?profile=true", TOPN.format(p=0, t=70))
    pairs = doc["results"][0]
    assert 1 < len(pairs) <= 20 and pairs[0]["id"] == 0
    spans = doc["profile"]["spans"]
    by_id = {sp["spanId"]: sp for sp in spans}
    phases = {sp["name"]: sp for sp in spans if sp["name"].startswith("topn.")
              and sp["name"] != "topn.stacks"}
    assert set(phases) == {"topn.phase1", "topn.phase2"}
    call = next(sp for sp in spans if sp["name"] == "call:TopN")
    for sp in phases.values():
        assert sp["parentId"] == call["spanId"]
    assert phases["topn.phase1"]["tags"] == {
        "path": path, "candidates": 0, "bucket": 1}
    n = len(pairs)
    assert phases["topn.phase2"]["tags"] == {
        "path": path, "candidates": n,
        "bucket": Executor._candidate_bucket(n)}

    def under(sp, phase):
        while sp is not None and sp is not phase:
            sp = by_id.get(sp["parentId"])
        return sp is phase

    for phase in phases.values():
        names = {sp["name"] for sp in spans if under(sp, phase)}
        assert PHASE_SPANS[path] <= names, (phase["name"], names)
    if path == "serial":
        assert all(sp["tags"]["rows"] == 40 for sp in spans
                   if sp["name"] in ("top.src", "top.select"))
    else:
        stacks = [sp for sp in spans if sp["name"] == "topn.stacks"]
        assert [sp["tags"]["candidates"] for sp in stacks] == [40, n]
    res = doc["profile"]["resources"]
    assert res["topnCandidates"] == n and res["topnKept"] == n
    # A scan reads every row of the fragment; the batched program the
    # candidates it was given, a slice each.
    assert res["topnRowsScanned"] == (80 if path == "serial" else 40 + n)
    assert res["servedBy"] == {path: 2}


def test_unprofiled_topn_pays_for_none_of_it(server, monkeypatch):
    """With no trace active every span of the TopN path is the shared
    no-op, no counter is kept, and the fragment's call stays the one
    expression (no split into wait and fetch)."""
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
    assert not {"top.kernel", "top.wait", "top.fetch"} & set(names)
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
