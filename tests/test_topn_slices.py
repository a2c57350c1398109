"""The batched TopN phase 1 over several slices, as a profile reads it:
span ``topn.mask`` (the host's per-slice cache mask and cut at ``n``,
tags ``slices``, ``candidates``, ``cut``) and the counters
``topnSlicesScanned`` / ``topnSlicesLive`` (the slices the pass scanned,
and those whose column of the count matrix is not all zero), per
request and in ``/debug/vars``; an unprofiled request opens no span.

Phase 1 reads the grouped frame's ranked-cache membership from arrays
memoised on its fragment list (``topnMembershipHits`` / ``Misses``) and
masks and cuts in NumPy: on random fragments its pairs equal the
serial per-fragment walk's, and a write that changes a ranked cache is
seen by the next TopN."""
import functools
import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, tracing
from pilosa_tpu.executor import Executor, pairs_add
from pilosa_tpu.pql import parse
from pilosa_tpu.server.server import Server
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions

# Candidates of frame f a slice (row: how many of the slice's first
# columns it holds); frame g's row 0 holds the first 40 columns of slices
# 0 and 1 and nothing of slice 2, where f has rows all the same.
F_ROWS = {0: {1: 30, 2: 20, 3: 10, 4: 5, 5: 1},
          1: {2: 25, 6: 15, 7: 3},
          2: {1: 50, 8: 50}}
TOPN = 'TopN(Bitmap(frame="g", rowID=0), frame="f", n={n})'


def _post(s, path, body):
    req = urllib.request.Request(f"http://{s.host}{path}", data=body.encode(),
                                 method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _get(s, path):
    with urllib.request.urlopen(f"http://{s.host}{path}", timeout=30) as resp:
        return json.loads(resp.read())


@pytest.fixture
def server(tmp_path):
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    _post(s, "/index/i", "{}")
    _post(s, "/index/i/frame/f", '{"options": {"cacheType": "ranked"}}')
    _post(s, "/index/i/frame/g", "{}")
    frame_f = s.holder.index("i").frame("f")
    frame_g = s.holder.index("i").frame("g")
    for sl, rows in F_ROWS.items():
        base = sl * SLICE_WIDTH
        for row, n in rows.items():
            frame_f.import_bits([row] * n, [base + c for c in range(n)])
    for sl in (0, 1):
        frame_g.import_bits([0] * 40, [sl * SLICE_WIDTH + c
                                       for c in range(40)])
    s.executor._force_path = "batched"
    yield s
    s.close()


def _want(n):
    """Two-phase TopN by hand: per slice the rows that meet g's 40
    columns, cut at n by (-count, id); the kept ids counted again over
    every slice."""
    kept = set()
    for sl in (0, 1):
        pairs = sorted((-min(c, 40), r) for r, c in F_ROWS[sl].items())
        kept |= {r for _, r in (pairs[:n] if n else pairs)}
    totals = {r: sum(min(F_ROWS[sl].get(r, 0), 40) for sl in (0, 1))
              for r in kept}
    out = sorted((-c, r) for r, c in totals.items() if c)
    return [{"id": r, "count": -c} for c, r in (out[:n] if n else out)]


@pytest.mark.parametrize("n, cut", [(2, 2), (3, 1), (0, 0)])
def test_a_profiled_multi_slice_topn_reads_its_mask_and_slices(server, n, cut):
    before = _get(server, "/debug/vars")
    doc = _post(server, "/index/i/query?profile=true", TOPN.format(n=n))
    assert doc["results"][0] == _want(n)
    spans = doc["profile"]["spans"]
    masks = [sp for sp in spans if sp["name"] == "topn.mask"]
    by_id = {sp["spanId"]: sp for sp in spans}
    # One mask, phase 1's: the re-query is read from phase 1's matrix
    # and masks nothing.
    assert len(masks) == 1
    assert by_id[masks[0]["parentId"]]["name"] != "topn.phase2"
    candidates = len(set().union(*map(set, F_ROWS.values())))
    assert masks[0]["tags"] == {"slices": 3, "candidates": candidates,
                                "cut": cut}
    res = doc["profile"]["resources"]
    # Slice 2 holds candidates but none of g's bits: scanned, not live.
    assert (res["topnSlicesScanned"], res["topnSlicesLive"]) == (3, 2)
    # Phase 1's matrix holds the recount: both phases are its serves.
    assert res["servedBy"] == {"batched": 2}
    assert (res["topnRecountsDerived"], res["topnRecountsRun"]) == (1, 0)
    after = _get(server, "/debug/vars")
    assert after["topnRecountsDerived"] - before["topnRecountsDerived"] == 1
    assert after["topnSlicesScanned"] - before["topnSlicesScanned"] == 3
    assert after["topnSlicesLive"] - before["topnSlicesLive"] == 2


def test_an_unprofiled_topn_opens_no_mask_span(server, monkeypatch):
    made = []
    real_span = tracing.span

    def spy(name, **tags):
        sp = real_span(name, **tags)
        made.append((name, sp is tracing.NOP_SPAN))
        return sp

    monkeypatch.setattr(tracing, "span", spy)
    before = _get(server, "/debug/vars")
    out = _post(server, "/index/i/query", TOPN.format(n=2))
    assert out["results"][0] == _want(2) and "profile" not in out
    assert ("topn.mask", True) in made
    assert all(nop for name, nop in made if name == "topn.mask")
    # The process totals count whether or not a request was profiled.
    after = _get(server, "/debug/vars")
    assert after["topnSlicesScanned"] - before["topnSlicesScanned"] == 3


def test_a_filter_empty_everywhere_reads_no_live_slice(server):
    doc = _post(server, "/index/i/query?profile=true",
                'TopN(Bitmap(frame="g", rowID=9), frame="f", n=2)')
    assert doc["results"][0] == []
    res = doc["profile"]["resources"]
    assert (res["topnSlicesScanned"], res["topnSlicesLive"]) == (3, 0)


def test_a_profiled_topn_reads_its_membership_lookups(server):
    """A pass builds the grouped frame's membership once; the next pass
    over the same fragment list finds it."""
    before = _get(server, "/debug/vars")
    outs = [_post(server, "/index/i/query?profile=true", TOPN.format(n=n))
            for n in (2, 3)]
    assert [o["results"][0] for o in outs] == [_want(2), _want(3)]
    got = [(o["profile"]["resources"]["topnMembershipHits"],
            o["profile"]["resources"]["topnMembershipMisses"])
           for o in outs]
    assert got == [(0, 1), (1, 0)]
    after = _get(server, "/debug/vars")
    assert after["topnMembershipHits"] - before["topnMembershipHits"] == 1
    assert (after["topnMembershipMisses"]
            - before["topnMembershipMisses"]) == 1


# ------------------------------------ phase 1 against the serial walk

RAND_SLICES = (0, 1, 2, 3, 4)
NO_F_SLICE = 2          # frame g holds bits here, frame f none


@pytest.fixture(scope="module")
def rand_ex(tmp_path_factory):
    """Frame f: 24 rows a slice, each in 1-6 of the slice's first 48
    columns (so counts tie), under a ranked cache of 4 that trims to
    the top 4 once it passes 14 entries: a slice's membership is a few
    of its rows. Frame g's rows 0 and 1 hold random halves of the same
    columns in every slice, slice 2 included. Row attribute ``cat``."""
    h = Holder(str(tmp_path_factory.mktemp("rand") / "d")).open()
    rng = np.random.default_rng(20261017)
    idx = h.create_index("i")
    f = idx.create_frame("f", FrameOptions(cache_type="ranked",
                                           cache_size=4))
    g = idx.create_frame("g")
    for sl in RAND_SLICES:
        base = sl * SLICE_WIDTH
        if sl != NO_F_SLICE:
            rows, cols = [], []
            for row in range(24):
                n = int(rng.integers(1, 7))
                rows += [row] * n
                cols += (base + rng.choice(48, n, replace=False)).tolist()
            f.import_bits(rows, cols)
        for row in (0, 1):
            cols = base + np.flatnonzero(rng.random(48) < 0.5)
            g.import_bits([row] * len(cols), cols.tolist())
    for row in range(24):
        f.row_attr_store.set_attrs(row, {"cat": "x" if row % 3 else "y"})
    ex = Executor(h)
    yield ex
    h.close()


def _serial(ex, call, slices):
    return functools.reduce(
        pairs_add, [ex._execute_topn_slice("i", call, s) for s in slices],
        [])


@pytest.mark.parametrize("src", ['Bitmap(frame="g", rowID=0)',
                                 'Union(Bitmap(frame="g", rowID=0), '
                                 'Bitmap(frame="g", rowID=1))'],
                         ids=["row", "union"])
@pytest.mark.parametrize("args", [
    "n=2", "n=3", "n=1", "",                        # cut, ties at the cut
    "n=2, threshold=3", "threshold=2",             # min_threshold
    'n=2, field="cat", filters=["x"]',             # attribute filter
    'field="cat", filters=["y"]',
    "n=2, tanimotoThreshold=20", "tanimotoThreshold=10",
], ids=["n2", "n3", "n1", "all", "n2-t3", "t2", "n2-x", "y", "tan20-n2",
        "tan10"])
@pytest.mark.parametrize("slices", [RAND_SLICES, (0, 1), (2, 3, 4)],
                         ids=["all", "half", "half-no-f"])
def test_phase1_equals_the_serial_walk(rand_ex, src, args, slices):
    call = parse(f'TopN({src}, frame="f"' + (f", {args}" if args else "")
                 + ")").calls[0]
    got = rand_ex._batched_topn_phase1("i", call, list(slices))
    want = _serial(rand_ex, call, slices)
    assert got == want
    # The serial walk's answer is what the batched pass meets: some
    # candidates, and the cut at n over the cache's few rows.
    if args == "n=2":
        assert want


def test_a_fragment_over_the_candidate_limit_declines_before_a_build(
        rand_ex, monkeypatch):
    """The decline check reads the fragments' cache sizes before any
    membership is built: nothing is kept and nothing counted."""
    monkeypatch.setattr(rand_ex, "TOPN_CANDIDATES_MAX", 2)
    call = parse('TopN(Bitmap(frame="g", rowID=0), frame="f", n=2)').calls[0]
    slices = [0, 3]                     # a list no other test walks
    before = dict(rand_ex.topn_membership)
    assert rand_ex._batched_topn_phase1("i", call, slices) is None
    frags = rand_ex._frag_list("i", "f", "standard", slices)
    assert frags.members is None
    assert rand_ex.topn_membership == before


# --------------------------------- a write reaches the next TopN's mask

@pytest.fixture
def small_ex(tmp_path):
    h = Holder(str(tmp_path / "d")).open()
    idx = h.create_index("i")
    f = idx.create_frame("f", FrameOptions(cache_type="ranked"))
    g = idx.create_frame("g")
    for sl in (0, 1):
        base = sl * SLICE_WIDTH
        f.import_bits([1] * 5 + [2] * 3, [base + c for c in range(5)]
                      + [base + c for c in range(3)])
        g.import_bits([0] * 8, [base + c for c in range(8)])
    f2 = idx.create_frame("f2", FrameOptions(cache_type="ranked"))
    f2.import_bits([7, 7], [0, SLICE_WIDTH])
    ex = Executor(h)
    yield ex
    h.close()


PHASE1 = 'TopN(Bitmap(frame="g", rowID=0), frame="{f}", n=5)'


def _phase1(ex, frame="f"):
    return ex._batched_topn_phase1(
        "i", parse(PHASE1.format(f=frame)).calls[0], [0, 1])


def test_a_setbit_into_a_ranked_cache_is_seen_by_the_next_topn(small_ex):
    assert _phase1(small_ex) == [(1, 10), (2, 6)]
    assert _phase1(small_ex) == [(1, 10), (2, 6)]
    # Row 3 enters slice 1's ranked cache only: the memo of the list
    # that did not hold it must not answer the next pass.
    small_ex.execute("i", 'SetBit(frame="f", rowID=3, '
                     f'columnID={SLICE_WIDTH + 7})')
    assert _phase1(small_ex) == [(1, 10), (2, 6), (3, 1)]
    assert _serial(small_ex, parse(PHASE1.format(f="f")).calls[0],
                   [0, 1]) == [(1, 10), (2, 6), (3, 1)]


def test_recalculating_the_caches_is_seen_by_the_next_topn(small_ex):
    """``recalculate_cache`` rebuilds a ranked cache from the counts (a
    sidecar lost in a crash): membership changes with no bit set, and
    the next pass must not answer from the memo of the old one."""
    frag = small_ex.holder.fragment("i", "f", "standard", 1)
    with frag.mu:
        frag.cache.entries.pop(2)       # slice 1's cache lost row 2
    assert _phase1(small_ex) == [(1, 10), (2, 3)]
    assert _phase1(small_ex) == [(1, 10), (2, 3)]
    frag.recalculate_cache()
    assert _phase1(small_ex) == [(1, 10), (2, 6)]


def test_membership_hits_and_misses_on_a_repeated_and_a_fresh_frame(
        small_ex):
    counted = small_ex.topn_membership
    before = dict(counted)

    def delta():
        return (counted["topnMembershipHits"] - before["topnMembershipHits"],
                counted["topnMembershipMisses"]
                - before["topnMembershipMisses"])

    _phase1(small_ex)
    assert delta() == (0, 1)
    _phase1(small_ex)
    _phase1(small_ex)
    assert delta() == (2, 1)
    assert _phase1(small_ex, "f2") == [(7, 2)]
    assert delta() == (2, 2)
    assert _phase1(small_ex, "f2") == [(7, 2)]
    assert delta() == (3, 2)


def test_concurrent_passes_share_one_list_and_count_every_pass(small_ex):
    """Threads racing the first pass over one fragment list: each may
    build the membership (the same arrays), every answer is right, and
    each pass counts once as a hit or a miss."""
    import sys
    import threading

    counted = small_ex.topn_membership
    before = sum(counted.values())
    want = [(1, 10), (2, 6)]
    got, errors = [], []

    def work():
        try:
            for _ in range(5):
                got.append(_phase1(small_ex))
        except Exception as exc:  # noqa: BLE001 — asserted below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert got == [want] * 60
    assert sum(counted.values()) - before == 60
    assert counted["topnMembershipHits"] >= 48
