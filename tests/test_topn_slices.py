"""The batched TopN phase 1 over several slices, as a profile reads it:
span ``topn.mask`` (the host's per-slice cache mask and cut at ``n``,
tags ``slices``, ``candidates``, ``cut``) and the counters
``topnSlicesScanned`` / ``topnSlicesLive`` (the slices the pass scanned,
and those whose column of the count matrix is not all zero), per
request and in ``/debug/vars``; an unprofiled request opens no span."""
import json
import urllib.request

import pytest

from pilosa_tpu import SLICE_WIDTH, tracing
from pilosa_tpu.server.server import Server

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
    # One mask, phase 1's: the re-query is given ids and masks nothing.
    assert len(masks) == 1
    assert by_id[masks[0]["parentId"]]["name"] != "topn.phase2"
    candidates = len(set().union(*map(set, F_ROWS.values())))
    assert masks[0]["tags"] == {"slices": 3, "candidates": candidates,
                                "cut": cut}
    res = doc["profile"]["resources"]
    # Slice 2 holds candidates but none of g's bits: scanned, not live.
    assert (res["topnSlicesScanned"], res["topnSlicesLive"]) == (3, 2)
    assert res["servedBy"] == {"batched": 2}
    after = _get(server, "/debug/vars")
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
