"""The taxi cell's own pieces, at sizes a test can hold: the rehearsal
size and the configuration's arithmetic; the generator's fragments
decoded back to the rides; the cube reference against a brute-force
two-phase TopN over the same rides; the served answers of all three
forms against the reference, on the batched and on the serial path; the
two-phase corner (a row cut from one slice's top 10 comes back with its
whole total); the bytes model's arithmetic; and the two controls, which
put in the program's place through the run's own comparison come out
as not correct."""
import io
import itertools
import json
import os
import tarfile

import numpy as np
import pytest

from perfbench import run
from perfbench.datagen import taxi
from perfbench.lib import bytes_model, pql, taxi_bytes_model
from perfbench.lib.serverproc import Client
from perfbench.reference import taxi_topn

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2_147_483_959
Q3 = ('TopN(Intersect({w}, Bitmap(frame="dist_miles", rowID={d})), '
      'frame="passenger_count")')
Q4_TOP10 = 'TopN(Intersect({w}, {pp}), frame="dist_miles", n=10)'
Q4 = ('TopN(Intersect({w}, Bitmap(frame="passenger_count", rowID={p})), '
      'frame="dist_miles")')


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def _config(rehearsal=True):
    config = _json("configs", "nyc-taxi-24m.json")
    if rehearsal:
        config["shape"].update(_json("rehearsal", "nyc-taxi-24m.json")["shape"])
    return config


def _cube(config, seed):
    shape = config["shape"]
    cube = taxi_topn.Cube(shape["slices"], shape["months"],
                          len(shape["passenger_pct"]), shape["distance_rows"])
    rides = [taxi.rides(config, seed, s) for s in range(shape["slices"])]
    for s, r in enumerate(rides):
        cube.add(s, *r)
    return cube, rides


# ---------------------------------------------- sizes and arithmetic

def test_the_rehearsal_is_a_few_slices_over_every_month():
    config = _config()
    shape = config["shape"]
    assert 3 <= shape["slices"] <= 4 and shape["months"] == 24
    assert sum(shape["monthly_rides"]) == shape["rides"]
    assert -(-shape["rides"] // (1 << 20)) == shape["slices"]
    assert shape["packed_bytes"] == (shape["staged_rows"] * shape["slices"]
                                     * bytes_model.SLICE_ROW_BYTES)


def test_the_configuration_adds_up():
    shape = _config(rehearsal=False)["shape"]
    assert len(shape["monthly_rides"]) == shape["months"] == 24
    assert sum(shape["monthly_rides"]) == shape["rides"] == 354_760_000
    assert -(-shape["rides"] // (1 << 20)) == shape["slices"] == 339
    assert sum(shape["passenger_pct"]) == pytest.approx(100.0)
    # 24 month views, the one whole year's view, 10 + 64 value rows.
    views = taxi.stage_queries(_config(rehearsal=False))[0].count("Range(")
    assert views == 25
    assert shape["staged_rows"] == views + 10 + 64 == 99
    assert shape["packed_bytes"] == 99 * 339 * (1 << 17) == 4_398_907_392
    law = taxi._law(_config(rehearsal=False))
    assert law.sum() == pytest.approx(1.0) and len(law) == 64
    assert law[:3].sum() > 0.6 and 0 < law[63] < 1e-6


def test_the_pools_and_the_covers():
    config = _config(rehearsal=False)
    pools = taxi.pools(config)
    assert {k: len(v) for k, v in pools.items()} == {
        "window": 300, "passengers": 10, "passenger_pair": 45,
        "distance": 64}
    assert pools["window"][0] == ('Range(frame="pickup", rowID=0, '
                                  'start="2013-07-01T00:00", '
                                  'end="2013-08-01T00:00")')
    sizes = {taxi.cover_views(config, a, b)
             for a in range(24) for b in range(a + 1, 25)}
    assert sizes == set(range(1, 18))
    # [2013-07, 2015-07): six months, the year 2014, six months.
    assert taxi.cover_views(config, 0, 24) == 13
    stage = taxi.stage_queries(config)
    assert len(stage) == 3 + 3 * 17 + 7 + 5
    assert stage[-6].endswith(f"ids={list(range(64))})")
    assert stage[-1].endswith(f"ids={list(range(10))})")
    # q3 and q4 are one call shape; the path model's looks at the serial
    # path, its 3rd to 11th queries, odd ones, meet both grouped frames.
    shared = [q for q in stage if q.startswith("TopN(")
              and "n=10" not in q and "ids=" not in q]
    looked = {q.rsplit("frame=", 1)[1] for q in shared[3:12:2]}
    assert looked == {'"passenger_count")', '"dist_miles")'}


# ------------------------------------------------------ the generator

def _members(tar):
    with tarfile.open(fileobj=io.BytesIO(tar)) as t:
        return {m.name: t.extractfile(m).read() for m in t.getmembers()}


def _rows_of(data):
    """{row id: bool[2^20]} of a fragment's roaring file, by the
    program's own decoder."""
    from pilosa_tpu.roaring import codec

    blocks, _, _ = codec.deserialize(data)
    out = {}
    for key, block in blocks.items():
        row = out.setdefault(key // 16, np.zeros(1 << 20, dtype=bool))
        at = (key % 16) << 16
        row[at:at + (1 << 16)] = np.unpackbits(
            np.asarray(block).view(np.uint8), bitorder="little")
    return out


@pytest.mark.parametrize("s", [0, 3])
def test_the_generators_fragments_decode_back_to_the_rides(s):
    config = _config()
    month, passenger, distance = taxi.rides(config, SEED, s)
    n = len(month)
    assert n == taxi.columns_in_slice(config, s)
    bounds = taxi.month_bounds(config)
    col = s * (1 << 20) + np.arange(n)
    assert ((bounds[month] <= col) & (col < bounds[month + 1])).all()
    posts = {view: _members(tar)
             for _, view, tar in taxi.slice_posts(config, s, month)}
    for view, member in posts.items():
        rows = _rows_of(member["data"])
        assert set(rows) == {0} and json.loads(member["cache"]) == [0]
        if view == "standard":
            want = np.ones(n, dtype=bool)
        else:
            stamp = view[len("standard_"):]
            want = np.array([f"{taxi.month_start(config, m):%Y%m}"
                             .startswith(stamp) for m in range(24)])[month]
        assert (rows[0][:n] == want).all() and not rows[0][n:].any()
    assert {v for v in posts if len(v) == len("standard_201307")} == {
        f"standard_{taxi.month_start(config, int(m)):%Y%m}"
        for m in np.unique(month)}
    for code in (passenger, distance):
        present, data = taxi.roaring_codes(code)
        rows = _rows_of(data)
        assert sorted(rows) == present == sorted(set(code.tolist()))
        for r, bits in rows.items():
            assert (np.flatnonzero(bits) == np.flatnonzero(code == r)).all()
        # The container type of each header (after the 8-byte cookie and
        # count: key u64, type u16, n u16): a row that passes 4,096
        # columns in a container is bitmaps, any other ARRAYs.
        hdr = np.frombuffer(data, dtype=[("key", "<u8"), ("typ", "<u2"),
                                         ("n", "<u2")],
                            count=int.from_bytes(data[4:8], "little"),
                            offset=8)
        widest = {r: max(np.count_nonzero(code[c:c + (1 << 16)] == r)
                         for c in range(0, n, 1 << 16)) for r in rows}
        for key, typ in zip(hdr["key"], hdr["typ"]):
            assert typ == (2 if widest[int(key) // 16] > 4096 else 1)
        assert {1, 2} <= set(hdr["typ"].tolist())


# ------------------------------------------------------ the reference

def _brute(rides, query, n_slices):
    """Two-phase TopN over the rides themselves, slice by slice."""
    call = pql.parse(query)
    window, filt = call.children[0].children
    start, end = (int(window.args[k][:4]) * 12 + int(window.args[k][5:7])
                  - (2013 * 12 + 7) for k in ("start", "end"))
    leaves = [filt] if filt.name == "Bitmap" else filt.children
    values = {leaf.args["rowID"] for leaf in leaves}
    by_passenger = leaves[0].args["frame"] == "passenger_count"
    n = call.args.get("n", 0)
    kept, per_slice = set(), []
    for s in range(n_slices):
        month, passenger, distance = rides[s]
        group, other = ((distance, passenger) if by_passenger
                        else (passenger, distance))
        keep = (month >= start) & (month < end) & np.isin(other,
                                                          list(values))
        counts = np.bincount(group[keep], minlength=64)
        per_slice.append(counts)
        cached = set(group.tolist())
        pairs = sorted((-int(c), r) for r, c in enumerate(counts)
                       if c > 0 and r in cached)
        kept |= {r for _, r in (pairs[:n] if n else pairs)}
    totals = np.sum(per_slice, axis=0)
    out = sorted((-int(totals[r]), r) for r in kept if totals[r] > 0)
    return [{"id": r, "count": -c} for c, r in (out[:n] if n else out)]


def _queries(config, k):
    """k queries of each form, drawn from the pools."""
    pools = taxi.pools(config)
    rng = np.random.default_rng(5)
    pick = lambda pool: pools[pool][rng.integers(len(pools[pool]))]
    out = []
    for _ in range(k):
        out += [Q3.format(w=pick("window"), d=pick("distance")),
                Q4_TOP10.format(w=pick("window"), pp=pick("passenger_pair")),
                Q4.format(w=pick("window"), p=pick("passengers"))]
    return out


@pytest.fixture(scope="module")
def rehearsal_data():
    config = _config()
    cube, rides = _cube(config, SEED)
    return config, rides, taxi_topn.Reference(config, {"cube": cube})


def test_the_cube_reference_equals_a_brute_force_two_phase_topn(
        rehearsal_data):
    config, rides, reference = rehearsal_data
    queries = _queries(config, 8) + [
        Q4.format(w=taxi.window(config, 0, 24), p=1),
        Q4_TOP10.format(w=taxi.window(config, 23, 24),
                        pp=taxi.pools(config)["passenger_pair"][-1]),
        Q3.format(w=taxi.window(config, 5, 6), d=63)]
    for q in queries:
        assert reference.answer(q) == _brute(rides, q, len(rides)), q
    assert any(len(reference.answer(q)) > 5 for q in queries)


@pytest.mark.parametrize("bad", [
    'TopN(Bitmap(frame="dist_miles", rowID=1), frame="passenger_count")',
    Q4.format(w='Range(frame="pickup", rowID=0, start="2014-01-02T00:00", '
                'end="2014-03-01T00:00")', p=1),
    Q4.format(w=Q3, p=1),
    Q3.format(w='Bitmap(frame="pickup", rowID=0)', d=1),
    'TopN(Intersect(Range(frame="pickup", rowID=0, start="2014-01-01T00:00",'
    ' end="2014-03-01T00:00"), Bitmap(frame="dist_miles", rowID=1)), '
    'frame="dist_miles")',
    Q4.format(w=taxi.window(_config(), 0, 3), p=1).replace(
        'frame="dist_miles")', 'frame="dist_miles", ids=[1, 2])'),
])
def test_the_reference_refuses_what_is_not_the_mix(bad, rehearsal_data):
    with pytest.raises(ValueError):
        rehearsal_data[2].answer(bad)


# ------------------------------------------------------ the served path

@pytest.fixture(scope="module")
def served(tmp_path_factory, rehearsal_data):
    from pilosa_tpu.server.server import Server

    config, _, reference = rehearsal_data
    server = Server(str(tmp_path_factory.mktemp("taxi") / "d"),
                    bind="localhost:0").open()
    client = Client(int(server.host.rsplit(":", 1)[1]))
    data = taxi.load(client, config, SEED, lambda *a, **k: None)
    # The cube the load hands on is the one the rides make again.
    assert (data["cube"].counts.sum(axis=1) == reference.running[:, -1]).all()
    yield server, client, config, reference
    client.close()
    server.close()


def _ask(client, query, profile=False):
    status, body = client.send(
        "POST", "/index/trips/query" + ("?profile=true" if profile else ""),
        query)
    assert status == 200, body[:300]
    return json.loads(body)


@pytest.mark.parametrize("path", ["batched", "serial"])
def test_served_answers_equal_the_reference_for_all_three_forms(served,
                                                                path):
    server, client, config, reference = served
    server.executor._force_path = path
    try:
        for q in _queries(config, 4):
            doc = _ask(client, q, profile=True)
            assert doc["results"][0] == reference.answer(q), q
            served_by = doc["profile"]["resources"]["servedBy"]
            if doc["results"][0]:
                assert served_by == {path: 2}, (q, served_by)
    finally:
        server.executor._force_path = None


def _corner(config, reference):
    """A q4-top10 query whose answer holds a row that some slice cut
    from its own top 10: its phase-1 sum falls short of its total."""
    pools = taxi.pools(config)
    for w, pp in itertools.product(pools["window"][::-1],
                                   pools["passenger_pair"][:9]):
        q = Q4_TOP10.format(w=w, pp=pp)
        want = reference.answer(q)
        short = reference.answer(q, control="no_recount")
        if want != short:
            return q, want, short
    raise AssertionError("no query of the mix cuts a row at a slice")


def test_a_row_cut_from_one_slices_top_10_comes_back_whole(served):
    server, client, config, reference = served
    q, want, short = _corner(config, reference)
    got = _ask(client, q, profile=True)
    assert got["results"][0] == want
    res = got["profile"]["resources"]
    assert res["topnCandidates"] > 10
    mask = next(sp for sp in got["profile"]["spans"]
                if sp["name"] == "topn.mask")
    assert mask["tags"]["cut"] >= 1
    full = {p["id"]: p["count"] for p in want}
    part = {p["id"]: p["count"] for p in short}
    assert any(part.get(r, 0) < c for r, c in full.items())


# --------------------------------------------------------- the controls

@pytest.mark.parametrize("control", [True, "no_recount"])
def test_each_control_in_the_programs_place_is_not_correct(served, control):
    server, client, config, reference = served
    queries = _queries(config, 3) + [_corner(config, reference)[0]]
    picked = [{"pql": q} for q in queries]
    got = [_ask(client, q)["results"][0] for q in queries]
    want = reference.answers(queries)
    assert not run.judge(reference, picked, want, got)
    stand_in = reference.answers(queries, control=control)
    wrong = run.judge(reference, picked, want, stand_in)
    assert wrong and not run.verdict(len(picked), len(wrong), 0)
    assert run.verdict(len(picked), 0, 0)


# ---------------------------------------------------------- bytes model

@pytest.mark.parametrize("query, rows", [
    (Q4.format(w='Range(frame="pickup", rowID=0, start="2013-07-01T00:00", '
                 'end="2015-07-01T00:00")', p=1), 64 + 13 + 1),
    (Q3.format(w='Range(frame="pickup", rowID=0, start="2014-02-01T00:00", '
                 'end="2014-03-01T00:00")', d=7), 10 + 1 + 1),
    (Q4_TOP10.format(w='Range(frame="pickup", rowID=0, '
                       'start="2013-07-01T00:00", end="2014-12-01T00:00")',
                     pp='Union(Bitmap(frame="passenger_count", rowID=1), '
                        'Bitmap(frame="passenger_count", rowID=2))'),
     64 + 17 + 2),
    (Q3.format(w='Range(frame="pickup", rowID=0, start="2014-01-01T00:00", '
                 'end="2015-01-01T00:00")', d=0), 10 + 1 + 1),
])
def test_the_bytes_model_counts_one_scan_of_the_algorithms_rows(query, rows):
    shape = _config(rehearsal=False)["shape"]
    call = pql.parse(query)
    assert taxi_bytes_model.request_rows(call, shape) == rows
    assert taxi_bytes_model.request_bytes(call, shape) \
        == rows * 339 * 131_072
    with pytest.raises(ValueError):
        taxi_bytes_model.grouped_rows(
            pql.parse('TopN(Bitmap(frame="pickup", rowID=0), frame="pickup")'),
            shape)
