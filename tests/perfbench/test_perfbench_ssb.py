"""The Star Schema Benchmark cell's own pieces, at sizes a test can hold:
the cube reference against a row-by-row filter over the same lineorder
values; the generator's bit planes and containers decoded back to those
values; the bytes model's arithmetic; the float32 control, which put in
the program's place through the run's own comparison comes out as not
correct; and the mix's capacity against what it says it was sized for."""
import io
import itertools
import json
import os
import tarfile

import numpy as np
import pytest

from perfbench import run
from perfbench.datagen import ssb
from perfbench.lib import loadgen, pql, sum_bytes_model, sum_layer
from perfbench.reference import ssb_flight1

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SUM = ('Sum(Intersect({dates}, Range(frame="lo", lo_discount {d}), '
       'Range(frame="lo", lo_quantity {q})), frame="lo", '
       'field="lo_revrate")')
YEAR = 'Bitmap(frame="d_year", rowID={})'
MONTH = 'Bitmap(frame="d_yearmonthnum", rowID={})'
WEEK = 'Bitmap(frame="d_weeknuminyear", rowID={}), ' + YEAR


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def _config(rows):
    config = _json("configs", "ssb-sf30-flight1.json")
    config["shape"].update(lineorder_rows=rows, slices=-(-rows // (1 << 20)))
    return config


def _values(config, seed):
    """(day, discount, quantity, price) of every lineorder row, and
    their cube."""
    parts = [ssb.lineorder(config, seed, s)
             for s in range(config["shape"]["slices"])]
    cube = ssb_flight1.Cube(ssb.n_days(config))
    for p in parts:
        cube.add(*p)
    return [np.concatenate(x) for x in zip(*parts)], cube


# ------------------------------------------------------ the reference

QUERIES = [
    (SUM.format(dates=YEAR.format(1993), d=">< [1, 3]", q="< 25"),
     lambda y, ym, w, d, q: (y == 1993) & (d >= 1) & (d <= 3) & (q < 25)),
    (SUM.format(dates=MONTH.format(199401), d=">< [4, 6]", q=">< [26, 35]"),
     lambda y, ym, w, d, q: (ym == 199401) & (d >= 4) & (d <= 6)
     & (q >= 26) & (q <= 35)),
    (SUM.format(dates=WEEK.format(6, 1994), d=">< [5, 7]", q=">< [26, 35]"),
     lambda y, ym, w, d, q: (w == 6) & (y == 1994) & (d >= 5) & (d <= 7)
     & (q >= 26) & (q <= 35)),
    (SUM.format(dates=YEAR.format(1998), d=">< [0, 2]", q="< 2"),
     lambda y, ym, w, d, q: (y == 1998) & (d <= 2) & (q < 2)),
    (SUM.format(dates=WEEK.format(53, 1996), d=">< [8, 10]", q=">< [41, 50]"),
     lambda y, ym, w, d, q: (w == 53) & (y == 1996) & (d >= 8) & (q >= 41)),
    (SUM.format(dates=MONTH.format(199812), d=">< [1, 3]", q="< 50"),
     lambda y, ym, w, d, q: ym == 0),
    (SUM.format(dates=YEAR.format(1992), d="!= 5", q=">= 17"),
     lambda y, ym, w, d, q: (y == 1992) & (d != 5) & (q >= 17)),
    (SUM.format(dates=YEAR.format(1995), d="== 10", q="<= 50"),
     lambda y, ym, w, d, q: (y == 1995) & (d == 10)),
]


@pytest.mark.parametrize("seed", [3, 2_147_483_777])
def test_the_cube_reference_equals_a_row_by_row_filter(seed):
    config = _config(700_000)
    (day, discount, quantity, price), cube = _values(config, seed)
    reference = ssb_flight1.Reference(config, {"cube": cube})
    # The calendar by a third route: numpy's, as the generator takes it.
    attrs = ssb.date_attributes(config)
    for frame, of_day in attrs.items():
        assert (reference.calendar[frame] == of_day).all()
    y, ym, w = (attrs[f][day] for f in
                ("d_year", "d_yearmonthnum", "d_weeknuminyear"))
    revenue = price.astype(object) * discount       # Python integers
    for query, keep in QUERIES:
        rows = np.broadcast_to(keep(y, ym, w, discount, quantity),
                               day.shape)
        want = {"sum": int(sum(revenue[rows])), "count": int(rows.sum())}
        assert reference.answer(query) == want, query
    assert any(reference.answer(q)["count"] > 1000 for q, _ in QUERIES)
    assert reference.answer(QUERIES[5][0]) == {"sum": 0, "count": 0}


@pytest.mark.parametrize("bad", [
    'Count(Bitmap(frame="d_year", rowID=1993))',
    'Sum(Bitmap(frame="d_year", rowID=1993), frame="lo", field="lo_revrate")',
    SUM.format(dates=YEAR.format(1993), d="< 3", q="< 5").replace(
        'field="lo_revrate"', 'field="lo_quantity"'),
    SUM.format(dates="Union(" + YEAR.format(1993) + ")", d="< 3", q="< 5"),
])
def test_the_reference_refuses_what_is_not_flight_1(bad):
    config = _config(1000)
    _, cube = _values(config, 1)
    with pytest.raises(ValueError):
        ssb_flight1.Reference(config, {"cube": cube}).answer(bad)


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


@pytest.mark.parametrize("s, n", [(0, 1 << 20), (1, 300_000)])
def test_the_generators_planes_and_rows_decode_back_to_the_values(s, n):
    config = _config((1 << 20) + 300_000)
    day, discount, quantity, price = values = ssb.lineorder(config, 77, s)
    assert len(day) == n == ssb.rows_in_slice(config, s)
    assert quantity.min() == 1 and quantity.max() == 50
    assert discount.min() == 0 and discount.max() == 10
    assert day.min() == 0 and day.max() == ssb.n_days(config) - 1 == 2405
    attrs = {f: ssb.date_codes(v)
             for f, v in ssb.date_attributes(config).items()}
    posts = {(frame, view): _members(tar)
             for frame, view, tar in ssb.slice_posts(config, values, attrs)}
    assert len(posts) == 6
    measures = {"lo_quantity": quantity, "lo_discount": discount,
                "lo_revrate": price.astype(np.int64) * discount}
    for name, field in config["shape"]["fields"].items():
        depth = ssb.bit_depth(field)
        assert depth == {"lo_quantity": 6, "lo_discount": 4,
                         "lo_revrate": 27}[name]
        rows = _rows_of(posts["lo", "field_" + name]["data"])
        assert set(rows) <= set(range(depth + 1))
        # The exists row at ``depth``: every row of the slice, no other.
        assert rows[depth][:n].all() and not rows[depth][n:].any()
        decoded = sum(rows[i].astype(np.int64) << i
                      for i in range(depth) if i in rows)
        assert (decoded[:n] + field["min"] == measures[name]).all()
        assert not decoded[n:].any()
    for frame, of_day in ssb.date_attributes(config).items():
        member = posts[frame, "standard"]
        rows = _rows_of(member["data"])
        want = of_day[day]
        assert sorted(rows) == sorted(set(want.tolist())) \
            == json.loads(member["cache"])
        for rid, bits in rows.items():
            assert (np.flatnonzero(bits) == np.flatnonzero(want == rid)).all()
    # The month and week rows travel as ARRAY containers, the years and
    # the planes as bitmaps (type at byte 8 + 8 of the first header).
    kinds = {k: int.from_bytes(v["data"][16:18], "little")
             for k, v in posts.items()}
    assert kinds == {("lo", "field_lo_quantity"): 2,
                     ("lo", "field_lo_discount"): 2,
                     ("lo", "field_lo_revrate"): 2, ("d_year", "standard"): 2,
                     ("d_yearmonthnum", "standard"): 1,
                     ("d_weeknuminyear", "standard"): 1}


def test_the_field_ranges_are_the_sources():
    config = _json("configs", "ssb-sf30-flight1.json")
    shape = config["shape"]
    pk = np.arange(1, shape["parts"] + 1)
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    assert int(retail.max()) * 50 * 10 == shape["fields"]["lo_revrate"]["max"]
    assert shape["fields"]["lo_revrate"]["max"] < 1 << 27
    assert shape["lineorder_rows"] == shape["scale_factor"] * 6_000_000
    assert shape["slices"] == -(-shape["lineorder_rows"] // (1 << 20)) == 172
    rows = sum(ssb.bit_depth(f) + 1 for f in shape["fields"].values()) \
        + sum(shape["date_frames"].values())
    assert rows == shape["rows"] == 184
    assert shape["packed_bytes"] == rows * shape["slices"] * (1 << 17)
    assert {k: len(v) for k, v in ssb.pools(config).items()} == {
        "year": 7, "yearmonth": 84, "week": 53, "dwindow": 9, "qbound": 49,
        "qwindow": 41}
    assert ssb.pools(config)["dwindow"][0] == "[0, 2]"
    assert ssb.pools(config)["dwindow"][-1] == "[8, 10]"
    assert ssb.pools(config)["qwindow"][-1] == "[41, 50]"


# ----------------------------------------------------- the bytes model

def test_sum_bytes_model_arithmetic():
    config = _json("configs", "ssb-sf30-flight1.json")
    fields = sum_layer.fields_of(config)
    assert {f: sum_bytes_model.field_rows(v) for (_, f), v in fields.items()} \
        == {"lo_quantity": 7, "lo_discount": 5, "lo_revrate": 28}
    rows = lambda q: sum_bytes_model.sum_rows(pql.parse(q), fields)
    q11, q12, q13 = (q for q, _ in QUERIES[:3])
    assert (rows(q11), rows(q12), rows(q13)) == (41, 41, 42)
    # Two conditions on one field read it once; the summed field under a
    # condition as well.
    twice = ('Sum(Intersect(Range(frame="lo", lo_quantity > 5), '
             'Range(frame="lo", lo_quantity < 9), '
             'Range(frame="lo", lo_revrate > 100)), frame="lo", '
             'field="lo_revrate")')
    assert rows(twice) == 28 + 7
    assert rows('Sum(frame="lo", field="lo_discount")') == 5
    assert sum_bytes_model.sum_bytes(pql.parse(q13), fields, 172) \
        == 42 * 172 * 131072 == 946_864_128
    mix = _json("traffic", "flight1-mixed-c1.json")
    traffic = loadgen.Traffic(mix, ssb.pools(config), 5, budget=5000)
    for q in itertools.islice(traffic.window(0), 60):
        assert rows(q) == (41, 41, 42)[q.form]


def test_the_roofline_reader_counts_the_traced_requests_bytes():
    from types import SimpleNamespace

    config = _json("configs", "ssb-sf30-flight1.json")
    q11, _, q13 = (q for q, _ in QUERIES[:3])
    log = [{"ok": True, "pql": q, "t0": 1.0 + k, "t1": 1.001 + k}
           for k, q in enumerate((q11, q13))]
    # Two launches of 2 ms inside a 3 s interval that starts at the
    # client's second 0.
    trace = {"span_ps": (0, int(3e12)),
             "launches": [(int(1e12), int(2e9)), (int(2e12), int(2e9))]}
    ctx = SimpleNamespace(log=log, trace=trace, trace_t0=0.0, config=config,
                          device={"deviceKind": "TPU v5 lite"})
    need = (41 + 42) * 172 * 131072
    assert sum_layer.roofline_pct(ctx) \
        == pytest.approx(100.0 * need / 819e9 / 4e-3)
    assert sum_layer.roofline_pct(SimpleNamespace(
        log=log, trace=None, trace_t0=None, config=config)) is None


@pytest.mark.parametrize("profiles, want", [
    ([{"bsiPreludeHits": 0, "bsiPreludeMisses": 1}] * 3, 0.0),
    ([{"bsiPreludeHits": 1, "bsiPreludeMisses": 0},
      {"bsiPreludeHits": 0, "bsiPreludeMisses": 1}], 50.0),
    ([{"bsiPreludeHits": 0, "bsiPreludeMisses": 0}], None),
    ([{"slices": 3}], None),        # the parent: no such counter
    ([], None),
])
def test_the_prelude_hit_share(profiles, want):
    from types import SimpleNamespace

    ctx = SimpleNamespace(log=[{"ok": True, "profile": {"resources": p}}
                               for p in profiles])
    assert sum_layer.prelude_hit_pct(ctx) == want


# --------------------------------------------------------- the control

def _window(seed, n=64):
    config = _json("configs", "ssb-sf30-flight1.json")
    mix = _json("traffic", "flight1-mixed-c1.json")
    traffic = loadgen.Traffic(mix, ssb.pools(config), seed, budget=5000)
    return list(itertools.islice(traffic.window(0), n))


@pytest.mark.parametrize("seed", [2_147_483_801, 17])
def test_the_control_in_the_programs_place_is_not_correct(tmp_path, seed):
    """What ``--control`` does on the chip, at a size a test can hold:
    four slices, at which a year's and a month's revenue already pass
    2^24 many times over."""
    config = _config(4 << 20)
    _, cube = _values(config, seed)
    reference = ssb_flight1.Reference(config, {"cube": cube})
    qs = _window(seed)
    exact, control = reference.answers(qs), reference.answers(qs, True)
    assert max(a["sum"] for a in exact) > 1 << 24
    differ = sum(a != b for a, b in zip(exact, control))
    assert differ > len(qs) // 2
    # Close, as a lower precision is: never off by a thousandth.
    for a, b in zip(exact, control):
        assert abs(a["sum"] - b["sum"]) <= a["sum"] // 1000
    log = [{"ok": True, "status": 200, "pql": q, "result": a}
           for q, a in zip(qs, exact)]
    picked, wrong, failed, stand_in = run.compare(
        reference, log, str(tmp_path), None, seed, control=True)
    assert run.verdict(len(picked), wrong, failed) is True
    assert stand_in == differ
    assert run.verdict(len(picked), stand_in, failed) is False


def test_explain_says_how_far_off():
    config = _config(1000)
    _, cube = _values(config, 1)
    reference = ssb_flight1.Reference(config, {"cube": cube})
    want = {"sum": 10, "count": 2}
    assert reference.explain("q", {"sum": 11, "count": 2}, want) == {
        "query": "q", "got": {"sum": 11, "count": 2}, "want": want,
        "sum_difference": 1, "count_difference": 0}
    assert reference.explain("q", None, want)["sum_difference"] is None


# ------------------------------------------------------------- the mix

def test_the_mix_holds_what_it_was_sized_for():
    config = _json("configs", "ssb-sf30-flight1.json")
    mix = _json("traffic", "flight1-mixed-c1.json")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    traffic = loadgen.Traffic(mix, ssb.pools(config), 11)
    assert [f["weight"] for f in mix["forms"]] == [1, 6, 6]
    assert [len(t) for t in traffic._tuples] == [3087, 30_996, 136_899]
    reserve = mix["warmup"]["reserve_per_form"]
    assert traffic.capacity() == (3087 - reserve) * 13 == 38_883
    sized = mix["sized_for"]
    assert sized["measured_q_per_s"] > 0
    assert traffic.capacity() >= (sized["at_least_windows"]
                                  * sized["measured_q_per_s"]
                                  * bench["run_seconds"])
    assert mix["clients"] == 1 and "compare" not in mix
