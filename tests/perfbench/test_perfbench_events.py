"""The time-window cell's own pieces, at sizes a test can hold: the mix,
its pools and ``capacity()``; the generator's day bitmaps against the
activity model, its views and containers decoded back by the program's
own codec; the reference against a brute force over unpacked booleans;
the control, which put in the program's place through the run's own
comparison comes out as not correct; the bytes model's cover against the
engine's ``views_by_time_range``; the staging's walk; the readers of the
new counters on profiles that have them and that lack them."""
import datetime
import io
import json
import os
import random
import tarfile
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import run
from perfbench.datagen import events
from perfbench.lib import cover_bytes_model, cover_layer, loadgen, pql
from perfbench.reference import events_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FORM = ('Count(Intersect(Range(frame="activity", rowID={e}, {w}), '
        'Bitmap(frame="segment", rowID={s})))')
RET = ('Count(Intersect(Range(frame="activity", rowID=0, {w1}), '
       'Range(frame="activity", rowID=1, {w2}), '
       'Bitmap(frame="segment", rowID={s})))')
BUCKETS = [2, 4, 8, 16, 24, 32, 48, 64]


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def _config(last_day=None, slices=None):
    config = _json("configs", "events-ymd-67m.json")
    if last_day:
        config["shape"]["last_day"] = last_day
    if slices:
        config["shape"]["slices"] = slices
    return config


def _bucket(n):
    """The engine's rule, written again: a power of two up to 16, then
    also the halfway steps."""
    return next(b for b in BUCKETS + [96, 128] if b >= max(n, 2))


def _covers(query):
    """Views of the minimal cover of each window of a query."""
    return [cover_bytes_model.count_rows(kid, "YMD")
            for kid in pql.parse(query).children[0].children
            if kid.name == "Range"]


# ----------------------------------------------- configuration and mix

def test_the_configuration_states_its_deployment():
    config = _config()
    shape = config["shape"]
    assert config["architecture"] is None and config["reduced"] == []
    assert events.n_days(config) == shape["days"] == 181
    views = events.view_names(config)
    assert len(views) == len(set(views)) == shape["views"] == 189
    assert views[:3] == ["standard", "standard_2017", "standard_201701"]
    assert views[8] == "standard_20170101" and views[-1] == "standard_20170630"
    assert shape["fragments"] == (len(views) + 1) * shape["slices"] == 12_160
    rows = 2 * len(views) + shape["segment_rows"]
    assert shape["row_stacks"] == rows == 410
    assert shape["packed_bytes"] == rows * shape["slices"] * (1 << 17)
    assert shape["columns"] == shape["slices"] << 20
    model = shape["activity_model"]
    assert sum(c["eighths"] for c in model["cohorts"]) == 8
    session = sum(c["eighths"] / 8 * 2.0 ** -c["session_log2"]
                  for c in model["cohorts"])
    assert model["day_density_pct"]["session"] == 100 * session
    assert model["day_density_pct"]["purchase"] == 100 * session \
        * 2.0 ** -model["purchase_given_session_log2"]
    assert "[start, end)" in config["guarantees"]["answers"]
    assert "end day included" in config["control"]
    cell = next(w for w in BENCH["workloads"]
                if w["config"] == config["name"])
    assert cell["name"] == "events67m-window-c1" and cell["chips"] == 1


def test_the_pools_are_the_windows_the_mix_describes():
    config = _config()
    pools = events.pools(config)
    assert {k: len(v) for k, v in pools.items()} == {
        "event": 2, "segment": 32, "week": 175, "week_1of3": 59,
        "week_2of3": 59, "week_3of3": 57, "month": 306, "picked": 10_980}
    assert pools["week"][0] == \
        'start="2017-01-01T00:00", end="2017-01-08T00:00"'
    assert pools["week"][-1] == \
        'start="2017-06-24T00:00", end="2017-07-01T00:00"'
    by_pool = {}
    for name in ("week", "month", "picked"):
        sizes = [_covers(FORM.format(e=0, w=w, s=0))[0]
                 for w in pools[name]]
        by_pool[name] = (min(sizes), max(sizes), sum(sizes) / len(sizes))
    assert by_pool["week"] == (7, 7, 7.0)
    # A month preset is 28 or 30 day views; one month view where it is
    # a calendar month (February by 28 days; April and June by 30); and
    # February with two days beside it where 30 days hold it whole.
    sizes = [_covers(FORM.format(e=0, w=w, s=0))[0] for w in pools["month"]]
    assert sorted(set(sizes)) == [1, 3, 28, 30]
    assert (sizes.count(1), sizes.count(3)) == (3, 3)
    lo, hi, mean = by_pool["picked"]
    assert (lo, hi) == (1, 63) and 31 < mean < 33


def test_the_mix_sends_four_kinds_of_query_and_holds_ten_windows():
    config, mix = _config(), _json("traffic", "window-mixed-c1.json")
    assert mix["loop"] == "closed" and mix["clients"] == 1
    # W7 : W30 : ANY : RET = 1 : 2 : 6 : 3, ANY as a form an event and
    # RET as a form a third of the session weeks, so that every form's
    # space is listed whole whatever a run's sampling budget.
    assert [f["weight"] for f in mix["forms"]] == [1, 2, 3, 3, 1, 1, 1]
    pools = events.pools(config)
    assert pools["week_1of3"] + pools["week_2of3"] + pools["week_3of3"] \
        == pools["week"]
    for form in mix["forms"]:
        space = 1
        for pool in form["operands"].values():
            space *= len(pools[pool])
        assert space <= loadgen.ENUMERATE_LIMIT, form
    traffic = loadgen.Traffic(mix, pools, 2_147_483_949)
    assert traffic.capacity() == 116_928 \
        == loadgen.Traffic(mix, pools, 5, budget=5000).capacity()
    sized = mix["sized_for"]
    assert traffic.capacity() >= sized["at_least_windows"] \
        * sized["measured_q_per_s"] * BENCH["run_seconds"] > 0
    window = traffic.window(0)
    sent = [next(window) for _ in range(2400)]
    assert len(set(sent)) == len(sent)
    warm = [q for phase in traffic.ladder(4) for s in phase for q in s]
    assert not set(warm) & set(sent)
    counts = [sum(1 for q in sent if q.form == i) for i in range(7)]
    assert counts == [200, 400, 600, 600, 200, 200, 200]
    ref = events_window.Reference(config, {"seed": 1})
    operands = views = 0
    thirds = set()
    for q in sent:
        tree = ref.operands(q)                 # every query parses
        covers = _covers(q)
        assert len(tree) == len(covers) + 1
        if q.form >= 4:
            assert covers == [7, 7]
            assert [op[1] for op in tree[:2]] == [0, 1]
            thirds.add((q.form, tree[0][2] // 59))
        else:
            assert len(covers) == 1
            _, event, a, b = tree[0]
            assert 0 <= a < b <= 181
            assert b - a == 7 if q.form == 0 else \
                b - a in (28, 30) if q.form == 1 else 31 <= b - a <= 150
            assert q.form < 2 or event == q.form - 2
        views += sum(covers)
        operands += sum(_bucket(n) for n in covers)
    assert thirds == {(4, 0), (5, 1), (6, 2)}
    # What the mix makes the engine read: the prediction in PERF.md.
    assert 13.0 < 100 * (operands - views) / operands < 14.5
    assert 29 < operands / len(sent) + 1 < 31


# ---------------------------------------------------------- the generator

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


def _bools(words):
    return np.unpackbits(words.view(np.uint8), axis=-1, bitorder="little") \
        .astype(bool)


def test_the_day_bitmaps_follow_the_activity_model():
    config = _config(last_day="2017-02-28")
    days = events.day_rows(config, 91, 3)
    assert days.shape == (2, 59, 1 << 14) and days.dtype == np.uint64
    assert (days == events.day_rows(config, 91, 3)).all()      # the seed
    assert (days != events.day_rows(config, 91, 4)).any()      # the slice
    assert (days != events.day_rows(config, 92, 3)).any()
    # A longer deployment has the same first days: a rehearsal's days
    # are the deployment's.
    longer = events.day_rows(_config(last_day="2017-03-31"), 91, 3)
    assert (longer[:, :59] == days).all()
    session, purchase = _bools(days[0]), _bools(days[1])
    assert not (purchase & ~session).any()         # a purchase is a session
    assert abs(session.mean() - 0.1171875) < 2e-4
    assert abs(purchase.mean() - 0.0146484375) < 1e-4
    # The cohorts, by how many of the 59 days a user had a session:
    # daily about 29.5, weekly 7.4, rare 0.9.
    per_user = session.sum(axis=0)
    assert abs((per_user >= 19).mean() - 1 / 8) < 2e-3
    assert abs(((per_user >= 1).mean()) - (
        1 / 8 + 3 / 8 * (1 - (7 / 8) ** 59) + 1 / 2 * (1 - (63 / 64) ** 59))
    ) < 2e-3
    # Days are independent: the same users, another draw.
    both = (session[0] & session[1]).mean()
    assert abs(both - (1 / 8 / 4 + 3 / 8 / 64 + 1 / 2 / 4096)) < 1e-3


def test_the_views_are_ors_of_their_days_in_the_containers_a_snapshot_writes():
    config = _config(last_day="2017-02-03")
    days = events.day_rows(config, 5, 0)
    views = events.slice_views(config, days)
    assert [v for v, _ in views] == events.view_names(config)
    assert len(views) == 2 + 2 + 34
    by_name = dict(views)
    day_bools = _bools(days)
    january = day_bools[:, :31].any(axis=1)
    everything = day_bools.any(axis=1)
    kinds = {}
    for name, words in views:
        tar = _members(events.tar_of(events.roaring(words), [0, 1]))
        assert json.loads(tar["cache"]) == [0, 1]
        rows = _rows_of(tar["data"])
        assert sorted(rows) == [0, 1]
        want = {"standard": everything, "standard_2017": everything,
                "standard_201701": january,
                "standard_201702": day_bools[:, 31:].any(axis=1)}.get(name)
        if want is None:
            d = (datetime.date(int(name[9:13]), int(name[13:15]),
                               int(name[15:17])) - datetime.date(2017, 1, 1))
            want = day_bools[:, d.days]
        for r in (0, 1):
            assert (rows[r] == want[r]).all(), (name, r)
        # The type of each row's first container (12-byte headers: key
        # u64, type u16, n-1 u16; 16 containers a row, none empty).
        data = tar["data"]
        assert int.from_bytes(data[4:8], "little") == 32
        kinds[name] = tuple(int.from_bytes(data[at + 8:at + 10], "little")
                            for at in (8, 8 + 12 * 16))
    # A day of sessions is bitmap containers, a day of purchases ARRAY
    # containers; a month, the year and ``standard`` are bitmaps.
    assert kinds["standard_20170117"] == (2, 1)
    assert kinds["standard_20170203"] == (2, 1)
    assert kinds["standard_201701"] == (2, 2) == kinds["standard"]
    assert (by_name["standard"] == by_name["standard_2017"]).all()
    segments = events.segment_rows(config, 5, 0)
    assert segments.shape == (32, 1 << 14)
    density = _bools(segments).mean(axis=1)
    assert np.allclose(density, [0.5, 0.25, 0.125] * 10 + [0.5, 0.25],
                       atol=2e-3)


def test_staging_touches_every_view_and_walks_every_bucket_in_both_orders():
    config = _config()
    queries = events.stage_queries(config)
    assert len(set(queries)) == len(queries)
    everything, walk = queries[:3], queries[3:]
    for event, q in enumerate(everything[:2]):
        tree = pql.parse(q).children[0]
        assert tree.name == "Union"
        seen = set()
        for kid in tree.children:
            assert kid.args["rowID"] == event
            if kid.name == "Bitmap":
                seen.add("standard")
                continue
            a, b = (datetime.datetime.strptime(kid.args[k], "%Y-%m-%dT%H:%M")
                    for k in ("start", "end"))
            seen |= {"standard_" + v
                     for v in cover_bytes_model.cover(a, b, "YMD")}
        assert seen == set(events.view_names(config))
    assert [leaf.args["rowID"] for leaf in pql.leaves(pql.parse(
        everything[2]))] == list(range(32))
    staging = config["staging"]
    assert len(walk) == staging["settle"] + 2 * len(BUCKETS) + 1
    assert staging["settle"] >= 12          # the path model's exploring
    settle = walk[:staging["settle"]]
    assert {tuple(_covers(q)) for q in settle} == {(7,)}
    pairs = walk[staging["settle"]:-1]
    assert [_bucket(_covers(q)[0]) for q in pairs[::2]] == BUCKETS
    assert [_bucket(_covers(q)[0]) for q in pairs[1::2]] == BUCKETS
    ref = events_window.Reference(config, {"seed": 1})
    for sparse_first, dense_first in zip(pairs[::2], pairs[1::2]):
        (_, e1, a1, b1), (_, s1) = ref.operands(sparse_first)
        (_, e2, a2, b2), (_, s2) = ref.operands(dense_first)
        assert (a1, b1) == (a2, b2) and b1 - a1 == _covers(sparse_first)[0]
        assert (e1, s1, e2, s2) == (1, 0, 0, 2)
    assert _covers(walk[-1]) == [7, 7]
    with pytest.raises(ValueError):
        events.day_only_window(config, 58)


# ------------------------------------------------------------ the reference

def _brute(config, seed, query, inclusive=False):
    """The count by booleans a user and day: which users of the segment
    row have the event on some day of each window."""
    first = datetime.date.fromisoformat(config["shape"]["first_day"])
    n = events.n_days(config)
    total = 0
    for s in range(config["shape"]["slices"]):
        days = _bools(events.day_rows(config, seed, s))
        segments = _bools(events.segment_rows(config, seed, s))
        keep = np.ones(1 << 20, dtype=bool)
        for kid in pql.parse(query).children[0].children:
            if kid.name == "Bitmap":
                keep &= segments[kid.args["rowID"]]
                continue
            a, b = ((datetime.date.fromisoformat(kid.args[k][:10])
                     - first).days for k in ("start", "end"))
            on = [d for d in range(n) if a <= d < b + inclusive]
            keep &= days[kid.args["rowID"]][on].any(axis=0) if on \
                else np.zeros(1 << 20, dtype=bool)
        total += int(keep.sum())
    return total


def _w(a, b):
    day = datetime.date(2017, 1, 1)
    return (f'start="{day + datetime.timedelta(days=a)}T00:00", '
            f'end="{day + datetime.timedelta(days=b)}T00:00"')


QUERIES = [
    FORM.format(e=0, w=_w(3, 10), s=0),
    FORM.format(e=1, w=_w(0, 31), s=1),          # January, a month view
    FORM.format(e=1, w=_w(17, 40), s=2),
    FORM.format(e=0, w=_w(40, 47), s=31),        # ends with the data
    FORM.format(e=1, w=_w(44, 60), s=5),         # runs past the data
    FORM.format(e=0, w=_w(-10, 2), s=4),         # starts before it
    FORM.format(e=0, w=_w(9, 9), s=3),           # no day at all
    FORM.format(e=0, w=_w(60, 70), s=3),         # wholly past the data
    RET.format(w1=_w(0, 7), w2=_w(7, 14), s=8),
    RET.format(w1=_w(20, 27), w2=_w(20, 27), s=9),
]


@pytest.mark.parametrize("seed", [3, 2_147_483_777])
def test_the_reference_equals_a_brute_force_and_the_control_does_not(seed):
    config = _config(last_day="2017-02-16", slices=2)     # 47 days
    reference = events_window.Reference(config, {"seed": seed})
    want = [_brute(config, seed, q) for q in QUERIES]
    assert reference.answers(QUERIES) == want
    assert want[6] == want[7] == 0 and min(want[:6]) > 1000
    control = reference.answers(QUERIES, control=True)
    assert control == [_brute(config, seed, q, inclusive=True)
                       for q in QUERIES]
    # One more day: more users wherever that day has data.
    assert [c > w for c, w in zip(control, want)] == [
        True, True, True, False, False, True, True, False, True, True]
    assert reference.explain(QUERIES[0], want[0] + 2, want[0])[
        "difference"] == 2


@pytest.mark.parametrize("bad", [
    'Count(Bitmap(frame="segment", rowID=1))',
    'Count(Intersect(Bitmap(frame="segment", rowID=1)))',
    FORM.format(e=2, w=_w(1, 8), s=0),
    FORM.format(e=0, w=_w(1, 8), s=0).replace("T00:00", "T06:00", 1),
    FORM.format(e=0, w=_w(1, 8), s=0).replace("Intersect", "Union"),
    FORM.format(e=0, w=_w(1, 8), s=0).replace('"segment"', '"activity"'),
    'Sum(' + FORM.format(e=0, w=_w(1, 8), s=0)[6:],
])
def test_the_reference_refuses_what_is_not_a_windowed_count(bad):
    reference = events_window.Reference(_config(), {"seed": 1})
    with pytest.raises(ValueError):
        reference.operands(bad)


def test_the_control_in_the_programs_place_is_not_correct(tmp_path):
    """Through the run's own ``compare`` and ``verdict``: the exact
    answers pass, the control's (the end day included) do not."""
    config = _config(last_day="2017-02-16", slices=2)
    reference = events_window.Reference(config, {"seed": 7})
    log = [{"pql": loadgen.Query(q, 0), "ok": True, "status": 200,
            "result": a} for q, a in zip(QUERIES,
                                         reference.answers(QUERIES))]
    picked, mismatched, failed, control = run.compare(
        reference, log, str(tmp_path), None, 7, control=True)
    assert (len(picked), mismatched, failed) == (len(QUERIES), 0, 0)
    assert run.verdict(len(picked), mismatched, failed) is True
    assert control == 7
    assert run.verdict(len(picked), control, failed) is False


# ----------------------------------------------------------- the bytes model

def test_the_bytes_models_cover_is_the_engines_on_random_windows():
    from pilosa_tpu import time_quantum as tq

    rng = random.Random(33)
    base = datetime.datetime(2016, 10, 1)
    for quantum in ("YMD", "YMDH", "MD", "D", "YM", "Y", "DH", "H", "M"):
        for _ in range(400):
            a = base + datetime.timedelta(hours=rng.randrange(24 * 500))
            b = a + datetime.timedelta(
                hours=rng.randrange(-5, 24 * rng.choice([1, 3, 40, 400])))
            if rng.random() < 0.5:
                a, b = a.replace(hour=0), b.replace(hour=0)
            want = [v.split("_")[1] for v in tq.views_by_time_range(
                "standard", a, b, quantum)]
            assert cover_bytes_model.cover(a, b, quantum) == want, \
                (quantum, a, b)


def test_the_bytes_model_counts_the_minimal_cover_and_the_leaves():
    week = pql.parse(FORM.format(e=0, w=_w(3, 10), s=0))
    assert cover_bytes_model.count_rows(week, "YMD") == 7 + 1
    assert cover_bytes_model.count_bytes(week, "YMD", 64) == 8 * 64 << 17
    # 17 January to 20 April: 15 days, February, March, 19 days.
    picked = pql.parse(FORM.format(e=1, w=_w(16, 109), s=0))
    assert cover_bytes_model.count_rows(picked, "YMD") == 15 + 2 + 19 + 1
    assert cover_bytes_model.count_rows(picked, "D") == 93 + 1
    retention = pql.parse(RET.format(w1=_w(0, 7), w2=_w(7, 14), s=1))
    assert cover_bytes_model.count_rows(retention, "YMD") == 15
    assert cover_bytes_model.count_rows(
        pql.parse(FORM.format(e=1, w=_w(0, 181), s=0)), "YMD") == 6 + 1
    assert cover_bytes_model.count_rows(
        pql.parse(FORM.format(e=1, w=_w(0, 365), s=0)), "YMD") == 1 + 1


# ------------------------------------------------------------- the readers

def _ctx(resources):
    log = [{"ok": True, "profile": {"resources": r, "spans": []}}
           for r in resources]
    return SimpleNamespace(log=log)


def test_cover_pad_pct_reads_the_counters_and_nothing_where_there_are_none():
    both = [{"rangeCoverViews": 7, "rangeCoverOperands": 8},
            {"rangeCoverViews": 30, "rangeCoverOperands": 32},
            {"rangeCoverViews": 14, "rangeCoverOperands": 16}]
    assert cover_layer.cover_pad_pct(_ctx(both)) == 100 * 5 / 56
    # The parent's profiles have no such keys; a window without a Range
    # planned nothing.
    assert cover_layer.cover_pad_pct(_ctx([{"planMs": 1.0}])) is None
    assert cover_layer.cover_pad_pct(_ctx([])) is None
    assert cover_layer.cover_pad_pct(_ctx(
        [{"rangeCoverViews": 0, "rangeCoverOperands": 0}])) is None
    reader = run.load_metric("cover_pad_pct.ev")
    assert reader.read(_ctx(both)) == 100 * 5 / 56
    assert reader.read(_ctx([{"planMs": 1.0}])) is None


def test_cover_roofline_divides_the_minimal_covers_bytes_by_the_device_time():
    config = _config()
    q = FORM.format(e=0, w=_w(3, 10), s=0)
    need = 8 * 64 << 17
    busy_ps = int(2 * need / 819e9 * 1e12)       # twice the least time
    ctx = SimpleNamespace(
        config=config, device={"deviceKind": "TPU v5 lite"}, trace_t0=100.0,
        trace={"span_ps": (0, int(1e12)), "launches": [(int(0.4e12), busy_ps)],
               "window_s": 1.0, "busy_s": busy_ps / 1e12},
        log=[{"ok": True, "pql": q, "t0": 100.39, "t1": 100.41}])
    assert abs(cover_layer.roofline_pct(ctx) - 50.0) < 1e-6
    assert run.load_metric("cover_roofline").read(ctx) \
        == cover_layer.roofline_pct(ctx)
    ctx.trace = None
    assert cover_layer.roofline_pct(ctx) is None
