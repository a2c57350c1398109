"""The general traffic generator: every query of a run is distinct,
warm-up and window share none, every seed sends the same mix in another
order, and a mix that is too small for its window says so. All of it
holds for a mix whose operands are no rows (``NOT_ROWS``, a fixture and
no cell): nothing here reads a form out of a query's text."""
import collections
import hashlib
import importlib
import itertools
import json
import os
import re

import pytest

from perfbench.lib import loadgen
from perfbench.lib.serverproc import HarnessFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


# What no standing cell sends: a numeric window and a single bound (the
# Star Schema Benchmark's Q1.1, whose whole space is 7 x 9 x 49 = 3,087
# queries), a quoted pair of times, and a column to write. The first two
# forms share the pool of windows; the third's tuples are too many to
# list, so they are sampled. Weights 1 : 4 : 4.
NOT_ROWS = "fixture-not-rows"
NOT_ROWS_MIX = {
    "loop": "closed", "clients": 1,
    "forms": [
        {"pql": 'Sum(Intersect(Bitmap(frame="d_year", rowID={y}), '
                'Range(frame="lo", lo_discount >< {dw}), '
                'Range(frame="lo", lo_quantity < {q})), '
                'frame="lo", field="lo_revrate")',
         "weight": 1,
         "operands": {"y": "year", "dw": "window", "q": "bound"}},
        {"pql": 'Count(Intersect(Range(frame="orders", rowID={y}, {t}), '
                'Range(frame="lo", lo_discount >< {dw})))',
         "weight": 4,
         "operands": {"y": "year", "t": "times", "dw": "window"}},
        {"pql": 'SetBit(frame="seen", rowID={y}, columnID={c})',
         "weight": 4, "operands": {"y": "year", "c": "column"}}],
    "warmup": {"reserve_per_form": 8, "ladder_rounds": 2}}
_MONTHS = [f"{y}-{m:02d}-01T00:00" for y in (1992, 1993) for m in range(1, 13)]
NOT_ROWS_POOLS = {
    "year": [str(y) for y in range(1992, 1999)],
    "window": [f"[{d - 1}, {d + 1}]" for d in range(1, 10)],
    "bound": [str(q) for q in range(2, 51)],
    "times": [f'start="{a}", end="{b}"'
              for a, b in itertools.combinations(_MONTHS, 2)],
    "column": [str(c) for c in range(1 << 20)]}
MIXES = CELLS + [NOT_ROWS]


def _mix(name):
    """The mix and the pools of a cell, or of the fixture."""
    if name == NOT_ROWS:
        return NOT_ROWS_MIX, NOT_ROWS_POOLS
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    config = _json("configs", w["config"] + ".json")
    gen = importlib.import_module("perfbench.datagen." + config["datagen"])
    return _json("traffic", w["traffic"] + ".json"), gen.pools(config)


def _traffic(name, seed):
    mix, pools = _mix(name)
    # The fixture's sampled form would be its scarcest on a small budget:
    # it gets the one a run has.
    budget = 200_000 if name == NOT_ROWS else 5000
    return loadgen.Traffic(mix, pools, seed, budget=budget), mix


def _warm_up(traffic, mix):
    """Every query of the warm-up: the ladder, then the mixed phase."""
    warm = [q for phase in traffic.ladder(mix["warmup"]["ladder_rounds"])
            for s in phase for q in s if q is not None]
    return warm + [q for s in traffic.mixed_warm() for q in s]


def _canonical(q):
    """Commutative two-operand forms compare unordered."""
    m = re.fullmatch(r"Count\((Intersect|Union|Xor)\((Bitmap\([^)]*\)), "
                     r"(Bitmap\([^)]*\))\)\)", q)
    return (m.group(1), frozenset(m.groups()[1:])) if m else q


@pytest.mark.parametrize("cell", MIXES)
def test_every_query_of_a_run_is_distinct(cell):
    traffic, mix = _traffic(cell, 2147483999)
    warm = _warm_up(traffic, mix)
    window = [q for k in range(traffic.clients)
              for q in itertools.islice(traffic.window(k), 300)]
    assert None not in window
    every = [_canonical(q) for q in warm + window]
    assert len(set(every)) == len(every)
    assert 0 < len(warm) <= (mix["warmup"]["reserve_per_form"]
                             * len(mix["forms"]))


@pytest.mark.parametrize("cell", MIXES)
def test_every_seed_sends_the_same_mix_in_another_order(cell):
    orders = []
    for seed in (1, 2, 3_000_000_011):
        traffic, mix = _traffic(cell, seed)
        weights = [f["weight"] for f in mix["forms"]]
        qs = list(itertools.islice(traffic.window(0), sum(weights) * 25))
        assert collections.Counter(q.form for q in qs) \
            == {i: w * 25 for i, w in enumerate(weights)}
        orders.append([q.form for q in qs])
    assert orders[0] != orders[1] != orders[2] != orders[0]


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_011])
def test_a_mix_that_is_not_rows_runs_dry_where_its_scarcest_form_does(seed):
    traffic, mix = _traffic(NOT_ROWS, seed)
    reserve = mix["warmup"]["reserve_per_form"]
    warm = _warm_up(traffic, mix)
    assert 0 < len(warm) <= reserve * len(mix["forms"])
    window = list(itertools.takewhile(lambda q: q is not None,
                                      traffic.window(0)))
    assert len(set(warm + window)) == len(warm + window)
    # Q1.1's 3,087 less the reserve, once a deck of nine: the stream says
    # dry in the deck after capacity(), at the first draw of that form.
    assert traffic.capacity() == (7 * 9 * 49 - reserve) * 9 == 27_711
    assert 0 <= len(window) - traffic.capacity() < 9
    sent = collections.Counter(q.form for q in window)
    assert sent[0] == 7 * 9 * 49 - reserve
    assert sent[1] < 7 * 276 * 9 - reserve and sent[2] < 200_000 - reserve


# sha256 over the warm-up and the first 500 queries of the window, as
# loadgen of the parent of PR 30 (d4848fb) rendered them: a query that
# learns its form is the same text, sent at the same place.
PARENT_DIGEST = {
    ("seg1b-count-c1", 1): "f0266675bfca8c95",
    ("seg1b-count-c1", 2): "d2e30f77d99c01d8",
    ("seg1b-count-c1", 77): "7979b8e4eb4705ad",
    ("chem500k-tanimoto-c1", 1): "f2a6512abe612b7a",
    ("chem500k-tanimoto-c1", 2): "65696cedc859caae",
    ("chem500k-tanimoto-c1", 77): "edb975e58c796b7d"}


@pytest.mark.parametrize("cell, seed", list(PARENT_DIGEST))
def test_the_queries_of_a_seed_are_the_parents(cell, seed):
    mix, pools = _mix(cell)
    # As run.py builds it: the budget decides how many tuples a form
    # too large to list samples.
    t = loadgen.Traffic(mix, pools, seed)
    h = hashlib.sha256()
    for phase in t.ladder(mix["warmup"]["ladder_rounds"]):
        h.update(json.dumps(phase).encode())
    h.update(json.dumps(t.mixed_warm()).encode())
    for k in range(t.clients):
        h.update(json.dumps(
            list(itertools.islice(t.window(k), 500))).encode())
    assert h.hexdigest()[:16] == PARENT_DIGEST[cell, seed]


def test_same_seed_gives_the_same_queries():
    a, _ = _traffic(CELLS[0], 77)
    b, _ = _traffic(CELLS[0], 77)
    for k in (0, a.clients - 1):
        assert list(itertools.islice(a.window(k), 50)) \
            == list(itertools.islice(b.window(k), 50))


def test_a_mix_too_small_for_its_window_fails_the_run():
    mix = {"loop": "closed", "clients": 1, "forms": [
        {"pql": "Count(Intersect({a}, {b}))", "weight": 1,
         "operands": {"a": "row", "b": "row"}, "unordered": True}],
        "warmup": {"reserve_per_form": 1, "ladder_rounds": 1}}
    traffic = loadgen.Traffic(mix, {"row": ["A", "B", "C"]}, 5)
    assert traffic.capacity() == 2
    qs = list(itertools.islice(traffic.window(0), 3))
    assert qs[2] is None and None not in qs[:2]

    class Dead:
        def send(self, *a):
            return 200, b'{"results": [1]}'

        def close(self):
            pass

    with pytest.raises(HarnessFailure, match="ran out of distinct"):
        loadgen.run_closed(Dead(), "/q", [traffic.window(0)], 5.0)

    class Refusing(Dead):
        def send(self, *a):
            return 500, b"RESOURCE_EXHAUSTED"

    # A server that refuses at once burns through the queries: the run
    # goes on, and its failed requests make it not correct.
    log, _ = loadgen.run_closed(Refusing(), "/q", [traffic.window(0)], 5.0)
    assert [r["status"] for r in log] == [500, 500]


@pytest.mark.parametrize("cell", CELLS)
def test_the_mix_holds_ten_windows_at_the_measured_rate(cell):
    """A later PR that serves several times as fast still finds distinct
    queries: the mix states the rate it was sized for, and holds
    ``at_least_windows`` windows of it."""
    traffic, mix = _traffic(cell, 11)
    sized = mix["sized_for"]
    assert sized["at_least_windows"] >= 10
    need = (sized["at_least_windows"] * sized["measured_q_per_s"]
            * BENCH["run_seconds"])
    assert traffic.capacity() >= need, (cell, traffic.capacity(), need)
    # capacity() is what a window can draw: the stream gives that many
    # queries and then says that it is dry.
    small = dict(mix, forms=[dict(f, weight=min(f["weight"], 3))
                             for f in mix["forms"]])
    t = loadgen.Traffic(small, traffic._pools, 11)
    qs = list(itertools.islice(t.window(0), t.capacity() + len(t._deck())))
    assert None not in qs[:t.capacity()] and None in qs


def test_closed_loop_log_and_decode():
    class Fake:
        def __init__(self):
            self.n = 0

        def send(self, method, path, body):
            self.n += 1
            if self.n % 4 == 0:
                return 503, b"shed"
            if self.n % 5 == 0:
                return 200, b"not json"
            return 200, json.dumps({"results": [self.n]}).encode()

        def close(self):
            pass

    log, t_open = loadgen.run_closed(
        Fake(), "/q", [iter(f"Q{i}" for i in range(10))], 5.0)
    loadgen.decode(log)
    assert len(log) == 10 and all(r["t1"] >= r["t0"] >= t_open for r in log)
    assert [r["ok"] for r in log] == [True, True, True, False, False,
                                      True, True, False, True, False]
    assert log[0]["result"] == 1 and log[3]["status"] == 503
