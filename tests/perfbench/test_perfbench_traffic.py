"""The general traffic generator: every query of a run is distinct,
warm-up and window share none, every seed sends the same mix in another
order, and a mix that is too small for its window says so."""
import collections
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


def _traffic(cell, seed):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    config = _json("configs", w["config"] + ".json")
    gen = importlib.import_module("perfbench.datagen." + config["datagen"])
    mix = _json("traffic", w["traffic"] + ".json")
    return loadgen.Traffic(mix, gen.pools(config), seed, budget=5000), mix


def _canonical(q):
    """Commutative two-operand forms compare unordered."""
    m = re.fullmatch(r"Count\((Intersect|Union|Xor)\((Bitmap\([^)]*\)), "
                     r"(Bitmap\([^)]*\))\)\)", q)
    return (m.group(1), frozenset(m.groups()[1:])) if m else q


@pytest.mark.parametrize("cell", CELLS)
def test_every_query_of_a_run_is_distinct(cell):
    traffic, mix = _traffic(cell, 2147483999)
    warm = [q for phase in traffic.ladder(mix["warmup"]["ladder_rounds"])
            for s in phase for q in s if q is not None]
    warm += [q for s in traffic.mixed_warm() for q in s]
    window = [q for k in range(traffic.clients)
              for q in itertools.islice(traffic.window(k), 300)]
    assert None not in window
    every = [_canonical(q) for q in warm + window]
    assert len(set(every)) == len(every)
    assert 0 < len(warm) <= (mix["warmup"]["reserve_per_form"]
                             * len(mix["forms"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_sends_the_same_mix_in_another_order(cell):
    form_of = lambda q: re.sub(r'frame="\w+", rowID=\d+', "ROW", q)
    counts, orders = [], []
    for seed in (1, 2, 3_000_000_011):
        traffic, mix = _traffic(cell, seed)
        deck = sum(f["weight"] for f in mix["forms"])
        qs = list(itertools.islice(traffic.window(0), deck * 25))
        counts.append(collections.Counter(form_of(q) for q in qs))
        orders.append(qs)
    assert counts[0] == counts[1] == counts[2]
    assert orders[0] != orders[1] != orders[2]


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
