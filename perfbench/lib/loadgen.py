"""The one general traffic generator and the closed-loop driver.

A traffic mix is a data file, ``perfbench/traffic/<name>.json``:

    {"loop": "closed", "clients": 8,
     "forms": [{"pql": "Count(Intersect({a}, {b}))", "weight": 1,
                "operands": {"a": "row", "b": "row"}, "unordered": true},
               ...],
     "warmup": {"reserve_per_form": 8, "ladder_rounds": 2, "mixed_s": 5,
                "quiet_s": 3, "max_s": 600},
     "compare": {"sample": 320}}

``operands`` names, for each placeholder of ``pql``, a pool of strings
that the configuration's generator module provides (``pools(config)``).
The operands of one query are distinct entries. Every query of a run is
distinct: each form's tuples are drawn from the seed without
replacement, warm-up takes the first of them and the window the rest,
dealt round robin to the clients. ``unordered`` forms count (a, b) and
(b, a) as one query. The forms come in the order of a deck that holds
each form ``weight`` times and is shuffled anew, from the seed, every
time it runs out: every seed sends the same mix in another order.

A query knows its form: what ``ladder``, ``mixed_warm`` and ``window``
yield is a ``Query``, the text itself (a ``str``: sent, logged, parsed
and compared as before) with the index of its form in ``forms`` on it.
Nothing has to read a form back out of the text, so a mix may vary an
operand of any kind: a row, a column, a bound, a window, a time."""
import itertools
import json
import threading
import time

import numpy as np

from .serverproc import check

ENUMERATE_LIMIT = 400_000


def _tuples(form, pools, rng, need):
    """Distinct operand tuples (indices into the pools) in seeded order:
    all of them when they can be listed, else ``need`` sampled ones."""
    names = list(form["operands"])
    sizes = [len(pools[form["operands"][n]]) for n in names]
    same_pool = len(set(form["operands"].values())) == 1
    unordered = form.get("unordered", False)
    check(not unordered or (same_pool and len(names) == 2),
          f"unordered needs two operands of one pool: {form['pql']}")
    total = int(np.prod(sizes, dtype=np.float64))
    if total <= ENUMERATE_LIMIT:
        if unordered:
            out = list(itertools.combinations(range(sizes[0]), 2))
        else:
            out = [t for t in itertools.product(*map(range, sizes))
                   if not same_pool or len(set(t)) == len(t)]
        order = rng.permutation(len(out))
        return [out[i] for i in order]
    seen, out = set(), []
    while len(out) < need:
        draw = rng.integers(0, sizes, size=(need, len(sizes)))
        for t in map(tuple, draw.tolist()):
            key = tuple(sorted(t)) if unordered else t
            if key in seen or (same_pool and len(set(t)) < len(t)):
                continue
            seen.add(key)
            out.append(t)
    return out[:need]


class Query(str):
    """A query's text, with ``form``: the index in the mix's ``forms``
    of the form it was rendered from."""
    __slots__ = ("form",)

    def __new__(cls, text, form):
        self = super().__new__(cls, text)
        self.form = form
        return self


class Traffic:
    """The queries of one run, from the mix, the pools and the seed."""

    def __init__(self, mix, pools, seed, budget=200_000):
        check(mix["loop"] == "closed",
              f"loop {mix['loop']!r}: only closed loops are built yet")
        self.clients = int(mix["clients"])
        self.forms = mix["forms"]
        self.reserve = int(mix["warmup"]["reserve_per_form"])
        self.seed = seed
        self._pools = pools
        self._tuples = [
            _tuples(form, pools, np.random.default_rng([seed, 101, i]),
                    budget)
            for i, form in enumerate(self.forms)]
        self._warm_used = [0] * len(self.forms)

    def _render(self, i, j):
        form, tuples = self.forms[i], self._tuples[i]
        if j >= len(tuples):
            return None
        vals = {n: self._pools[form["operands"][n]][k]
                for n, k in zip(form["operands"], tuples[j])}
        return Query(form["pql"].format(**vals), i)

    def _warm(self, i):
        """The next reserved warm-up query of form i; None when the
        reserve is spent."""
        j = self._warm_used[i]
        if j >= self.reserve:
            return None
        self._warm_used[i] += 1
        return self._render(i, j)

    def ladder(self, rounds):
        """Warm-up phases that make every program of the cell compile:
        each form alone, from ``clients`` concurrent senders down to one
        by halves (a server that fuses concurrent queries of one form
        compiles one program per group size). Yields one list of
        per-client query lists per phase."""
        n = self.clients
        while n >= 1:
            for i in range(len(self.forms)):
                yield [[self._warm(i) for _ in range(rounds)]
                       for _ in range(n)]
            n //= 2

    def mixed_warm(self):
        """What is left of the reserve, in the window's own mix, dealt
        to the clients."""
        deck = self._deck()
        rng = np.random.default_rng([self.seed, 307])
        out = [[] for _ in range(self.clients)]
        k = 0
        while True:
            for i in rng.permutation(deck).tolist():
                q = self._warm(i)
                if q is None:
                    return out
                out[k % self.clients].append(q)
                k += 1

    def _deck(self):
        return [i for i, f in enumerate(self.forms)
                for _ in range(int(f["weight"]))]

    def window(self, client_k):
        """The window's queries of one client, without end until a form
        has no distinct query left (then None, which fails the run)."""
        rng = np.random.default_rng([self.seed, 211, client_k])
        deck = self._deck()
        cursor = [0] * len(self.forms)
        while True:
            for i in rng.permutation(deck).tolist():
                j = self.reserve + client_k + cursor[i] * self.clients
                cursor[i] += 1
                yield self._render(i, j)

    def capacity(self):
        """Queries the window can send before a form runs dry."""
        cycles = min((len(t) - self.reserve) // int(f["weight"])
                     for f, t in zip(self.forms, self._tuples))
        return cycles * len(self._deck())


def run_closed(client, path, streams, seconds, on_open=None):
    """``len(streams)`` client threads, each sending its stream's next
    query when the last one's answer has been read, until ``seconds``
    have passed; an answer in flight then is waited for. Returns the
    log and the clock reading at which the window opened."""
    logs = [[] for _ in streams]
    dry = []
    gate = threading.Barrier(len(streams) + 1)
    t_open = [0.0]

    def run(k, stream):
        gate.wait()
        stop = t_open[0] + seconds
        log = logs[k]
        for seq, pql in enumerate(stream):
            if time.perf_counter() >= stop:
                break
            if pql is None:
                dry.append(k)
                break
            t0 = time.perf_counter()
            status, body = client.send("POST", path, pql)
            t1 = time.perf_counter()
            log.append({"client": k, "seq": seq, "pql": pql, "t0": t0,
                        "t1": t1, "status": status, "body": body})
        client.close()

    threads = [threading.Thread(target=run, args=(k, s), daemon=True)
               for k, s in enumerate(streams)]
    for t in threads:
        t.start()
    t_open[0] = time.perf_counter()
    gate.wait()
    if on_open is not None:
        on_open(t_open[0])
    for t in threads:
        t.join()
    log = sorted((r for lg in logs for r in lg), key=lambda r: r["t0"])
    # A mix that a sound system exhausts is too small for the window and
    # has to say so. Where answers failed, a server that refuses at once
    # has burnt through the queries: that run goes on and is not correct.
    check(not dry or any(r["status"] != 200 for r in log),
          f"clients {dry} ran out of distinct queries: the mix is too "
          "small for this window")
    return log, t_open[0]


def decode(log):
    """Parse each answer: ``ok`` is HTTP 200 with a JSON body that has
    one result; ``result`` is that result, ``profile`` the span block
    of a ``?profile=true`` answer."""
    for r in log:
        r["ok"], r["result"], r["profile"] = False, None, None
        if r["status"] == 200:
            try:
                out = json.loads(r["body"])
                r["result"] = out["results"][0]
                r["profile"] = out.get("profile")
                r["ok"] = len(out["results"]) == 1
            except (ValueError, KeyError, IndexError, TypeError):
                pass
        if r["ok"]:
            del r["body"]
    return log
