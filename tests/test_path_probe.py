"""PR 35: a launch-site span names the program it launches, and the path
model's looks at the loser run under ``path.probe`` and are counted,
traced or not.

The served programs (a batched Count, a batched Sum, a per-fragment TopN
with a src) are driven over HTTP; the path model on a bare ``Executor``
with hand-made map, reduce and batch functions, so that which pick is a
probe is read from ``_path_choice`` itself, query by query."""
import json
import threading
import time
import urllib.request

import pytest

from pilosa_tpu import SLICE_WIDTH, querystats, tracing
from pilosa_tpu import executor as executor_mod
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import topn as topn_ops
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.server.server import Server


def _post(s, path, body):
    req = urllib.request.Request(f"http://{s.host}{path}",
                                 data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read() or b"{}")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three rows over two slices in frame ``f`` and an int field
    ``v`` in frame ``g``."""
    s = Server(str(tmp_path_factory.mktemp("d")), bind="localhost:0").open()
    _post(s, "/index/i", "{}")
    _post(s, "/index/i/frame/f", "{}")
    _post(s, "/index/i/frame/g", json.dumps({"options": {
        "rangeEnabled": True,
        "fields": [{"name": "v", "type": "int", "min": 0, "max": 100}]}}))
    for sl in range(2):
        for r in (1, 2, 3):
            for c in range(r + 1):
                col = sl * SLICE_WIDTH + r + c
                _post(s, "/index/i/query",
                      f'SetBit(frame="f", rowID={r}, columnID={col})')
                _post(s, "/index/i/query",
                      f'SetFieldValue(frame="g", columnID={col}, v={r + c})')
    yield s
    s.close()


def _profiled(s, pql):
    return _post(s, "/index/i/query?profile=true", pql)


# --------------------------------------- the launch site names its program

PROGRAM_CASES = {
    "count": ('Count(Intersect(Bitmap(frame="f", rowID=1), '
              'Bitmap(frame="f", rowID=2)))',
              "batched", "_run_count_split", "kernel.dispatch",
              "pilosa_count_batched_k2"),
    "sum": ('Sum(Bitmap(frame="f", rowID=2), frame="g", field="v")',
            "batched", "_run_outputs_split", "kernel.dispatch",
            "pilosa_sum_batched_k1"),
    "topn": ('TopN(Bitmap(frame="f", rowID=3), frame="f", n=2, '
             'tanimotoThreshold=10)',
             "serial", "fetch_counts", "top.kernel",
             # phase 1 selects inside its program; the explicit-ids
             # re-query takes the counts
             (topn_ops.tanimoto_select_at.__name__,
              topn_ops.TANIMOTO_FRAGMENT_PROBE_PROGRAM)),
}


@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_the_launch_site_span_names_the_jitted_function(
        served, monkeypatch, case):
    """``program`` is the ``__name__`` of the very function the site
    enqueues: what ``_cached_fn`` set from ``program_name()``, or the
    per-fragment scan's constant; ``jit_`` + it is how the launch
    reads on a device trace's ``XLA Modules`` line. The spans' other
    tags stay."""
    pql, path, site, span_name, want = PROGRAM_CASES[case]
    owner = topn_ops if site == "fetch_counts" else executor_mod
    real, fns = getattr(owner, site), []

    def spy(fn, *a, **k):
        fns.append(fn)
        return real(fn, *a, **k)

    monkeypatch.setattr(owner, site, spy)
    monkeypatch.setattr(served.executor, "_force_path", path)
    prof = _profiled(served, pql)["profile"]
    assert fns, "the traced launch site did not run"
    spans = [sp for sp in prof["spans"] if sp["name"] == span_name]
    assert len(spans) == len(fns)
    for sp, fn in zip(spans, fns):
        assert sp["tags"]["program"] == fn.__name__
    assert sorted({fn.__name__ for fn in fns}) == sorted(
        want if isinstance(want, tuple) else (want,))
    extra = {"scanned"} if case == "topn" else set()
    assert set(spans[0]["tags"]) == {"program"} | extra
    names = [sp["name"] for sp in prof["spans"]]
    wait, fetch = (("top.wait", "top.fetch") if case == "topn"
                   else ("kernel.wait", "kernel.fetch"))
    at = names.index(span_name)
    assert names[at + 1:at + 3] == [wait, fetch]


def test_the_untraced_arms_are_the_parents(served, monkeypatch):
    """No trace: no split runner, no ``Span``, the same answers."""
    def refuse(*a, **k):
        raise AssertionError("ran without a trace")

    monkeypatch.setattr(executor_mod, "_run_count_split", refuse)
    monkeypatch.setattr(executor_mod, "_run_outputs_split", refuse)
    monkeypatch.setattr(tracing.Span, "__init__", refuse)
    monkeypatch.setattr(served.executor, "_force_path", "batched")
    got = _post(served, "/index/i/query",
                'Count(Intersect(Bitmap(frame="f", rowID=2), '
                'Bitmap(frame="f", rowID=3)))'
                'Sum(Bitmap(frame="f", rowID=3), frame="g", field="v")')
    # A column's value is its offset in its slice: row 3 holds 3..6.
    assert got["results"] == [4, {"sum": 2 * (3 + 4 + 5 + 6), "count": 8}]
    monkeypatch.setattr(served.executor, "_force_path", "serial")
    got = _post(served, "/index/i/query",
                'TopN(Bitmap(frame="f", rowID=2), frame="f", n=1, '
                'tanimotoThreshold=10)')
    assert got["results"][0][0]["id"] == 2


# ------------------------------------------------- which pick is a probe

COUNT = parse('Count(Intersect(Bitmap(frame="f", rowID=1), '
              'Bitmap(frame="f", rowID=2)))').calls[0]


def _bare_executor():
    e = Executor.__new__(Executor)      # the path model touches no holder
    e._path_stats, e._path_mu, e._force_path = {}, threading.Lock(), None
    return e


def _picks(e, slices, n_queries, settle):
    """(n, choice, probe) of ``n_queries`` picks; ``settle`` sets the
    minima after each pick, as the runs' records would."""
    out = []
    for _ in range(n_queries):
        choice, probe, st, n, _ = e._path_choice(COUNT, slices)
        out.append((n, choice, probe))
        settle(st, choice)
    return out


def _batched_wins(st, choice):
    st["b"], st["s"] = 0.001, 0.050


def _serial_wins(st, choice):
    st["b"], st["s"] = 0.004, 0.002


def test_probes_are_the_explorations_turns_and_the_64th_querys_look():
    """Within SERIAL_PROBE_MAX_SLICES, batched the winner: queries 2-11
    alternate and each is a measurement; then one pick in 64 looks at
    the loser (serial); every other pick is the steady ``batched``."""
    got = _picks(_bare_executor(), list(range(64)), 200, _batched_wins)
    assert [p for p in got if p[2]] == (
        [(n, "serial" if n % 2 else "batched", True) for n in range(2, 12)]
        + [(63, "serial", True), (127, "serial", True),
           (191, "serial", True)])
    assert {c for _, c, probe in got if not probe} == {"batched"}


def test_a_first_serial_sample_is_a_probe():
    """Past the exploration with no serial minimum yet (a seeded or a
    long-lived entry): the pick that takes the first one is marked."""
    e = _bare_executor()
    key = (e._call_shape(COUNT), 7)
    e._path_stats[key] = {"n": 40, "b": 0.001}
    assert e._path_choice(COUNT, list(range(64)))[:2] == ("serial", True)
    e._path_stats[key] = {"n": 40, "b": 0.001}
    e._path_stats[(e._call_shape(COUNT), 10)] = {"n": 40, "b": 0.001}
    assert e._path_choice(COUNT, list(range(954)))[:2] == ("batched", False)


def test_no_pick_over_954_slices_is_a_probe():
    """954 slices against SERIAL_PROBE_MAX_SLICES 512: the exploration,
    the missing serial minimum and the 64th query all fall back to the
    steady ``batched``, which is no measurement."""
    assert Executor.SERIAL_PROBE_MAX_SLICES == 512
    got = _picks(_bare_executor(), list(range(954)), 200,
                 lambda st, choice: st.__setitem__("b", 0.001))
    assert {(c, probe) for _, c, probe in got} == {("batched", False)}
    # A serial minimum from elsewhere (a loaded model) changes nothing.
    got = _picks(_bare_executor(), list(range(954)), 200, _batched_wins)
    assert {(c, probe) for _, c, probe in got} == {("batched", False)}


def test_a_steady_serial_pick_is_no_probe_though_it_has_a_deadline():
    """One slice, serial the winner (the per-fragment TopN of a
    similarity search): the served ``serial`` is the model's choice,
    deadline or not; its look at the loser is a BATCHED run."""
    got = _picks(_bare_executor(), [0], 200, _serial_wins)
    steady = [p for p in got if p[0] >= 12 and p[0] % 64 != 63]
    assert {(c, probe) for _, c, probe in steady} == {("serial", False)}
    assert [p for p in got if p[0] >= 12 and p[2]] == [
        (63, "batched", True), (127, "batched", True),
        (191, "batched", True)]


# --------------------------------------- a probe's span and its counters

class _Loop:
    """A slice loop whose slices take ``slice_s`` each, and a batched
    program that answers at once: serial loses by the deadline."""

    def __init__(self, slice_s=0.0):
        self.slice_s, self.mapped, self.batched = slice_s, 0, 0

    def map_fn(self, s):
        self.mapped += 1
        if self.slice_s:
            time.sleep(self.slice_s)
        with tracing.span("inside.map"):
            return 1

    @staticmethod
    def reduce_fn(acc, v):
        return (acc or 0) + v

    def batch_fn(self, slices):
        self.batched += 1
        return len(slices)


def _serve(e, loop, slices):
    return e._local_exec(COUNT, slices, loop.map_fn, loop.reduce_fn,
                         loop.batch_fn)


def _settled(e, slices, queries=12):
    """An executor whose entry for COUNT over ``slices`` is past its
    exploration with batched the winner."""
    loop = _Loop()
    for _ in range(queries):
        assert _serve(e, loop, slices) == len(slices)
    (st,) = e._path_stats.values()
    return st


def _traced(e, loop, slices):
    tr = tracing.Tracer(ring_size=4)
    qs = querystats.QueryStats()
    with tr.start("query"), querystats.scope(qs):
        out = _serve(e, loop, slices)
    return out, tr.recent(1)[0]["spans"], qs.to_dict()


def _model(e):
    (row,) = e.path_model_snapshot().values()
    return row


def test_an_aborted_serial_probe_is_one_span_and_three_counts():
    e, slices = _bare_executor(), list(range(40))
    st = _settled(e, slices)
    assert _model(e)["probes"] == 10       # the exploration: queries 2-11
    assert _model(e)["probeAborts"] == 0
    before = _model(e)
    st["n"] = 63                           # the next pick looks at serial
    st["b"], st["s"] = 0.001, 0.002        # deadline: the 50 ms floor
    loop = _Loop(slice_s=0.02)
    out, spans, res = _traced(e, loop, slices)
    assert out == 40 and loop.batched == 1 and 2 <= loop.mapped < 40
    by = {sp["name"]: sp for sp in spans}
    probe = by["path.probe"]
    assert probe["tags"] == {"path": "serial", "outcome": "aborted",
                             "deadline_ms": 50.0, "slices": loop.mapped}
    assert probe["parentId"] == by["query"]["spanId"]
    assert by["exec.route"]["tags"] == {"choice": "serial"}
    # One span a probe, not one a slice; what the slices open of their
    # own still nests under it.
    assert "slice" not in by
    inside = [sp for sp in spans if sp["name"] == "inside.map"]
    assert len(inside) == loop.mapped
    assert {sp["parentId"] for sp in inside} == {probe["spanId"]}
    assert res["pathProbes"] == 1 and res["pathProbeAborts"] == 1
    assert res["servedBy"] == {"batched": 1}
    after = _model(e)
    assert after["probes"] == before["probes"] + 1
    assert after["probeAborts"] == 1
    assert after["probeMs"] - before["probeMs"] \
        == pytest.approx(probe["durationMs"], abs=2.0)
    assert after["probeMs"] - before["probeMs"] >= 50.0


def test_a_finished_probe_serial_or_batched_says_so():
    e, slices = _bare_executor(), list(range(6))
    st = _settled(e, slices)
    st["n"], st["b"], st["s"] = 63, 0.001, 0.002
    out, spans, res = _traced(e, _Loop(), slices)
    by = {sp["name"]: sp for sp in spans}
    assert out == 6 and by["path.probe"]["tags"] == {
        "path": "serial", "outcome": "finished", "deadline_ms": 50.0,
        "slices": 6}
    assert "slice" not in by
    assert res["pathProbes"] == 1 and res["pathProbeAborts"] == 0
    assert res["servedBy"] == {"serial": 1}
    # Serial the winner: the 64th query's look is a batched run.
    st["n"], st["b"], st["s"] = 63, 0.004, 0.002
    loop = _Loop()
    out, spans, res = _traced(e, loop, slices)
    by = {sp["name"]: sp for sp in spans}
    assert out == 6 and loop.batched == 1 and loop.mapped == 0
    assert by["path.probe"]["tags"] == {"path": "batched",
                                        "outcome": "finished"}
    assert res["pathProbes"] == 1 and res["pathProbeAborts"] == 0
    assert _model(e)["probeAborts"] == 0


def test_a_batched_probe_that_declines_is_aborted_and_served_serial():
    e, slices = _bare_executor(), list(range(6))
    st = _settled(e, slices)
    st["n"], st["b"], st["s"] = 63, 0.004, 0.002
    before = _model(e)
    loop = _Loop()
    loop.batch_fn = lambda sl: None
    out, spans, res = _traced(e, loop, slices)
    probe = next(sp for sp in spans if sp["name"] == "path.probe")
    assert out == 6 and probe["tags"] == {"path": "batched",
                                          "outcome": "aborted"}
    # The serving loop runs outside the probe and keeps its spans.
    slice_spans = [sp for sp in spans if sp["name"] == "slice"]
    assert len(slice_spans) == 6
    assert probe["spanId"] not in {sp["parentId"] for sp in slice_spans}
    assert res["pathProbes"] == 1 and res["pathProbeAborts"] == 1
    after = _model(e)
    assert (after["probes"], after["probeAborts"]) \
        == (before["probes"] + 1, before["probeAborts"] + 1)


def test_the_steady_serial_path_keeps_its_slice_spans():
    """One slice, serial the winner: ``slice`` and no ``path.probe``,
    deadline and all."""
    e = _bare_executor()
    st = _settled(e, [0])
    st["n"], st["b"], st["s"] = 20, 0.004, 0.002
    before = _model(e)
    out, spans, res = _traced(e, _Loop(), [0])
    names = [sp["name"] for sp in spans]
    assert out == 1 and "slice" in names and "path.probe" not in names
    assert res["pathProbes"] == 0 and res["pathProbeAborts"] == 0
    assert res["servedBy"] == {"serial": 1}
    after = _model(e)
    assert {k: after[k] for k in ("probes", "probeAborts", "probeMs")} \
        == {k: before[k] for k in ("probes", "probeAborts", "probeMs")}


def test_probes_are_counted_with_no_trace_active(monkeypatch):
    """The untraced run, which the end-to-end numbers come from: the
    same counts per call shape, no ``Span``, no accumulator."""
    e, slices = _bare_executor(), list(range(40))
    st = _settled(e, slices)
    real_init = tracing.Span.__init__
    monkeypatch.setattr(tracing.Span, "__init__",
                        lambda *a, **k: pytest.fail("a Span, untraced"))
    assert tracing.active_span() is None and querystats.active() is None
    before = _model(e)
    st["n"], st["b"], st["s"] = 63, 0.001, 0.002
    loop = _Loop(slice_s=0.02)
    assert _serve(e, loop, slices) == 40 and loop.batched == 1
    st["n"] = 127
    assert _serve(e, _Loop(), slices) == 40
    after = _model(e)
    assert after["probes"] == before["probes"] + 2
    assert after["probeAborts"] == before["probeAborts"] + 1
    assert after["probeMs"] >= before["probeMs"] + 50.0
    monkeypatch.setattr(tracing.Span, "__init__", real_init)


def test_a_request_that_is_no_probe_touches_nothing_new(monkeypatch):
    """A steady pick, untraced: no ``Span`` is made, ``_probe_outcome``
    is not reached, ``_record_path`` is told of no probe, the three
    keys stand; and traced, its resources read 0 for both counters."""
    e, slices = _bare_executor(), list(range(40))
    st = _settled(e, slices)
    before = _model(e)
    real_record = e._record_path

    def record(st_, path, elapsed, probe=False):
        assert not probe, "a probe's account, on no probe"
        real_record(st_, path, elapsed, probe)

    with monkeypatch.context() as m:
        m.setattr(e, "_record_path", record, raising=False)
        m.setattr(Executor, "_probe_outcome", staticmethod(
            lambda *a: pytest.fail("a probe's account, on no probe")))
        m.setattr(tracing.Span, "__init__",
                  lambda *a, **k: pytest.fail("a Span, untraced"))
        for n in (12, 13, 62, 64, 65):
            st["n"] = n
            assert _serve(e, _Loop(), slices) == 40
    out, spans, res = _traced(e, _Loop(), slices)
    assert "path.probe" not in {sp["name"] for sp in spans}
    assert res["pathProbes"] == 0 and res["pathProbeAborts"] == 0
    after = _model(e)
    assert after["queries"] == st["n"]
    assert {k: after[k] for k in ("probes", "probeAborts", "probeMs")} \
        == {k: before[k] for k in ("probes", "probeAborts", "probeMs")}


def test_a_loaded_path_model_does_not_carry_the_probe_counts():
    e, slices = _bare_executor(), list(range(6))
    _settled(e, slices)
    assert _model(e)["probes"] == 10
    saved = e.save_path_model()
    (entry,) = saved["entries"].values()
    assert set(entry) == {"b", "s", "inel"}
    fresh = _bare_executor()
    fresh.load_path_model(json.loads(json.dumps(saved)))
    assert _serve(fresh, _Loop(), slices) == 6
    row = _model(fresh)
    assert row["queries"] == Executor.PATH_SEED_N + 1
    assert (row["probes"], row["probeAborts"], row["probeMs"]) == (0, 0, 0.0)


def test_the_served_path_reports_probes_over_http(served, monkeypatch):
    """Through the server: the exploration's turns of a fresh call
    shape show in ``resources`` and in ``/debug/vars`` ``pathModel``."""
    monkeypatch.setattr(served.executor, "_force_path", None)
    shape = 'Count(Union(Bitmap(frame="f", rowID={a}), ' \
            'Bitmap(frame="f", rowID={b})))'
    seen = []
    for k, (a, b) in enumerate([(1, 2), (2, 1), (1, 3), (3, 1), (2, 3)]):
        prof = _profiled(served, shape.format(a=a, b=b))["profile"]
        probes = [sp for sp in prof["spans"] if sp["name"] == "path.probe"]
        assert len(probes) == prof["resources"]["pathProbes"]
        seen.append(len(probes))
    # Queries 0 and 1 are the first batched samples; 2, 3, 4 explore.
    assert seen == [0, 0, 1, 1, 1]
    with urllib.request.urlopen(f"http://{served.host}/debug/vars",
                                timeout=30) as resp:
        model = json.loads(resp.read())["pathModel"]
    (row,) = [v for k, v in model.items() if k.startswith("Count(Union(")]
    assert row["queries"] == 5 and row["probes"] == 3
    assert row["probeMs"] > 0
