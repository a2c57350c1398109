"""The Star Schema Benchmark's flight 1 on the served path (PR 31), on the
CPU backend at a small size: the lineorder rows of
``perfbench/datagen/ssb.py`` restored through ``POST /fragment/data``
(field views and date frames), then Q1.1-Q1.3 through the HTTP handler
on the batched and on the serial path against the plain reference,
windows at the fields' ends and the plan-time shortcuts included; and a
``?profile=true`` Sum carries the spans and counters that cut it."""
import json
import os

import jax
import numpy as np
import pytest

from perfbench.datagen import ssb
from perfbench.lib import pql
from perfbench.lib.serverproc import Client, compile_calls
from perfbench.reference import ssb_flight1
from pilosa_tpu.server.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659
SUM = ('Sum(Intersect({dates}, Range(frame="lo", lo_discount {d}), '
       'Range(frame="lo", lo_quantity {q})), frame="lo", '
       'field="lo_revrate")')


def _bitmap(frame, row):
    return f'Bitmap(frame="{frame}", rowID={row})'


YEAR = _bitmap("d_year", 1993)
MONTH = _bitmap("d_yearmonthnum", 199401)
WEEK = _bitmap("d_weeknuminyear", 6) + ", " + _bitmap("d_year", 1994)

QUERIES = {
    "Q1.1": SUM.format(dates=YEAR, d=">< [1, 3]", q="< 25"),
    "Q1.2": SUM.format(dates=MONTH, d=">< [4, 6]", q=">< [26, 35]"),
    "Q1.3": SUM.format(dates=WEEK, d=">< [5, 7]", q=">< [26, 35]"),
    "discount at its low end": SUM.format(dates=YEAR, d=">< [0, 2]",
                                          q="< 25"),
    "discount at its high end": SUM.format(dates=MONTH, d=">< [8, 10]",
                                           q=">< [41, 50]"),
    "quantity < 2": SUM.format(dates=YEAR, d=">< [4, 6]", q="< 2"),
    "quantity < 50": SUM.format(dates=YEAR, d=">< [4, 6]", q="< 50"),
    # A window over a whole field: the plan's not-null leaf.
    "every discount": SUM.format(dates=WEEK, d=">< [0, 10]",
                                 q=">< [1, 10]"),
    "every quantity": SUM.format(dates=MONTH, d=">< [1, 3]", q="< 51"),
    # A bound below the field's least value: the plan's empty node.
    "no quantity": SUM.format(dates=YEAR, d=">< [1, 3]", q="< 1"),
    # A month of the date dimension on which no order falls.
    "a month without orders": SUM.format(
        dates=_bitmap("d_yearmonthnum", 199811), d=">< [1, 3]",
        q=">< [1, 10]"),
}


def _config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "ssb-sf30-flight1.json")) as f:
        config = json.load(f)
    # Two full slices and a part of a third.
    config["shape"].update(lineorder_rows=2 * (1 << 20) + 300_000, slices=3)
    return config


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    config = _config()
    server = Server(str(tmp_path_factory.mktemp("ssb") / "d"),
                    bind="localhost:0").open()
    client = Client(int(server.host.rsplit(":", 1)[1]))
    data = ssb.load(client, config, SEED, lambda *a, **k: None)
    yield server, client, ssb_flight1.Reference(config, data)
    client.close()
    server.close()


def _ask(client, query, profile=False):
    path = "/index/ssb/query" + ("?profile=true" if profile else "")
    return client.json("POST", path, query)


@pytest.mark.parametrize("path", ["batched", "serial"])
@pytest.mark.parametrize("name", list(QUERIES))
def test_flight_1_on_the_served_path_equals_the_reference(served, path,
                                                          name):
    server, client, reference = served
    server.executor._force_path = path
    want = reference.answer(QUERIES[name])
    doc = _ask(client, QUERIES[name], profile=True)
    assert doc["results"] == [want]
    assert doc["profile"]["resources"]["servedBy"] == {path: 1}
    assert (want["count"] == 0) == (name in ("no quantity",
                                             "a month without orders"))


@pytest.mark.parametrize("path", ["batched", "serial"])
def test_a_restored_field_view_is_read_back_by_range(served, path):
    server, client, reference = served
    server.executor._force_path = path
    cube = reference.cube.counts
    for cond, want in (("lo_quantity >< [26, 35]", cube[:, :, 26:36].sum()),
                       ("lo_discount == 10", cube[:, 10, :].sum()),
                       ("lo_quantity < 2", cube[:, :, 1].sum()),
                       ("lo_revrate > 67108863", None)):
        got = _ask(client, f'Count(Range(frame="lo", {cond}))')["results"][0]
        if want is None:            # the top plane of the summed field
            assert 0 < got < cube.sum() // 20
        else:
            assert got == int(want)
    # Every lineorder row has a value, the third slice's 300,000 too.
    total = _ask(client, 'Sum(frame="lo", field="lo_quantity")')["results"][0]
    assert total["count"] == 2 * (1 << 20) + 300_000 == int(cube.sum())
    assert total["sum"] == int((cube.sum(axis=(0, 1))
                                * reference.axes["lo_quantity"][1]).sum())


def test_the_stage_queries_answer_and_name_every_date_row(served):
    server, client, reference = served
    server.executor._force_path = None
    config = _config()
    stage = ssb.stage_queries(config)
    rows = ssb.date_rows(config)
    assert [len(r) for r in rows.values()] == [7, 84, 53]
    for q, (frame, ids), field in zip(stage, rows.items(),
                                      config["shape"]["fields"]):
        assert all(_bitmap(frame, r) in q for r in ids)
        got = _ask(client, q, profile=True)
        # Every order has a year, a month and a week: the union is all.
        assert got["results"][0]["count"] == int(reference.cube.counts.sum())
        assert got["profile"]["resources"]["servedBy"] == {"batched": 1}
        assert f'field="{field}"' in q
    assert [_ask(client, q)["results"][0] for q in stage[3:]] \
        == reference.answers(list(QUERIES.values())[:3])


# ------------------------------------------ spans, tags and counters

SUM_SPANS = {"sum.plan", "kernel:sum_batched", "kernel.fn",
             "kernel.dispatch", "kernel.wait", "kernel.fetch", "sum.reduce"}


def test_a_profiled_sum_carries_its_spans_and_counters(served):
    server, client, _ = served
    ex = server.executor
    ex._force_path = "batched"
    before = dict(ex.bsi_prelude)
    query = SUM.format(dates=_bitmap("d_year", 1996), d=">< [2, 4]",
                       q="< 31")
    doc = _ask(client, query, profile=True)
    spans = doc["profile"]["spans"]
    by_id = {sp["spanId"]: sp for sp in spans}
    call = next(sp for sp in spans if sp["name"] == "call:Sum")

    def under_call(sp):
        while sp is not None and sp is not call:
            sp = by_id.get(sp["parentId"])
        return sp is call

    named = {sp["name"]: sp for sp in spans if under_call(sp)}
    assert SUM_SPANS <= set(named)
    assert named["sum.plan"]["tags"] == {"slices": 3, "memo": "miss",
                                         "leaves": 6, "rows": 41}
    assert named["kernel.fn"]["tags"]["compile"] in (True, False)
    for name in ("kernel.fn", "kernel.dispatch", "kernel.wait",
                 "kernel.fetch"):
        assert named[name]["parentId"] == named["kernel:sum_batched"]["spanId"]
    for name in ("build.frags", "build.window", "build.args"):
        assert named[name]["parentId"] == named["sum.plan"]["spanId"]
    res = doc["profile"]["resources"]
    assert (res["bsiPreludeMisses"], res["bsiPreludeHits"]) == (1, 0)
    assert res["servedBy"] == {"batched": 1} and res["stackBuilds"] <= 1
    # One count, shown in two places: /debug/vars moves with it.
    seen = client.json("GET", "/debug/vars")
    assert seen["bsiPreludeMisses"] == before["bsiPreludeMisses"] + 1 \
        == ex.bsi_prelude["bsiPreludeMisses"]
    assert seen["bsiPreludeHits"] == before["bsiPreludeHits"]
    # The same query again finds its prelude (a pinned path skips the
    # result memo): a hit, and no build under the plan.
    again = _ask(client, query, profile=True)
    assert again["results"] == doc["results"]
    assert again["profile"]["resources"]["bsiPreludeHits"] == 1
    plan = next(sp for sp in again["profile"]["spans"]
                if sp["name"] == "sum.plan")
    assert plan["tags"]["memo"] == "hit"
    assert not [sp for sp in again["profile"]["spans"]
                if sp["name"].startswith("build.")]


def test_an_untraced_sum_emits_no_span(served, monkeypatch):
    """Outside a trace the call is one expression: no span object is
    made, the split runner is not used, and the counters still move."""
    from pilosa_tpu import executor as executor_mod
    from pilosa_tpu import tracing

    server, client, reference = served
    ex = server.executor
    ex._force_path = "batched"
    made = []
    real = tracing.Span.__init__

    def spy(self, *a, **k):
        made.append(a)
        real(self, *a, **k)

    monkeypatch.setattr(tracing.Span, "__init__", spy)
    monkeypatch.setattr(executor_mod, "_run_outputs_split",
                        lambda *a: pytest.fail("split runner untraced"))
    before = ex.bsi_prelude["bsiPreludeMisses"]
    query = SUM.format(dates=_bitmap("d_year", 1997), d=">< [6, 8]",
                       q="< 12")
    assert _ask(client, query)["results"] == [reference.answer(query)]
    assert made == []
    assert ex.bsi_prelude["bsiPreludeMisses"] == before + 1


# ------------------------- predicate bits: host arrays with the launch

def _spied_operands(server, client, monkeypatch, query):
    """The operands the batched Sum's program was called with, and the
    leaf specs they were built from (the planes of the summed field go
    first and are no leaf)."""
    from pilosa_tpu import executor as executor_mod
    from pilosa_tpu.pql.parser import parse

    ex = server.executor
    ex._force_path = "batched"
    seen = []
    real = executor_mod._run_outputs

    def spy(fn, stacks):
        seen.append(list(stacks))
        return real(fn, stacks)

    with monkeypatch.context() as patch:
        patch.setattr(executor_mod, "_run_outputs", spy)
        got = _ask(client, query)["results"]
    (stacks,) = seen
    leaves = ex._co_bsi_resolve("ssb", parse(query).calls[0])[5]
    assert len(leaves) == len(stacks) - 1
    return got, leaves, stacks


@pytest.mark.parametrize("dates,d,q,depths", [
    pytest.param(_bitmap("d_year", 1995), ">< [2, 4]", "< 24", [4, 4, 6],
                 id="Q1.1"),
    pytest.param(_bitmap("d_weeknuminyear", 9) + ", "
                 + _bitmap("d_year", 1995), ">< [3, 5]", ">< [27, 36]",
                 [4, 4, 6, 6], id="Q1.3"),
])
def test_predicate_bits_reach_the_program_as_host_arrays(
        served, monkeypatch, dates, d, q, depths):
    """A ``bits`` leaf's operand is a NumPy int32[depth] that the jitted
    call uploads: an eager ``jnp.asarray`` would be a launched program
    of its own before the scan (PR 32)."""
    server, client, reference = served
    query = SUM.format(dates=dates, d=d, q=q)
    got, leaves, stacks = _spied_operands(server, client, monkeypatch, query)
    assert got == [reference.answer(query)]
    bits = [(sp, st) for sp, st in zip(leaves, stacks[1:])
            if sp[0] == "bits"]
    assert [sp[2] for sp, _ in bits] == depths
    for sp, st in bits:
        assert type(st) is np.ndarray
        assert st.dtype == np.int32 and st.shape == (sp[2],)
        assert st.tolist() == list(sp[1])
    for st in stacks:
        if isinstance(st, jax.Array):
            assert st.ndim >= 2
        else:
            assert type(st) is np.ndarray and st.ndim == 1
    # Rows and planes stay on the device.
    assert sum(isinstance(st, jax.Array) for st in stacks) \
        == len(stacks) - len(bits)


def test_a_second_query_of_a_served_shape_compiles_nothing(served,
                                                           monkeypatch):
    """The bits travel as operands of the same aval, so distinct bounds
    share the executable: ``compileCalls`` of /debug/kernels stands
    still, and the memoised prelude hands the same host arrays back."""
    server, client, reference = served
    server.executor._force_path = "batched"
    month = _bitmap("d_yearmonthnum", 199503)
    first = SUM.format(dates=month, d=">< [2, 4]", q=">< [11, 20]")
    second = SUM.format(dates=month, d=">< [6, 8]", q=">< [31, 40]")
    assert _ask(client, first)["results"] == [reference.answer(first)]
    before = compile_calls(client)[:2]
    assert _ask(client, second)["results"] == [reference.answer(second)]
    assert compile_calls(client)[:2] == before
    # A repeat is a prelude hit: the memo pinned host arrays.
    got, leaves, stacks = _spied_operands(server, client, monkeypatch,
                                          second)
    assert got == [reference.answer(second)]
    assert compile_calls(client)[:2] == before
    assert [type(st) for sp, st in zip(leaves, stacks[1:])
            if sp[0] == "bits"] == [np.ndarray] * 4


# -------------------------- every comparison, every aggregate under it

OPS = {"==": "== 17", "!=": "!= 17", "<": "< 17", "<=": "<= 17",
       ">": "> 33", ">=": ">= 33", "><": ">< [12, 29]"}
RANGE = 'Range(frame="lo", lo_quantity {cond})'
AGGREGATES = {
    "Count": "Count(" + RANGE + ")",
    "Sum": "Sum(" + RANGE + ', frame="lo", field="lo_revrate")',
    "Min": "Min(" + RANGE + ', frame="lo", field="lo_quantity")',
    "Max": "Max(" + RANGE + ', frame="lo", field="lo_quantity")',
}


def _from_the_cube(reference, aggregate, cond):
    _, quantities = reference.axes["lo_quantity"]
    ((_, _, parsed),) = pql.conditions(
        pql.parse(RANGE.format(cond=cond)))
    picked = ssb_flight1.select(parsed, quantities)
    counts = reference.cube.counts[:, :, picked].sum(axis=(0, 1))
    if aggregate == "Count":
        return int(counts.sum())
    if aggregate == "Sum":
        return {"sum": int(reference.cube.sums[:, :, picked].sum()),
                "count": int(counts.sum())}
    held = counts.nonzero()[0]
    at = held[0] if aggregate == "Min" else held[-1]
    return {"sum": int(quantities[picked][at]), "count": int(counts[at])}


@pytest.mark.parametrize("aggregate", list(AGGREGATES))
@pytest.mark.parametrize("op", list(OPS))
def test_every_comparison_under_every_aggregate(served, op, aggregate):
    """Batched against serial against the reference's cube: the bits of
    a comparison are host arrays on the batched path, whatever reads
    them (count, sum and descent programs)."""
    server, client, reference = served
    query = AGGREGATES[aggregate].format(cond=OPS[op])
    want = _from_the_cube(reference, aggregate, OPS[op])
    for path in ("batched", "serial"):
        server.executor._force_path = path
        doc = _ask(client, query, profile=True)
        assert doc["results"] == [want], path
        assert doc["profile"]["resources"]["servedBy"] == {path: 1}
