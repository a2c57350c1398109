"""The benchmark's PQL reader: conditions (``field OP value``), lists of
integers, what is refused, and that every text the two standing mixes
render still parses to the tree their references and bytes models read:
the one the form states, and the one the reader of PR 30's parent gave."""
import importlib
import json
import os
import re

import pytest

from perfbench.lib import loadgen, pql
from perfbench.lib.pql import Cond

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _shape(call):
    """(name, children's shapes, argument names in the order written)."""
    return (call.name, tuple(_shape(c) for c in call.children),
            tuple(call.args))


@pytest.mark.parametrize("n", [25, 0, -7])
@pytest.mark.parametrize("op", ["==", "!=", "<", "<=", ">", ">="])
def test_a_condition_with_an_integer(op, n):
    for text in (f"Range(lo_quantity {op} {n})", f"Range(lo_quantity{op}{n})"):
        call = pql.parse(text)
        assert _shape(call) == ("Range", (), ("lo_quantity",))
        assert call.args["lo_quantity"] == Cond(op, n) == (op, n)
        assert pql.conditions(call) == [(None, "lo_quantity", Cond(op, n))]
        assert pql.leaves(call) == []


@pytest.mark.parametrize("low, high", [(1, 3), (0, 0), (-5, -1), (-2, 10)])
def test_a_window_is_a_pair(low, high):
    call = pql.parse(f'Range(frame="lo", lo_discount >< [{low}, {high}])')
    assert call.args == {"frame": "lo",
                         "lo_discount": Cond("><", (low, high))}
    assert pql.conditions(call) == [("lo", "lo_discount",
                                     Cond("><", (low, high)))]


@pytest.mark.parametrize("text", [
    'Range(frame="lo", lo_quantity < 25)',
    'Range(lo_quantity < 25, frame="lo")'], ids=["after", "before"])
def test_a_condition_beside_its_frame_in_either_order(text):
    call = pql.parse(text)
    assert set(call.args) == {"frame", "lo_quantity"}
    assert pql.conditions(call) == [("lo", "lo_quantity", Cond("<", 25))]


ROW = 'Bitmap(frame="d_year", rowID=1993)'
WINDOW = 'Range(frame="lo", lo_discount >< [1, 3])'
BOUND = 'Range(frame="lo", lo_quantity < 25)'
ROW_SHAPE = ("Bitmap", (), ("frame", "rowID"))
WINDOW_SHAPE = ("Range", (), ("frame", "lo_discount"))
BOUND_SHAPE = ("Range", (), ("frame", "lo_quantity"))
BOTH = [("lo", "lo_discount", Cond("><", (1, 3))),
        ("lo", "lo_quantity", Cond("<", 25))]


@pytest.mark.parametrize("text, shape, n_leaves, conds", [
    (f"Intersect({ROW}, {WINDOW}, {BOUND})",
     ("Intersect", (ROW_SHAPE, WINDOW_SHAPE, BOUND_SHAPE), ()), 1, BOTH),
    (f"Union({BOUND}, {ROW}, {WINDOW})",
     ("Union", (BOUND_SHAPE, ROW_SHAPE, WINDOW_SHAPE), ()), 1, BOTH[::-1]),
    (f"Count(Intersect({ROW}, {ROW}, {BOUND}))",
     ("Count", (("Intersect", (ROW_SHAPE, ROW_SHAPE, BOUND_SHAPE), ()),),
      ()), 2, BOTH[1:]),
    (f'Sum(Intersect({ROW}, {WINDOW}, {BOUND}), frame="lo", '
     'field="lo_revrate")',
     ("Sum", (("Intersect", (ROW_SHAPE, WINDOW_SHAPE, BOUND_SHAPE), ()),),
      ("frame", "field")), 1, BOTH),
    (f'Sum({WINDOW}, frame="lo", field="lo_revrate", lo_revrate >= 0)',
     ("Sum", (WINDOW_SHAPE,), ("frame", "field", "lo_revrate")), 0,
     BOTH[:1] + [("lo", "lo_revrate", Cond(">=", 0))]),
], ids=["Intersect", "Union", "Count", "Sum", "Sum-with-its-own"])
def test_conditions_nested_under_calls(text, shape, n_leaves, conds):
    call = pql.parse(text)
    assert _shape(call) == shape
    assert len(pql.leaves(call)) == n_leaves
    assert pql.conditions(call) == conds


@pytest.mark.parametrize("text, ids", [
    ('TopN(frame="fingerprint", n=50, ids=[1, 2, 3])', [1, 2, 3]),
    ('TopN(ids=[7], frame="fingerprint")', [7]),
    ('TopN(frame="fingerprint", ids=[-1,0, 4096])', [-1, 0, 4096]),
    ('TopN(frame="fingerprint", ids=[])', [])])
def test_a_list_of_integers_is_a_plain_value(text, ids):
    call = pql.parse(text)
    assert call.args["ids"] == ids and type(call.args["ids"]) is list
    assert call.args["frame"] == "fingerprint"
    assert pql.conditions(call) == []


@pytest.mark.parametrize("text", [
    "Range(a <)", "Range(a >< [1])", "Range(a >< [1, 2, 3])",
    "Range(a <> 3)", "Range(a >< [1, 2", "TopN(ids=[1, 2", "Range(a >< 3)",
    "Range(a < [1, 2])", 'Range(a < "x")', 'Range(a >< ["x", "y"])',
    "Range(a < 1.5)", "Range(a = = 3)", "Range(a < 3", "Range(< 3)",
    "Count(Bitmap(rowID=1)", "Count(Bitmap(rowID=1)))", "Count(a=b)",
    "Count", ""])
def test_the_malformed_is_refused(text):
    with pytest.raises(ValueError):
        pql.parse(text)


def test_times_columns_and_the_issues_query():
    call = pql.parse('Range(frame="orders", rowID=1993, '
                     'start="1992-01-01T00:00", end="1992-03-01T00:00")')
    assert call.args == {"frame": "orders", "rowID": 1993,
                         "start": "1992-01-01T00:00",
                         "end": "1992-03-01T00:00"}
    call = pql.parse('SetBit(frame="seen", rowID=1998, columnID=891041)')
    assert call.args["columnID"] == 891041
    call = pql.parse(
        'Sum(Intersect(Bitmap(frame="d_year", rowID=1993), Range(frame="lo", '
        'lo_discount >< [1, 3]), Range(frame="lo", lo_quantity < 25)), '
        'frame="lo", field="lo_revrate")')
    assert pql.conditions(call) == [
        ("lo", "lo_discount", Cond("><", (1, 3))),
        ("lo", "lo_quantity", Cond("<", 25))]
    assert [leaf.args for leaf in pql.leaves(call)] \
        == [{"frame": "d_year", "rowID": 1993}]


# -- the two standing mixes ------------------------------------------------

def _two(op):
    return ("Count", ((op, (ROW_SHAPE, ROW_SHAPE), ()),), ())


_TOPN = ("TopN", (ROW_SHAPE,), ("frame", "n", "tanimotoThreshold"))
# (configuration, traffic mix): the tree each of its forms states.
STANDING = {
    ("segmentation-1b", "count-mixed-c1"): [
        _two("Intersect"), _two("Union"), _two("Difference"), _two("Xor"),
        ("Count", (("Intersect", (ROW_SHAPE, ("Difference",
                                              (ROW_SHAPE, ROW_SHAPE), ())),
                    ()),), ())],
    ("chem-500k", "tanimoto-mixed-c1"): [_TOPN, _TOPN, _TOPN]}

_PARENT_TOKEN = re.compile(
    r'\s*(?:([A-Za-z_][A-Za-z0-9_]*)|(-?\d+)|"([^"]*)"|(.))')


def _parent_parse(text):
    """The reader as it stood at PR 30's parent (d4848fb), to the tree
    as nested tuples."""
    tokens = [(m.lastindex, m.group(m.lastindex))
              for m in _PARENT_TOKEN.finditer(text) if m.group(0).strip()]

    def call_at(i):
        kind, name = tokens[i]
        assert kind == 1 and tokens[i + 1] == (4, "(")
        i += 2
        children, args = [], {}
        while tokens[i] != (4, ")"):
            if tokens[i] == (4, ","):
                i += 1
            elif tokens[i][0] == 1 and tokens[i + 1] == (4, "="):
                kind, val = tokens[i + 2]
                assert kind in (2, 3)
                args[tokens[i][1]] = int(val) if kind == 2 else val
                i += 3
            else:
                child, i = call_at(i)
                children.append(child)
        return (name, tuple(children), tuple(args.items())), i + 1

    tree, i = call_at(0)
    assert i == len(tokens)
    return tree


def _tree(call):
    return (call.name, tuple(_tree(c) for c in call.children),
            tuple(call.args.items()))


def _read(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("config, traffic, form", [
    (c, t, i) for (c, t), shapes in STANDING.items()
    for i in range(len(shapes))])
def test_what_a_standing_mix_renders_parses_as_before(config, traffic, form):
    """Up to 2,000 queries of the form (all there are, where a seed has
    fewer than 667) from three seeds."""
    cfg, mix = _read("configs", config + ".json"), \
        _read("traffic", traffic + ".json")
    assert len(mix["forms"]) == len(STANDING[config, traffic])
    gen = importlib.import_module("perfbench.datagen." + cfg["datagen"])
    pools, n = gen.pools(cfg), 0
    for seed in (1, 2, 3_000_000_011):
        t = loadgen.Traffic(mix, pools, seed, budget=700)
        for j in range(667):
            q = t._render(form, j)
            if q is None:
                break
            call = pql.parse(q)
            assert q.form == form
            assert _shape(call) == STANDING[config, traffic][form]
            assert len(pql.leaves(call)) == len(mix["forms"][form]["operands"])
            assert pql.conditions(call) == []
            assert _tree(call) == _parent_parse(q)
            n += 1
    assert n >= 3 * 496
