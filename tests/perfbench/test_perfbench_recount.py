"""``topn_recount_derived_pct.taxi``: the share of the multi-slice TopN
phase 2s of the window's profiled requests that were read from the
count matrices phase 1 fetched, read from the ``resources`` blocks
alone; None on a program whose profiles lack the keys, and where no
phase 2 was reached."""
import json

import pytest

from perfbench import run as pbrun

NAME = "topn_recount_derived_pct.taxi"
CELL = "taxi24m-topn-c1"


def _ctx(*resources):
    log = [{"t0": 100.0 + k, "t1": 100.5 + k, "ok": True, "pql": f"q{k}",
            "profile": {"spans": [], "resources": res}}
           for k, res in enumerate(resources)]
    return pbrun.Context(log=log, trace=None, trace_t0=None)


def _res(derived=0, run=0):
    return {"topnRecountsDerived": derived, "topnRecountsRun": run,
            "topnCandidates": 10 * (derived + run)}


@pytest.mark.parametrize("resources,want", [
    # every phase 2 was read from phase 1's matrix
    ([_res(derived=1)] * 4, 100.0),
    # one of eight phase 1s was a serial probe: its recount ran
    ([_res(run=1)] + [_res(derived=1)] * 7, 87.5),
    # every recount ran: a real 0, not a missing value
    ([_res(run=1)] * 3, 0.0),
    # an older program among newer ones: its request counts for nothing
    ([{"topnCandidates": 64}, _res(derived=3, run=1)], 75.0),
], ids=["all-derived", "one-run", "all-run", "older-among-newer"])
def test_share_of_phase_twos_read_from_the_matrix(resources, want):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) == want


@pytest.mark.parametrize("resources", [
    [],                                                   # no request
    [{"topnCandidates": 64, "topnRecountsSkipped": 0}],   # the parent
    [_res()],                                             # no phase 2
    [{"topnRecountsDerived": 4}],                         # half the pair
], ids=["empty", "parent", "no-phase-2", "partial"])
def test_nothing_to_read_is_none_never_zero(resources):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) is None


def test_failed_and_unprofiled_requests_are_not_read():
    ctx = _ctx(_res(derived=3, run=1))
    ctx.log.append({"t0": 1.0, "t1": 1.1, "ok": True, "pql": "plain"})
    ctx.log.append({"t0": 2.0, "t1": 2.1, "ok": False, "pql": "failed",
                    "profile": {"spans": [], "resources": _res(run=9)}})
    assert pbrun.load_metric(NAME).read(ctx) == 75.0


def test_the_benchmark_lists_the_metric_in_the_taxi_cell_only():
    """An addition: appended after what the benchmark had, by name."""
    bench = json.load(open(pbrun.os.path.join(pbrun.ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("topn_membership_hit_pct.taxi")
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "TopN phases",
        "moves": "query_p50_ms", "workloads": [CELL]}
