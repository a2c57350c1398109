"""``topn_membership_hit_pct.taxi``: the share of the batched TopN
phase-1 passes of the window's profiled requests that found the grouped
frame's ranked-cache membership memoised, read from the ``resources``
blocks alone; None on a program whose profiles lack the keys, and where
no pass was made."""
import json

import pytest

from perfbench import run as pbrun

NAME = "topn_membership_hit_pct.taxi"
CELL = "taxi24m-topn-c1"


def _ctx(*resources):
    log = [{"t0": 100.0 + k, "t1": 100.5 + k, "ok": True, "pql": f"q{k}",
            "profile": {"spans": [], "resources": res}}
           for k, res in enumerate(resources)]
    return pbrun.Context(log=log, trace=None, trace_t0=None)


def _res(hits=0, misses=0):
    return {"topnMembershipHits": hits, "topnMembershipMisses": misses,
            "topnSlicesScanned": 339 * (hits + misses)}


@pytest.mark.parametrize("resources,want", [
    # every pass found the grouped frame's membership
    ([_res(hits=1)] * 4, 100.0),
    # one of eight passes built it (a frame's first)
    ([_res(misses=1)] + [_res(hits=1)] * 7, 87.5),
    # a window cut in two halves: two passes, two lists
    ([_res(misses=2), _res(hits=2)], 50.0),
    # every pass built it: a real 0, not a missing value
    ([_res(misses=1)] * 3, 0.0),
    # an older program among newer ones: its request counts for nothing
    ([{"topnSlicesScanned": 339}, _res(hits=3, misses=1)], 75.0),
], ids=["all-hits", "first-miss", "halves", "all-misses",
        "older-among-newer"])
def test_share_of_passes_that_found_the_membership(resources, want):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) == want


@pytest.mark.parametrize("resources", [
    [],                                                   # no request
    [{"topnSlicesScanned": 339, "topnSlicesLive": 122}],  # the parent
    [_res()],                                             # no phase-1 pass
    [{"topnMembershipHits": 4}],                          # half the pair
], ids=["empty", "parent", "no-pass", "partial"])
def test_nothing_to_read_is_none_never_zero(resources):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) is None


def test_failed_and_unprofiled_requests_are_not_read():
    ctx = _ctx(_res(hits=3, misses=1))
    ctx.log.append({"t0": 1.0, "t1": 1.1, "ok": True, "pql": "plain"})
    ctx.log.append({"t0": 2.0, "t1": 2.1, "ok": False, "pql": "failed",
                    "profile": {"spans": [], "resources": _res(misses=9)}})
    assert pbrun.load_metric(NAME).read(ctx) == 75.0


def test_the_benchmark_lists_the_metric_in_the_taxi_cell_only():
    """An addition: appended after what the benchmark had, by name."""
    bench = json.load(open(pbrun.os.path.join(pbrun.ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("probe_share_pct.taxi")
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "TopN phases",
        "moves": "query_p50_ms", "workloads": [CELL]}
