"""``leaf_memo_hit_pct.c1`` (PR 25) on a hand-made log: the share of
fragment lists served from the plan cache's ``leaf`` entries, and None,
never 0, where there is nothing to read."""
import pytest

from perfbench import run

NAME = "leaf_memo_hit_pct.c1"


def _ctx(*resources):
    log = [{"t0": 100.0 + k, "t1": 100.5 + k, "ok": True, "pql": f"q{k}",
            "profile": {"spans": [], "resources": res}}
           for k, res in enumerate(resources)]
    return run.Context(log=log, trace=None, trace_t0=None)


@pytest.mark.parametrize("resources,want", [
    # three requests, 8 lists, one of them walked
    ([{"leafMemoHits": 3, "leafMemoMisses": 0},
      {"leafMemoHits": 2, "leafMemoMisses": 1},
      {"leafMemoHits": 2, "leafMemoMisses": 0}], 87.5),
    # every list walked: a real 0, not a missing value
    ([{"leafMemoHits": 0, "leafMemoMisses": 3}], 0.0),
    # an older program among newer ones: its request counts for nothing
    ([{"stackBuilds": 0},
      {"leafMemoHits": 1, "leafMemoMisses": 1}], 50.0),
], ids=["mixed", "all-walked", "older-among-newer"])
def test_share_of_lists_served_from_the_memo(resources, want):
    assert run.load_metric(NAME).read(_ctx(*resources)) == want


@pytest.mark.parametrize("resources", [
    [],                                            # no request
    [{"stackBuilds": 0, "planMs": 3.2}],           # the parent's profile
    [{"leafMemoHits": 0, "leafMemoMisses": 0}],    # no list was needed
    [{"leafMemoHits": 4}],                         # half the pair
], ids=["empty", "parent", "no-lists", "half"])
def test_nothing_to_read_is_none_never_zero(resources):
    assert run.load_metric(NAME).read(_ctx(*resources)) is None


def test_requests_without_a_profile_are_skipped():
    ctx = _ctx({"leafMemoHits": 2, "leafMemoMisses": 0})
    ctx.log.append({"t0": 1.0, "t1": 1.1, "ok": True, "pql": "plain"})
    assert run.load_metric(NAME).read(ctx) == 100.0
