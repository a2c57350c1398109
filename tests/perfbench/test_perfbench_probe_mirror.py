"""``probe_mirror_hit_pct.chem`` (PR 29) on a hand-made log: the share
of per-fragment TopN scans whose probe was read from the HBM mirror,
and None, never 0, where there is nothing to read."""
import pytest

from perfbench import run

NAME = "probe_mirror_hit_pct.chem"


def _ctx(*resources):
    log = [{"t0": 100.0 + k, "t1": 100.5 + k, "ok": True, "pql": f"q{k}",
            "profile": {"spans": [], "resources": res}}
           for k, res in enumerate(resources)]
    return run.Context(log=log, trace=None, trace_t0=None)


@pytest.mark.parametrize("resources,want", [
    # every request's one fragment took its probe from the mirror
    ([{"topnProbeFromMirror": 1, "topnProbeFromHost": 0}] * 3, 100.0),
    # four scans, one of them through host words
    ([{"topnProbeFromMirror": 1, "topnProbeFromHost": 0},
      {"topnProbeFromMirror": 2, "topnProbeFromHost": 1}], 75.0),
    # every probe through the host: a real 0, not a missing value
    ([{"topnProbeFromMirror": 0, "topnProbeFromHost": 2}], 0.0),
    # an older program among newer ones: its request counts for nothing
    ([{"topnRowsScanned": 500000},
      {"topnProbeFromMirror": 1, "topnProbeFromHost": 1}], 50.0),
], ids=["all-mirror", "mixed", "all-host", "older-among-newer"])
def test_share_of_probes_read_from_the_mirror(resources, want):
    assert run.load_metric(NAME).read(_ctx(*resources)) == want


@pytest.mark.parametrize("resources", [
    [],                                                  # no request
    [{"topnRowsScanned": 500000, "topnKept": 50}],       # the parent's profile
    [{"topnProbeFromMirror": 0, "topnProbeFromHost": 0}],   # no scan had a src
    [{"topnProbeFromMirror": 4}],                        # half the pair
], ids=["empty", "parent", "no-src", "half"])
def test_nothing_to_read_is_none_never_zero(resources):
    assert run.load_metric(NAME).read(_ctx(*resources)) is None


def test_requests_without_a_profile_are_skipped():
    ctx = _ctx({"topnProbeFromMirror": 2, "topnProbeFromHost": 0})
    ctx.log.append({"t0": 1.0, "t1": 1.1, "ok": True, "pql": "plain"})
    assert run.load_metric(NAME).read(ctx) == 100.0
