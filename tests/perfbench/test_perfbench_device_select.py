"""``topn_device_select_pct.chem`` (PR 36) on a hand-made log: the share
of per-fragment TopN scans whose top n the scan's program selected, and
None, never 0, where there is nothing to read. And a run of the TopN
cell at the rehearsal size whose device tail is broken underneath (the
ENGINE of the server child, altered through a ``sitecustomize`` module
on its ``PYTHONPATH``): a bucket with no room for a tie and the rows of
a tie taken from the high ids down keep every answer right while the
host's ``n_ge`` check sends the overflows to the counts; with the check
dropped the run reads ``correct: false``."""
import argparse
import json
import textwrap

import pytest

from perfbench import run as pbrun

NAME = "topn_device_select_pct.chem"
CELL = "chem500k-tanimoto-c1"


def _ctx(*resources):
    log = [{"t0": 100.0 + k, "t1": 100.5 + k, "ok": True, "pql": f"q{k}",
            "profile": {"spans": [], "resources": res}}
           for k, res in enumerate(resources)]
    return pbrun.Context(log=log, trace=None, trace_t0=None)


def _res(device=0, host=0, overflow=0):
    return {"topnSelectDevice": device, "topnSelectHost": host,
            "topnSelectOverflow": overflow}


@pytest.mark.parametrize("resources,want", [
    # every request's one fragment was selected inside its program
    ([_res(device=1)] * 3, 100.0),
    # eight scans: one overflowed its bucket, one had explicit ids
    ([_res(device=3), _res(device=3, overflow=1), _res(host=1)], 75.0),
    # every scan selected on the host: a real 0, not a missing value
    ([_res(host=2), _res(overflow=1)], 0.0),
    # an older program among newer ones: its request counts for nothing
    ([{"topnRowsScanned": 500000}, _res(device=1, host=1)], 50.0),
], ids=["all-device", "mixed", "none-device", "older-among-newer"])
def test_share_of_scans_selected_on_the_device(resources, want):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) == want


@pytest.mark.parametrize("resources", [
    [],                                                  # no request
    [{"topnRowsScanned": 500000, "topnProbeFromMirror": 1}],  # the parent
    [_res()],                                            # no scan had a src
    [{"topnSelectDevice": 4, "topnSelectHost": 0}],      # part of the three
], ids=["empty", "parent", "no-src", "partial"])
def test_nothing_to_read_is_none_never_zero(resources):
    assert pbrun.load_metric(NAME).read(_ctx(*resources)) is None


def test_requests_without_a_profile_are_skipped():
    ctx = _ctx(_res(device=2))
    ctx.log.append({"t0": 1.0, "t1": 1.1, "ok": True, "pql": "plain"})
    assert pbrun.load_metric(NAME).read(ctx) == 100.0


def test_the_benchmark_lists_the_metric_in_the_chem_cell_only():
    """An addition: appended after what the benchmark had, by name."""
    bench = json.load(open(pbrun.os.path.join(pbrun.ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) > names.index("probe_share_pct.ev")
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "fragment selection",
        "moves": "query_p50_ms", "workloads": [CELL]}


# ------------------------------------------- a broken device tail

SITECUSTOMIZE = textwrap.dedent('''
    """The test's fault, applied to the server child as it starts."""
    import os

    FAULT = os.environ.get("PERFBENCH_TEST_FAULT")
    if FAULT:
        from pilosa_tpu.ops import topn

        # A bucket of exactly n: a tie across the cut overflows it.
        topn.select_k = lambda n: n
        top_k, fetch = topn._top_k_exact, topn.fetch_counts

        def from_the_high_rows_down(cand, k):
            """The device's order inside a tie is its own: here the
            reverse of the host's (the cell's rows lie in id order)."""
            vals, rows = top_k(cand[::-1], k)
            return vals, cand.shape[0] - 1 - rows

        def check_dropped(fn, *args, **static):
            out = fetch(fn, *args, **static)
            if static and FAULT == "tie_check_dropped":
                out = out.copy()
                out[-1] = 0            # n_ge: "no more rows tie"
            return out

        topn._top_k_exact = from_the_high_rows_down
        topn.fetch_counts = check_dropped
''')


def _run(tmp_path, monkeypatch, fault):
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(SITECUSTOMIZE)
    monkeypatch.setenv("PYTHONPATH", str(site))
    monkeypatch.setenv("PERFBENCH_TEST_FAULT", fault)
    args = argparse.Namespace(workload=CELL, seed=2_147_483_777, seconds=1.0,
                              trace=0, rehearse=True, control=False)
    data = tmp_path / "data"
    data.mkdir()
    return pbrun.run(args, str(tmp_path), str(data))


def test_a_tight_bucket_is_still_exact_while_the_tie_check_stands(
        tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch, "tight_bucket")
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatched"] == [0, 0]
    assert out["compared"]["compared"][0] == out["attempted"] > 0


def test_a_device_tail_without_its_tie_check_is_not_correct(
        tmp_path, monkeypatch, capsys):
    out = _run(tmp_path, monkeypatch, "tie_check_dropped")
    assert out["correct"] is False
    mismatched = out["compared"]["mismatched"][0]
    assert out["compared"]["failed"][0] == 0 and out["failed"] == mismatched
    assert 1 <= mismatched < out["attempted"]
    report = json.load(open(tmp_path / "mismatch.json"))
    assert len(report["mismatched"]) == min(mismatched, 50)
    # Every wrong answer has the right counts: only WHICH rows of the
    # tie at the cut were kept differs, higher ids for lower.
    for m in report["mismatched"]:
        rows = m["differing_rows"]
        lost = [r for r in rows if r["got"] is None]
        extra = [r for r in rows if r["want"] is None]
        assert m["got_len"] == m["want_len"] and lost and extra
        assert len(lost) + len(extra) == len(rows)
        assert {r["want"] for r in lost} == {r["got"] for r in extra}
        assert len({r["want"] for r in lost}) == 1
        assert max(r["id"] for r in lost) < min(r["id"] for r in extra)
    assert '"mismatch_report"' in capsys.readouterr().out
