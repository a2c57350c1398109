"""A run of the Star Schema Benchmark cell whose timed path is broken
underneath comes out not correct, as ``test_perfbench_broken_path.py``
holds for the Count cell: the whole of the run's own code at the
rehearsal size on the CPU backend, the answers altered at the one place
the harness receives them, as the faults a BSI Sum can have would alter
them: a sum off by one; a count off by one; a plane dropped from every
weighted sum; an answer that never came; an answer that is no sum."""
import argparse
import json

import pytest

from perfbench import run as pbrun
from perfbench.lib import loadgen

CELL = "ssb30-flight1-c1"


def _run(tmp_path, monkeypatch, tamper=None):
    decode = loadgen.decode

    def tampered(log):
        decode(log)
        if tamper is not None:
            tamper(log)
        return log

    monkeypatch.setattr(loadgen, "decode", tampered)
    args = argparse.Namespace(workload=CELL, seed=2_147_483_777, seconds=1.0,
                              trace=0, rehearse=True, control=False)
    data = tmp_path / "data"
    data.mkdir()
    return pbrun.run(args, str(tmp_path), str(data))


def _a_sum_off_by_one(log):
    log[len(log) // 2]["result"]["sum"] += 1


def _a_count_off_by_one(log):
    log[-1]["result"]["count"] -= 1


def _a_plane_dropped(log):
    # Plane 3 of lo_revrate left out of the weighted sum: every answer
    # loses the 8s of the values that have that bit (here: of all).
    for r in log:
        r["result"]["sum"] -= 8 * r["result"]["count"]


def _not_a_sum(log):
    log[0]["result"] = 12345


def _one_never_answered(log):
    log[0].update(ok=False, status=0, body=b"timed out", result=None)


def test_a_sound_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatched"] == [0, 0]
    assert out["compared"]["compared"][0] == out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}


@pytest.mark.parametrize("tamper, wrong", [
    (_a_sum_off_by_one, 1),
    (_a_count_off_by_one, 1),
    (_a_plane_dropped, None),
    (_not_a_sum, 1),
    (_one_never_answered, 0),
], ids=lambda t: getattr(t, "__name__", str(t)))
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, capsys,
                                            tamper, wrong):
    out = _run(tmp_path, monkeypatch, tamper)
    assert out["correct"] is False
    mismatched, failed = out["compared"]["mismatched"][0], \
        out["compared"]["failed"][0]
    if wrong is None:
        # Every answer that selects a row is short; a week of 1998 past
        # the last order selects none and stays right.
        assert 0.8 * out["attempted"] < mismatched <= out["attempted"]
    else:
        assert mismatched == wrong and failed == (1 if wrong == 0 else 0)
    assert out["failed"] == mismatched + failed
    report = json.load(open(tmp_path / "mismatch.json"))
    assert len(report["mismatched"]) == min(mismatched, 50)
    assert len(report["failed"]) == failed
    assert '"mismatch_report"' in capsys.readouterr().out
