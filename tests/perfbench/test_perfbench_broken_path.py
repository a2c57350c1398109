"""A run whose timed path is broken underneath comes out not correct.

The harness's look for a chip is skipped (the rehearsal sizes on the CPU
backend); everything after it is the run's own code: server child, load,
staging, warm-up, window, drain, reference, comparison. The answers are
altered at the one place the harness receives them, as the faults the
cell can have would alter them: one answer changed where it is produced;
part of the slices left out of every count; a request that is never
answered; an answer that is not a count."""
import argparse
import json

import pytest

from perfbench import run as pbrun
from perfbench.lib import loadgen


def _run(tmp_path, monkeypatch, cell, tamper=None):
    decode = loadgen.decode

    def tampered(log):
        decode(log)
        if tamper is not None:
            tamper(log)
        return log

    monkeypatch.setattr(loadgen, "decode", tampered)
    args = argparse.Namespace(workload=cell, seed=2_147_483_777, seconds=1.0,
                              trace=0, rehearse=True, control=False)
    data = tmp_path / "data"
    data.mkdir()
    return pbrun.run(args, str(tmp_path), str(data))


def _one_count_off(log):
    log[len(log) // 2]["result"] += 1


def _a_slice_left_out(log):
    for r in log:
        r["result"] -= r["result"] // 4


def _not_a_count(log):
    log[-1]["result"] = {"bits": [1, 2, 3]}


def _one_never_answered(log):
    log[0].update(ok=False, status=0, body=b"timed out", result=None)


def test_a_sound_run_is_correct_and_a_lost_answer_is_not(tmp_path,
                                                        monkeypatch):
    out = _run(tmp_path, monkeypatch, CELL)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatched"] == [0, 0]
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "compared"


CELL = "seg1b-count-c1"


@pytest.mark.parametrize("tamper, wrong", [
    (_one_count_off, 1),
    (_a_slice_left_out, None),
    (_not_a_count, 1),
    (_one_never_answered, 0),
], ids=lambda t: getattr(t, "__name__", str(t)))
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, capsys,
                                            tamper, wrong):
    out = _run(tmp_path, monkeypatch, CELL, tamper)
    assert out["correct"] is False
    mismatched, failed = out["compared"]["mismatched"][0], \
        out["compared"]["failed"][0]
    if wrong is None:
        assert mismatched == out["attempted"]       # every count is short
    else:
        assert mismatched == wrong and failed == (1 if wrong == 0 else 0)
    assert out["failed"] == mismatched + failed
    # The mismatch report: a file, and an earlier line of stdout.
    report = json.load(open(tmp_path / "mismatch.json"))
    assert len(report["mismatched"]) == min(mismatched, 50)
    assert len(report["failed"]) == failed
    assert '"mismatch_report"' in capsys.readouterr().out
