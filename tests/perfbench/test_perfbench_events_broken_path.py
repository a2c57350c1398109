"""A run of the time-window cell whose timed path is broken underneath
comes out not correct: the whole of the run's own code at the rehearsal
size on the CPU backend, with the ENGINE of the server child altered as
the faults a view cover can have would alter it: a view dropped from a
cover; the end of a window taken inclusive; a padded slot of a bucketed
cover filled with a row from outside the cover. The server is a child
process, so the fault travels as a ``sitecustomize`` module on the
child's ``PYTHONPATH`` (the harness hands its own on) that patches the
engine as the child starts; nothing of the program knows of it."""
import argparse
import json
import textwrap

import pytest

from perfbench import run as pbrun

CELL = "events67m-window-c1"

SITECUSTOMIZE = textwrap.dedent('''
    """The test's fault, applied to the server child as it starts."""
    import datetime
    import os
    import sys

    FAULT = os.environ.get("PERFBENCH_TEST_FAULT")
    if FAULT:
        from pilosa_tpu import time_quantum as tq

        real = tq.views_by_time_range

        def pad_outside():
            """Once the executor is loaded: the slots that pad a cover to
            its bucket hold the day view of the window's END day, the
            first day outside [start, end)."""
            ex = sys.modules.get("pilosa_tpu.executor")
            if ex is None or getattr(ex.Executor, "_test_fault", False):
                return
            plan = ex.Executor._batched_plan

            def broken(self, index, call, leaves):
                before = len(leaves)
                node = plan(self, index, call, leaves)
                if (node is not None and call.name == "Range"
                        and not call.has_condition_arg()):
                    start, end = (datetime.datetime.strptime(
                        call.args[k], ex.TIME_FORMAT) for k in ("start", "end"))
                    n = len(real("standard", start, end, "YMD"))
                    outside = tq.view_by_time_unit("standard", end, "D")
                    for k in range(before + n, len(leaves)):
                        leaves[k] = leaves[k][:3] + (outside,)
                return node

            ex.Executor._batched_plan = broken
            ex.Executor._test_fault = True

        def views_by_time_range(name, start, end, quantum):
            if FAULT == "end_inclusive":
                end = end + datetime.timedelta(days=1)
            views = real(name, start, end, quantum)
            if FAULT == "view_dropped" and len(views) > 1:
                views = views[:-1]
            if FAULT == "pad_outside":
                pad_outside()
            return views

        tq.views_by_time_range = views_by_time_range
''')


def _run(tmp_path, monkeypatch, fault=None):
    site = tmp_path / "site"
    site.mkdir()
    (site / "sitecustomize.py").write_text(SITECUSTOMIZE)
    monkeypatch.setenv("PYTHONPATH", str(site))
    if fault:
        monkeypatch.setenv("PERFBENCH_TEST_FAULT", fault)
    args = argparse.Namespace(workload=CELL, seed=2_147_483_777, seconds=1.0,
                              trace=0, rehearse=True, control=False)
    data = tmp_path / "data"
    data.mkdir()
    return pbrun.run(args, str(tmp_path), str(data))


def test_a_sound_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatched"] == [0, 0]
    assert out["compared"]["compared"][0] == out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}


@pytest.mark.parametrize("fault, most", [
    ("view_dropped", True),     # a day, or a month, missing from the OR
    ("end_inclusive", True),    # one day too many, where it has data
    ("pad_outside", False),     # only covers that fall short of a bucket
])
def test_a_broken_cover_is_not_correct(tmp_path, monkeypatch, capsys, fault,
                                       most):
    out = _run(tmp_path, monkeypatch, fault)
    assert out["correct"] is False
    mismatched, failed = out["compared"]["mismatched"][0], \
        out["compared"]["failed"][0]
    assert failed == 0 and out["failed"] == mismatched
    # Every answer was compared; a window whose fault adds or drops no
    # user (it ends with the data, or its cover fills its bucket) stays
    # right.
    assert out["compared"]["compared"][0] == out["attempted"]
    assert mismatched > (0.8 if most else 0.3) * out["attempted"]
    report = json.load(open(tmp_path / "mismatch.json"))
    assert len(report["mismatched"]) == min(mismatched, 50)
    assert all(isinstance(m["difference"], int) and m["difference"]
               for m in report["mismatched"])
    assert '"mismatch_report"' in capsys.readouterr().out
