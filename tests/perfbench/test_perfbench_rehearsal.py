"""Every cell's control flow, end to end, as the driver calls it, at the
rehearsal sizes on the CPU backend: the last line of stdout has the
contract's keys and says ``cpu`` wherever it names a device; and the
harness refuses to measure where the server's device is not a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _harness(tmp_path, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="17",
               TMPDIR=str(tmp_path))
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:] + list(argv)
        + ["--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


CELLS = BENCH["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in CELLS])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_every_cell(tmp_path, cell, trace):
    p = _harness(tmp_path, "--workload", cell, "--seed", "2147483949",
                 "--seconds", "1", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["rehearsal"] is True
    assert last["metrics"] == {}               # no device metric off-chip
    assert last["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in CELLS if w["name"] == cell)
    assert last["device"]["count"] == chips
    assert list(last)[-1] == "compared"
    assert json.loads(p.stderr.strip().splitlines()[-1])["compared"] \
        == last["compared"]
    if trace:
        assert "compiles_in_window" in last["rehearsal_values"]
        assert last["breakdown"]["idle_gaps"]
    # Every request of the window is written down with the form of the
    # mix it was, for whoever splits a run's latency by form.
    out = tmp_path / "out" / f"{cell}-2147483949-t{trace}" / "requests.jsonl"
    sent = [json.loads(line) for line in out.read_text().splitlines()]
    traffic = next(w["traffic"] for w in CELLS if w["name"] == cell)
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           traffic + ".json")) as f:
        forms = json.load(f)["forms"]
    assert len(sent) == last["attempted"]
    for r in sent:
        shell = re.split(r"\{\w+\}", forms[r["form"]]["pql"])
        assert r["pql"].startswith(shell[0]) and r["pql"].endswith(shell[-1])
    assert not os.listdir(tmp_path) or os.listdir(tmp_path) == ["out"]


def test_no_measurement_where_the_device_is_not_a_tpu(tmp_path):
    p = _harness(tmp_path, "--workload", BENCH["workloads"][0]["name"],
                 "--seed", "5", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr
