"""PR 35's readers under ``--rehearse``, as the driver calls the harness:
the three cells that list ``probe_share_pct`` load and read it from the
server's own ``pathModel`` on the CPU backend, the count agrees with the
``path.probe`` spans of the same window, no probe holds a ``slice`` span,
and the by-name readers, which need a device plane, print nothing there
and break nothing."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SHARES = {m["workloads"][0]: m["name"] for m in BENCH["per_layer"]
          if m["name"].startswith("probe_share_pct.")}
BY_NAME = ("launch_delay_ms.", "completion_ms.", "readback_ms.",
           "idle_outside_spans_pct.")


@pytest.mark.parametrize("cell", sorted(SHARES))
def test_probe_share_reads_under_rehearsal(tmp_path, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="17",
               TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", cell, "--seed", "2147484161", "--seconds", "2",
           "--trace", "1", "--rehearse", "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["metrics"] == {}
    values = last["rehearsal_values"]
    share = values[SHARES[cell]]
    assert share["unit"] == "%" and 0.0 <= share["value"] < 100.0
    # No device plane on the CPU backend: the by-name readers find
    # nothing and say nothing.
    assert not [k for k in values if k.startswith(BY_NAME)]
    notes = [json.loads(line) for line in p.stderr.splitlines()
             if line.startswith('{"phase"')]
    assert not [n for n in notes if n["phase"] == "chains_by_name"]
    (counted,) = [n for n in notes if n["phase"] == "path_probes"]
    assert share["value"] == pytest.approx(counted["probeMs"] / 20.0)
    # Every request of the window is profiled: the model's count and
    # the spans are the same attempts.
    out = tmp_path / "out" / f"{cell}-2147484161-t1" / "requests.jsonl"
    sent = [json.loads(line) for line in out.read_text().splitlines()]
    probes = 0
    for r in sent:
        mine = [s for s in r["spans"] if s["name"] == "path.probe"]
        assert len(mine) == r["resources"]["pathProbes"]
        aborted = [s for s in mine if s["tags"]["outcome"] == "aborted"]
        assert len(aborted) == r["resources"]["pathProbeAborts"]
        ids = {s["spanId"] for s in mine}
        assert not [s for s in r["spans"]
                    if s["name"] == "slice" and s["parentId"] in ids]
        probes += len(mine)
    assert counted["probes"] == counted["probe_spans"] == probes
    if probes:
        assert counted["probe_span_ms"] == pytest.approx(
            counted["probeMs"], rel=0.2)
        window = next(n for n in notes if n["phase"] == "window")
        assert sum(row["probes"] for row in window["pathModel"].values()) \
            >= probes
