"""BENCHMARK.json against the builder's contract and against the files
it names: every entry has its file, every file its entry, and no name
of a cell or a configuration is written into the harness."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _pb(*parts):
    return os.path.join(ROOT, "perfbench", *parts)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert BENCH["command"][:2] == ["python3", "perfbench/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    # The yardstick's own tests are the yardstick's: under ``paths``.
    assert os.path.relpath(os.path.dirname(os.path.abspath(__file__)),
                           ROOT) in BENCH["paths"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
    assert len(cfg["source"]) <= 200 and "\n" not in cfg["source"]
    body = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    # What the comparison needs a configuration to declare.
    for key in ("guarantees", "precision", "control", "assumed", "shape",
                "datagen", "reference", "server"):
        assert key in body, key
    assert os.path.exists(_pb("datagen", body["datagen"] + ".py"))
    assert os.path.exists(_pb("reference", body["reference"] + ".py"))
    assert os.path.exists(_pb("rehearsal", cfg["name"] + ".json"))
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def test_configurations_differ_in_source_and_file():
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    # BENCHMARK.json is the one statement of a cell: no file repeats it.
    assert not os.path.exists(_pb("workloads"))
    assert os.path.exists(_pb("traffic", cell["traffic"] + ".json"))
    cfg = json.load(open(_pb("configs", cell["config"] + ".json")))
    assert cfg["server"]["chips"] == cell["chips"]


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry_and_reader(m):
    end_to_end = m in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    src = open(_pb("metrics", m["name"] + ".py")).read()
    assert "def read(ctx)" in src or "read = " in src
    cells = [w["name"] for w in BENCH["workloads"]]
    for c in m.get("workloads", []):
        assert c in cells
    if end_to_end:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for c in m.get("workloads", cells):
            assert _reports(moved, c), (m["name"], c)
        assert f"Moves {m['moves']}" in src.replace("\n", " ")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in BENCH["per_layer"])


def test_every_file_under_perfbench_serves_a_listed_cell():
    """Nothing rides along: each configuration, mix, rehearsal size,
    generator and reference under ``perfbench/`` is one that a listed
    cell names."""
    cfgs = {c["name"]: json.load(open(os.path.join(ROOT, c["file"])))
            for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == set(cfgs)

    def stems(d, ext):
        return {f[:-len(ext)] for f in os.listdir(_pb(d))
                if f.endswith(ext) and f != "__init__.py"}

    assert stems("configs", ".json") == set(cfgs)
    assert stems("rehearsal", ".json") == set(cfgs)
    assert stems("traffic", ".json") == {w["traffic"]
                                         for w in BENCH["workloads"]}
    assert stems("datagen", ".py") == {c["datagen"] for c in cfgs.values()}
    assert stems("reference", ".py") == {c["reference"]
                                         for c in cfgs.values()}
    assert sorted(os.listdir(_pb())) == sorted(
        ["__init__.py", "run.py", "configs", "datagen", "lib", "metrics",
         "recorded", "reference", "rehearsal", "traffic"]
        + [d for d in os.listdir(_pb()) if d == "__pycache__"])


def test_no_metric_file_without_an_entry():
    named = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    files = {f[:-3] for f in os.listdir(_pb("metrics"))
             if f.endswith(".py") and f != "__init__.py"}
    assert files == named


def test_the_harness_names_no_cell_configuration_or_mix():
    words = ({w["name"] for w in BENCH["workloads"]}
             | {c["name"] for c in BENCH["configs"]}
             | {w["traffic"] for w in BENCH["workloads"]})
    for rel in ("run.py", "lib/loadgen.py", "lib/serverproc.py",
                "lib/layer.py", "lib/stats.py", "lib/xplane.py"):
        src = open(_pb(rel)).read()
        assert "import jax" not in src and "from jax" not in src
        for w in words:
            assert w not in src, (rel, w)
