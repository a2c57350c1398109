"""PR 35: a request's device chain paired by the program's name
(``perfbench/lib/chains.py``) and the sixteen readers on it: on a
hand-made ``.xplane.pb`` where the nearest launch is another program's,
where a request is a probe, launches twice, or is named ``top.*``; None
on an older program's log and without a trace; and on a recorded slice
of a traced chip run of the windowed-Count cell that holds a probe."""
import json
import os

import pytest

from perfbench import run
from perfbench.lib import chains, spans, xplane
from test_perfbench_spans import (ANCHOR_NS, ANCHOR_PS, MS, _plane,
                                  _request, _write_trace)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(HERE, os.pardir, os.pardir,
                                    "BENCHMARK.json")))
FAMILIES = {"launch_delay_ms": ("chem", "ssb", "ev"),
            "completion_ms": ("c1", "chem", "ssb", "ev"),
            "readback_ms": ("chem", "ssb", "ev"),
            "idle_outside_spans_pct": ("chem", "ssb", "ev"),
            "probe_share_pct": ("chem", "ssb", "ev")}
SIXTEEN = [f"{fam}.{cell}" for fam, cells in FAMILIES.items()
           for cell in cells]
K9 = "pilosa_count_batched_k9"
TOPN = "pilosa_topn_tanimoto_frag_probe_k1"


def test_the_sixteen_are_the_benchmarks_new_entries():
    listed = [m["name"] for m in BENCH["per_layer"]]
    assert listed[-16:] == SIXTEEN
    for m in BENCH["per_layer"][-16:]:
        assert len(m["workloads"]) == 1
        probe = m["name"].startswith("probe_share_pct")
        assert m["source"] == ("program_counter" if probe
                               else "device_trace")
        assert m["layer"] == ("tier choice" if probe else "device")
        assert m["moves"] == ("query_p95_ms" if probe else "query_p50_ms")
    assert "probe_share_pct.c1" not in listed


# ------------------------------------------------- a hand-made capture

def _tagged(k, at_ms, capture, program=K9, top=False):
    """The spans test's request with its launch site naming a program;
    with ``top`` the three kernel spans carry the per-fragment scan's
    names."""
    r = _request(k, at_ms, 0.2, 0, capture)
    for s in r["profile"]["spans"]:
        if top and s["name"].startswith("kernel."):
            s["name"] = {"kernel.dispatch": "top.kernel",
                         "kernel.wait": "top.wait",
                         "kernel.fetch": "top.fetch"}.get(s["name"],
                                                          s["name"])
        if s["name"] in ("kernel.dispatch", "top.kernel"):
            s["tags"] = {"program": program}
    return r


def _with_span(r, name, parent, a_ms, b_ms, tags):
    """One more span in a request, times from its root's start."""
    spans_ = r["profile"]["spans"]
    k = spans_[0]["spanId"].split(":")[0]
    root_ns = spans_[0]["startNs"]
    spans_.append({"name": name, "spanId": f"{k}:{name}:{a_ms}",
                   "parentId": f"{k}:{parent}",
                   "durationMs": round(b_ms - a_ms, 3), "tags": tags,
                   "startNs": root_ns + int(a_ms * 1e6)})
    return r


# The device's clock is 1.4 ms early. A request's root starts at its
# ``at`` + 2 ms of the trace's clock, its launch site 5.6 ms in, its
# wait ends 8.0 ms in, its fetch 8.5. (program, true start, duration)
# on the host's clock, in ms:
EARLY = 1.4
LAUNCHES = [
    # a: another program's launch sits between the dispatch (17.6) and
    # the request's own, nearer to the dispatch.
    ("jit_bitmap_or(7)", 17.65, 0.1),
    (f"jit_{K9}(1)", 17.9, 0.5),
    # b: the fastest launch of the capture.
    (f"jit_{K9}(2)", 37.6, 0.5),
    # c: a probe: three per-slice launches, then its batched serve.
    ("jit_bitmap_and(3)", 55.0, 0.05), ("jit_bitmap_and(3)", 55.6, 0.05),
    ("jit_bitmap_and(3)", 56.2, 0.05), (f"jit_{K9}(1)", 57.9, 0.5),
    # d: a per-fragment TopN scan.
    (f"jit_{TOPN}(4)", 77.7, 0.4),
    # e: two launch sites in one request (a TopN's two phases).
    (f"jit_{K9}(1)", 97.7, 0.5), (f"jit_{K9}(1)", 99.0, 0.5),
    # f: the capture ended before its launch: no pair.
    # k1 is no prefix-match of k16p.
    ("jit_pilosa_count_batched_k16p(5)", 117.8, 0.5),
]


@pytest.fixture
def traced(tmp_path):
    capture = {"dir": str(tmp_path), "id": 7}
    a = _tagged("a", 10.0, capture)
    b = _tagged("b", 30.0, capture)
    c = _with_span(_tagged("c", 50.0, capture), "path.probe", "call:Count",
                   1.5, 5.0, {"path": "serial", "outcome": "aborted",
                              "deadline_ms": 3.0, "slices": 3})
    d = _tagged("d", 70.0, capture, program=TOPN, top=True)
    e = _with_span(_tagged("e", 90.0, capture), "kernel.dispatch",
                   "call:Count", 9.0, 9.2, {"program": K9})
    f = _tagged("f", 110.0, capture, program="pilosa_count_batched_k1")
    log = [a, b, c, d, e, f]
    host = _plane("/host:CPU", [
        ("python", 0, [(f"pilosa:anchor:7:{ANCHOR_NS}", ANCHOR_PS, 1000)])])
    device = _plane("/device:TPU:0", [
        ("XLA Modules", 0, [(name, int(round((t - EARLY) * MS)),
                             int(round(d_ * MS)))
                            for name, t, d_ in LAUNCHES]),
        ("XLA Ops", 0, [("%fusion", int(round((t - EARLY) * MS)),
                         int(round(d_ * MS))) for _, t, d_ in LAUNCHES])])
    _write_trace(str(tmp_path), host, device)
    trace = xplane.reduce_device(xplane.read_planes(
        xplane.find_xplane(str(tmp_path))))
    model = {"Count(X)/2^7slices": {"queries": 90, "batchedMs": 1.0,
                                    "serialMs": 60.0, "probes": 10,
                                    "probeAborts": 0, "probeMs": 100.0}}
    after = {"Count(X)/2^7slices": {"queries": 300, "batchedMs": 1.0,
                                    "serialMs": 60.0, "probes": 12,
                                    "probeAborts": 2, "probeMs": 230.5},
             "Sum(Y)/2^7slices": {"queries": 64, "batchedMs": 2.0,
                                  "serialMs": 50.0, "probes": 1,
                                  "probeAborts": 1, "probeMs": 20.0}}
    return run.Context(log=log, trace=trace, trace_t0=100.0, seconds=10.0,
                       before={"pathModel": model},
                       after={"pathModel": after})


def _ms(x):
    return int(round(x * MS))


def test_a_request_is_paired_with_its_own_program(traced, capsys):
    """Request a's nearest launch is ``jit_bitmap_or``'s: the pairing by
    the nearest launch takes it, the pairing by name does not."""
    near = spans.aligned(traced)[0]
    a_near = next(c for c in near if c[0] == _ms(17.6))
    assert a_near[2] - a_near[1] == _ms(0.1)          # bitmap_or's 0.1 ms
    got = chains.by_name(traced)
    assert got["chains"] == [
        (_ms(17.6), _ms(17.9), _ms(18.4), _ms(20.0), _ms(20.5)),
        (_ms(37.6), _ms(37.6), _ms(38.1), _ms(40.0), _ms(40.5)),
        (_ms(77.6), _ms(77.7), _ms(78.1), _ms(80.0), _ms(80.5))]
    # The smallest shift that puts no paired launch before its site is
    # b's; a's wait ends 3.0 ms after its launch does unshifted.
    assert got["shift_ps"] == _ms(EARLY) and got["slack_ps"] == _ms(1.6)
    assert (got["paired"], got["unpaired"], got["probes_left_out"],
            got["multi_launch_left_out"]) == (3, 1, 1, 1)
    notes = [json.loads(line) for line in
             capsys.readouterr().err.strip().splitlines()]
    (note,) = [n for n in notes if n["phase"] == "chains_by_name"]
    assert note["paired"] == 3 and note["unpaired"] == 1
    assert note["probes_left_out"] == 1
    assert note["multi_launch_left_out"] == 1
    assert note["device_clock_shift_ms"] == pytest.approx(1.4)
    assert note["device_clock_slack_ms"] == pytest.approx(1.6)
    assert note["site_to_wait_end_ms"] == pytest.approx(2.4)
    # Launch delay + scan + completion is the site plus the wait.
    for d0, s, e, w1, _ in got["chains"]:
        assert (s - d0) + (e - s) + (w1 - e) == _ms(2.4)
    # Said once a run, however many readers ask.
    chains.by_name(traced)
    assert "chains_by_name" not in capsys.readouterr().err


def _idle_outside_by_brute_force():
    """Every 10 us of the traced interval (the device's events, shifted):
    idle where no launch runs; covered where a leaf span of a request
    lies. All times above are multiples of 50 us."""
    step, idle, outside = 0.01, 0, 0
    first = min(t for _, t, _ in LAUNCHES)
    last = max(t + d for _, t, d in LAUNCHES)
    leaves = []
    for at, top in ((10, 0), (30, 0), (50, 0), (70, 1), (90, 0), (110, 0)):
        root = at + 2.0
        for a, b in ((0.0, 0.5), (0.5, 1.0), (1.0, 1.2), (1.2, 1.5),
                     (1.5, 1.6), (1.6, 1.8), (1.8, 5.5), (5.5, 5.6),
                     (5.6, 6.0), (6.0, 8.0), (8.0, 8.5), (9.0, 9.2),
                     (9.5, 10.0)):
            leaves.append((root + a, root + b))
    # The probe (c) covers 1.5-5.0 of call:Count as a leaf of its own,
    # which plan_and_stage's leaves cover already; e's second dispatch
    # is 9.0-9.2, where ``reduce`` lies.
    n = int(round((last - first) / step))
    for i in range(n):
        t = first + (i + 0.5) * step
        if any(s <= t < s + d for _, s, d in LAUNCHES):
            continue
        idle += 1
        if not any(a <= t < b for a, b in leaves):
            outside += 1
    return 100.0 * outside / idle


BY_HAND = {
    "launch_delay_ms": 0.1,          # 0.3, 0.0, 0.1
    "completion_ms": 1.9,            # 20.0 - 18.4, 40.0 - 38.1, 80.0 - 78.1
    "readback_ms": 2.4,              # the same with the fetch's 0.5
    # 130.5 ms of one shape and 20 of a shape first seen in the window,
    # of 10,000.
    "probe_share_pct": 1.505,
}


@pytest.mark.parametrize("name", SIXTEEN)
def test_reader_by_hand(traced, name, capsys):
    family = name.rsplit(".", 1)[0]
    got = run.load_metric(name).read(traced)
    if family == "idle_outside_spans_pct":
        assert got == pytest.approx(_idle_outside_by_brute_force(), abs=0.05)
        note = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
        assert note["phase"] == "idle_by_span"
        assert note["device_clock_shift_ms"] == pytest.approx(1.4)
        assert note["device_clock_slack_ms"] == pytest.approx(1.6)
        assert note["by_innermost_span_s"]["path.probe"] > 0
        # The run itself keeps the older pairing's answer apart.
        assert not hasattr(traced, "_aligned")
    else:
        assert got == pytest.approx(BY_HAND[family])
    if family == "probe_share_pct":
        note = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
        assert note == {"phase": "path_probes", "probes": 3,
                        "probeAborts": 3, "probeMs": pytest.approx(150.5),
                        "probe_spans": 1,
                        "probe_span_ms": pytest.approx(3.5)}


def test_no_probe_in_the_window_reads_zero_and_not_none(traced):
    traced.after = traced.before
    assert chains.probe_share_pct(traced) == 0.0


@pytest.mark.parametrize("name", SIXTEEN)
def test_reader_finds_nothing_in_an_older_programs_run(tmp_path, name):
    """The parent's run under this benchmark: launch-site spans without
    ``program``, a path model without ``probeMs``; and a run with no
    trace, or no request at all."""
    capture = {"dir": str(tmp_path), "id": 7}
    old = [_request("a", 10.0, 0.2, 0, capture),
           _request("b", 30.0, 0.2, 0, capture)]
    _write_trace(str(tmp_path), _plane("/host:CPU", [
        ("python", 0, [(f"pilosa:anchor:7:{ANCHOR_NS}", ANCHOR_PS, 1000)])]),
        _plane("/device:TPU:0", [
            (line, 0, [(f"jit_{K9}(1)", _ms(16.5), _ms(0.5)),
                       (f"jit_{K9}(1)", _ms(36.2), _ms(0.5))])
            for line in ("XLA Modules", "XLA Ops")]))
    trace = xplane.reduce_device(xplane.read_planes(
        xplane.find_xplane(str(tmp_path))))
    model = {"pathModel": {"Count(X)/2^7slices": {
        "queries": 9, "batchedMs": 1.0, "serialMs": None}}}
    ctx = run.Context(log=old, trace=trace, trace_t0=100.0, seconds=10.0,
                      before=model, after=model)
    assert spans.aligned(ctx) is not None      # the older pairing reads it
    assert run.load_metric(name).read(ctx) is None
    assert run.load_metric(name).read(run.Context(
        log=[], trace=None, trace_t0=None, seconds=10.0,
        before={"pathModel": {}}, after={"pathModel": {}})) is None
    assert run.load_metric(name).read(run.Context(
        log=[], trace=None, trace_t0=None, seconds=10.0,
        before={}, after={})) is None


@pytest.mark.parametrize("name", SIXTEEN)
def test_device_sourced_readers_need_the_trace(traced, name):
    source = next(m["source"] for m in BENCH["per_layer"]
                  if m["name"] == name)
    no_trace = run.Context(log=traced.log, trace=None, trace_t0=None,
                           seconds=10.0, before=traced.before,
                           after=traced.after)
    got = run.load_metric(name).read(no_trace)
    assert (got is None) == (source == "device_trace")


def test_a_program_name_is_matched_whole(traced):
    """``jit_pilosa_count_batched_k1`` starts the name of the k16p
    launch that lies in request f's window; it is not f's program."""
    launches = chains.named_launches(traced)
    assert sorted(launches) == sorted({
        "jit_bitmap_or", "jit_bitmap_and", f"jit_{K9}", f"jit_{TOPN}",
        "jit_pilosa_count_batched_k16p"})
    assert len(launches[f"jit_{K9}"]) == 5
    wins, probes, multi = chains.windows(spans.placed(traced))
    assert [w[4] for w in wins] == [
        f"jit_{K9}", f"jit_{K9}", f"jit_{TOPN}",
        "jit_pilosa_count_batched_k1"]
    assert (probes, multi) == (1, 1)


# ------------------------------------- a recorded slice of a chip run

RECORDED = os.path.join(HERE, os.pardir, os.pardir, "perfbench", "recorded",
                        "window_ev_chains_tpu_v5e.xplane.pb")


@pytest.fixture
def recorded(tmp_path):
    exp = json.load(open(RECORDED + ".expected.json"))
    d = tmp_path / "plugins" / "profile" / "rec"
    d.mkdir(parents=True)
    (d / "rec.xplane.pb").write_bytes(open(RECORDED, "rb").read())
    log = []
    for r in exp["requests"]:
        prof = dict(r["profile"], capture={"dir": str(tmp_path), "id": 1})
        log.append({"ok": True, "t0": 100.0 + r["at_s"], "pql": r["pql"],
                    "t1": 100.0 + r["at_s"] + r["ms"] / 1000.0,
                    "profile": prof})
    trace = xplane.reduce_device(xplane.read_planes(RECORDED))
    ctx = run.Context(log=log, trace=trace, trace_t0=100.0,
                      seconds=exp["window_s"], before=exp["before"],
                      after=exp["after"])
    return ctx, exp


def test_the_chains_on_a_recorded_chip_trace(recorded, capsys):
    """Consecutive requests of a traced run of the windowed-Count cell
    on a v5e, one of them the path model's aborted serial probe: the
    anchor, their ``pilosa:*`` annotations and every launch of the
    device around them, beside their own profile blocks."""
    ctx, exp = recorded
    probes = [r for r in exp["requests"]
              if any(s["name"] == "path.probe"
                     for s in r["profile"]["spans"])]
    assert len(exp["requests"]) >= 12 and len(probes) == 1
    (probe,) = [s for s in probes[0]["profile"]["spans"]
                if s["name"] == "path.probe"]
    assert probe["tags"]["path"] == "serial"
    assert probe["tags"]["outcome"] == "aborted"
    assert 0 < probe["tags"]["slices"] < 64
    assert probe["durationMs"] >= probe["tags"]["deadline_ms"] >= 50.0
    # One span a probe: no ``slice`` under it, whatever its slices ran.
    assert not [s for s in probes[0]["profile"]["spans"]
                if s["name"] == "slice"]
    assert probes[0]["profile"]["resources"]["pathProbes"] == 1
    assert probes[0]["profile"]["resources"]["pathProbeAborts"] == 1
    # The probe's per-slice programs are in the trace, by other names.
    launches = chains.named_launches(ctx)
    assert sum(len(v) for v in launches.values()) == exp["launches"]
    own = {"jit_" + s["tags"]["program"]
           for r in exp["requests"] for s in r["profile"]["spans"]
           if s["name"] == "kernel.dispatch"}
    assert own <= set(launches) and set(launches) - own

    got = chains.by_name(ctx)
    assert got["probes_left_out"] == 1
    assert got["multi_launch_left_out"] == 0 and got["unpaired"] == 0
    assert got["paired"] == len(exp["requests"]) - 1
    assert got["shift_ps"] == exp["device_clock_shift_ps"]
    assert got["slack_ps"] == exp["device_clock_slack_ps"]
    assert [list(c) for c in got["chains"]] == exp["chains"]
    for d0, start, end, w1, f1 in got["chains"]:
        assert d0 <= start < end < w1 < f1
        assert 0.05e9 < end - start < 0.8e9      # a cover's scan
        assert start - d0 < 1e9 and w1 - end < 2e9
    # The older pairing, by the nearest launch of any program, also
    # takes the probe request, by the batched serve that followed its
    # abort, from among the probe's hundred-odd per-slice launches.
    assert len(spans.aligned(ctx)[0]) == got["paired"] + 1
    for name in SIXTEEN:
        if not name.endswith(".ev"):
            continue
        assert run.load_metric(name).read(ctx) \
            == pytest.approx(exp["metrics"][name], rel=1e-9), name
    m = exp["metrics"]
    assert m["readback_ms.ev"] > m["completion_ms.ev"] > 0
    assert m["probe_share_pct.ev"] > 0
