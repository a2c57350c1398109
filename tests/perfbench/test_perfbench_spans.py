"""The nine span-level readers of PR 24 on a hand-made run: each value by
hand, None where its source is absent (no spans, no trace, no anchor);
the anchor arithmetic on a small synthetic ``.xplane.pb``; and the join
of spans and program launches on a recorded chip trace."""
import json
import os

import pytest

from perfbench import run
from perfbench.lib import spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(HERE, os.pardir, os.pardir,
                                    "BENCHMARK.json")))
NINE = ["http_parse_ms.c1", "route_ms.c1", "dispatch_ms.c1",
        "device_wait_ms.c1", "stack_builds.c1", "count_scan_ms.c1",
        "launch_delay_ms.c1", "readback_ms.c1", "idle_outside_spans_pct.c1"]
MS = 10 ** 9                              # picoseconds


# -------------------------------------------- a small .xplane.pb writer

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, payload):
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines):
    """``lines``: [(line name, line start ns, [(event name, offset ps,
    duration ps)])]; event names become the plane's metadata."""
    ids = {}
    for _, _, events in lines:
        for text, _, _ in events:
            ids.setdefault(text, len(ids) + 1)
    body = _field(2, name.encode())
    for text, mid in ids.items():
        meta = _field(1, mid) + _field(2, text.encode())
        body += _field(4, _field(1, mid) + _field(2, meta))
    for lname, t0_ns, events in lines:
        ln = _field(2, lname.encode()) + _field(3, t0_ns)
        for text, off_ps, dur_ps in events:
            ln += _field(4, _field(1, ids[text]) + _field(2, off_ps)
                         + _field(3, dur_ps))
        body += _field(3, ln)
    return _field(1, body)


def _write_trace(trace_dir, *planes):
    d = os.path.join(trace_dir, "plugins", "profile", "2026_01_01")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "vm.xplane.pb"), "wb") as f:
        f.write(b"".join(planes))


# ------------------------------------------------- a hand-made request

ANCHOR_NS = 5_000_000_000        # the server's clock as the capture began
ANCHOR_PS = 2 * MS               # where the trace's clock puts that moment

# (name, parent, start ms, end ms) from the root's start. Leaves add up
# to 9.2 ms of the root's 10; call:Count keeps 0.3 ms to itself and
# kernel:count_batched 0.5.
SHAPE = [
    ("query", None, 0.0, 10.0),
    ("parse", "query", 0.0, 0.5),
    ("call:Count", "query", 0.5, 9.5),
    ("count.plan", "call:Count", 0.5, 1.0),
    ("result.memo", "call:Count", 1.0, 1.2),
    ("exec.route", "call:Count", 1.2, 1.5),
    ("plan_and_stage", "call:Count", 1.5, 5.5),
    ("plan.tree", "plan_and_stage", 1.5, 1.6),
    ("stacks.memo", "plan_and_stage", 1.6, 1.8),
    ("stacks.build", "plan_and_stage", 1.8, 5.5),
    ("kernel:count_batched", "call:Count", 5.5, 9.0),
    ("kernel.fn", "kernel:count_batched", 5.5, 5.6),
    ("kernel.dispatch", "kernel:count_batched", 5.6, 6.0),
    ("kernel.wait", "kernel:count_batched", 6.0, 8.0),
    ("kernel.fetch", "kernel:count_batched", 8.0, 8.5),
    ("reduce", "call:Count", 9.0, 9.2),
    ("encode", "query", 9.5, 10.0),
]


def _request(k, at_ms, parse_ms, builds, capture):
    """One profiled request whose root starts ``at_ms`` after the
    anchor on the server's clock."""
    t0_ns = ANCHOR_NS + int(at_ms * 1e6)
    out = []
    for name, parent, a, b in SHAPE:
        sp = {"name": name, "spanId": f"{k}:{name}",
              "parentId": parent and f"{k}:{parent}",
              "durationMs": round(b - a, 3), "tags": {}}
        if capture:
            sp["startNs"] = t0_ns + int(a * 1e6)
        out.append(sp)
    out[0]["tags"] = {"httpParseMs": parse_ms}
    prof = {"spans": out, "resources": {"stackBuilds": builds,
                                        "planMs": 4.0, "fallbackChain": []}}
    if capture:
        prof["capture"] = capture
    t0 = 100.0 + at_ms / 1000.0
    return {"t0": t0, "t1": t0 + 0.011, "ok": True, "pql": f"q{k}",
            "profile": prof}


@pytest.fixture
def traced(tmp_path):
    """Two requests, 10 and 30 ms after the anchor, so at 12 and 32 ms
    of the trace's host clock; each causes one launch of 0.5 ms. The
    device's clock is early: it puts the launches 1.4 and 1.2 ms before
    the ``kernel.dispatch`` spans that caused them."""
    capture = {"dir": str(tmp_path), "id": 7}
    log = [_request("a", 10.0, 0.2, 2, capture),
           _request("b", 30.0, 0.4, 0, capture)]
    host = _plane("/host:CPU", [
        ("python", 0, [(f"pilosa:anchor:7:{ANCHOR_NS}", ANCHOR_PS, 1000),
                       ("pilosa:anchor:6:1", 0, 1000)]),
        ("python", 0, [("pilosa:query", 12 * MS + 2_000_000, 10 * MS),
                       ("pilosa:query", 32 * MS + 4_000_000, 10 * MS)]),
    ])
    _write_trace(str(tmp_path), host)
    launches = [(int(16.2 * MS), MS // 2), (int(36.4 * MS), MS // 2)]
    trace = {"span_ps": (10 * MS, 50 * MS), "busy_s": 0.001,
             "window_s": 0.04, "launches": launches,
             "modules": {"jit_pilosa_count_batched_k2(11)": [0.0005, 1],
                         "jit_pilosa_count_batched_k3(12)": [0.0005, 1],
                         "jit_pilosa_stack_scatter_k1(13)": [1.0, 1]}}
    return run.Context(log=log, trace=trace, trace_t0=100.0)


BY_HAND = {
    "http_parse_ms.c1": 0.3,                  # median of 0.2 and 0.4
    "route_ms.c1": 1.0,                       # 0.5 + 0.2 + 0.3
    "dispatch_ms.c1": 0.5,                    # 0.1 + 0.4
    "device_wait_ms.c1": 2.5,                 # 2.0 + 0.5
    "stack_builds.c1": 2,                     # 2 + 0
    "count_scan_ms.c1": 0.5,                  # 1 ms over 2 launches
    # The device's clock goes forward by 1.4 ms, the least that lets no
    # launch start before its dispatch: delays of 0 and 0.2 ms.
    "launch_delay_ms.c1": 0.1,
    "readback_ms.c1": 2.3,                    # 8.5 - 6.1 and 8.5 - 6.3
    # 40 ms less 1 ms busy are idle. Each request's leaves cover 9.2 ms
    # less its launch, which lies inside dispatch and wait: 17.4 of 39.
    "idle_outside_spans_pct.c1": 100.0 * (1.0 - 17.4 / 39.0),
}


@pytest.mark.parametrize("name", NINE)
def test_reader_by_hand(traced, name, capsys):
    assert run.load_metric(name).read(traced) == pytest.approx(BY_HAND[name])
    if name == "idle_outside_spans_pct.c1":
        note = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
        assert note["idle_s"] == pytest.approx(0.039)
        by = note["by_innermost_span_s"]
        assert by["(between requests)"] == pytest.approx(0.020)
        assert by["stacks.build"] == pytest.approx(2 * 0.0037)
        assert by["kernel.wait"] == pytest.approx(0.0019 + 0.0017)
        assert by["call:Count (self)"] == pytest.approx(2 * 0.0003)
        assert note["requests_placed"] == 2
        # Self time of spans with children: 0.8 of each root's 10 ms.
        assert note["parent_self_share_pct"] == pytest.approx(8.0)
        assert note["dispatch_to_fetch_ms"] == pytest.approx(2.9)
        assert note["device_clock_shift_ms"] == pytest.approx(1.4)
        # kernel.wait ends 3.3 and 3.1 ms after the launches end on the
        # device's clock: the shift could be up to 3.1, 1.7 more.
        assert note["device_clock_slack_ms"] == pytest.approx(1.7)
        # The annotations sit 2 and 4 us after the mapped span starts.
        assert note["anchor_skew_us"] == {"median": pytest.approx(3.0),
                                          "max": pytest.approx(4.0),
                                          "roots_without_annotation": 0}


@pytest.mark.parametrize("name", NINE)
def test_reader_finds_nothing_in_an_older_programs_run(name):
    """The parent's run under this benchmark: profiles without the new
    spans, tags, counters or capture block, programs named ``jit_fn``."""
    old = _request("a", 10.0, 0.2, 0, None)
    prof = old["profile"]
    prof["spans"] = [s for s in prof["spans"]
                     if s["name"] in ("query", "parse", "call:Count",
                                      "plan_and_stage",
                                      "kernel:count_batched")]
    prof["spans"][0]["tags"] = {"index": "users"}
    del prof["resources"]["stackBuilds"]
    trace = {"span_ps": (10 * MS, 50 * MS), "busy_s": 0.001,
             "window_s": 0.04, "launches": [(int(16.2 * MS), MS // 2)],
             "modules": {"jit_fn(654839580305270304)": [0.0005, 1]}}
    ctx = run.Context(log=[old], trace=trace, trace_t0=100.0)
    assert run.load_metric(name).read(ctx) is None
    assert run.load_metric(name).read(run.Context(
        log=[], trace=None, trace_t0=None)) is None


@pytest.mark.parametrize("name", NINE)
def test_device_sourced_readers_need_the_trace_and_the_anchor(
        traced, tmp_path, name):
    source = next(m["source"] for m in BENCH["per_layer"]
                  if m["name"] == name)
    no_trace = run.Context(log=traced.log, trace=None, trace_t0=None)
    got = run.load_metric(name).read(no_trace)
    assert (got is None) == (source == "device_trace")
    if name in ("launch_delay_ms.c1", "readback_ms.c1",
                "idle_outside_spans_pct.c1"):
        # The capture's file holds another capture's anchor only.
        _write_trace(str(tmp_path), _plane("/host:CPU", [
            ("python", 0, [("pilosa:anchor:6:1", 0, 1000)])]))
        blind = run.Context(log=traced.log, trace=traced.trace,
                            trace_t0=100.0)
        assert run.load_metric(name).read(blind) is None
        # And no file at all.
        for r in traced.log:
            r["profile"]["capture"]["dir"] = str(tmp_path / "nowhere")
        assert run.load_metric(name).read(run.Context(
            log=traced.log, trace=traced.trace, trace_t0=100.0)) is None


def test_anchor_arithmetic_on_a_synthetic_xplane(tmp_path):
    """The anchor's name holds the server's clock in nanoseconds, its
    event the trace's in picoseconds (line start in ns plus offset)."""
    host = _plane("/host:CPU", [
        ("python", 3, [("pilosa:parse", 10, 5)]),
        ("python", 1_000, [("pilosa:anchor:3:123456789", 2_500, 7)]),
    ])
    dev = _plane("/device:TPU:0", [("XLA Modules", 0, [("jit_x(1)", 0, 5)])])
    _write_trace(str(tmp_path), host, dev)
    planes = xplane.read_planes(xplane.find_xplane(str(tmp_path)),
                                prefix="/host:")
    assert [p["name"] for p in planes] == ["/host:CPU"]
    # 1,000 ns and 2,500 ps on the trace's clock; 123,456,789 ns on the
    # server's.
    assert spans.anchor_offset_ps(planes, 3) \
        == 1_000 * 1000 + 2_500 - 123_456_789 * 1000
    assert spans.anchor_offset_ps(planes, 4) is None
    assert spans.annotations(planes, "pilosa:parse") == [3 * 1000 + 10]


def test_spans_tile_a_request_and_idle_gaps_tile_the_interval(traced):
    segs = spans.innermost_segments(spans.placed(traced)[0])
    assert sum(b - a for _, a, b, _ in segs) == 10 * MS
    assert sorted((n, b - a) for n, a, b, leaf in segs if not leaf) == [
        ("call:Count", 3 * MS // 10), ("kernel:count_batched", MS // 2)]
    gaps = spans.idle_gaps([(5, 10), (12, 3), (30, 100)], 0, 40)
    assert gaps == [[0, 5], [15, 30]]
    assert spans.overlap_ps(gaps, spans.merged([(3, 8), (4, 20), (39, 50)])) \
        == 2 + 5
    chains, launches, slack, shift = spans.aligned(traced)
    assert chains == [
        (int(17.6 * MS), int(17.6 * MS), int(18.1 * MS), int(20.5 * MS)),
        (int(37.6 * MS), int(37.8 * MS), int(38.3 * MS), int(40.5 * MS))]
    assert launches == [(int(17.6 * MS), MS // 2), (int(37.8 * MS), MS // 2)]
    assert (slack, shift) == (int(1.7 * MS), int(1.4 * MS))


def test_alignment_finds_a_device_clock_that_is_far_off(tmp_path):
    """Five requests at uneven distances; the device's clock is 54 ms
    early (seen on the chip), further than the requests lie apart, and
    one launch belongs to no profiled request. Only one shift puts a
    launch behind every dispatch; the fastest launch then reads 0."""
    capture = {"dir": str(tmp_path), "id": 7}
    at = [60.0, 67.0, 80.0, 86.5, 98.0]
    delay = [0.3, 0.1, 0.2, 0.4, 0.25]
    log = [_request(str(k), t, 0.2, 0, capture) for k, t in enumerate(at)]
    _write_trace(str(tmp_path), _plane("/host:CPU", [
        ("python", 0, [(f"pilosa:anchor:7:{ANCHOR_NS}", ANCHOR_PS, 1000)])]))
    # Root at t + 2 ms of the trace, its dispatch 5.6 ms in.
    launches = sorted(
        [(int((t + 2 + 5.6 + d - 54) * MS), MS // 2)
         for t, d in zip(at, delay)] + [(int(20.0 * MS), MS // 2)])
    ctx = run.Context(log=log, trace_t0=100.0, trace={
        "span_ps": (0, 60 * MS), "launches": launches, "modules": {}})
    chains, moved, slack, shift = spans.aligned(ctx)
    assert shift == pytest.approx(53.9 * MS) and len(chains) == 5
    assert [round((s - d0) / MS, 3) for d0, s, _, _ in chains] \
        == [0.2, 0.0, 0.1, 0.3, 0.15]
    assert spans.launch_delay_ms(ctx) == pytest.approx(0.15)
    # kernel.wait ends 2.4 ms after its dispatch began; the slowest
    # launch would still end by then if it came 1.5 ms later than it
    # reads unshifted, which is 1.6 later than it reads here.
    assert slack == pytest.approx(1.6 * MS)
    assert moved[1] == (pytest.approx(73.9 * MS), MS // 2)   # the stray one
    assert len(moved) == 6


RECORDED = os.path.join(HERE, os.pardir, os.pardir, "perfbench", "recorded",
                        "count_c1_spans_tpu_v5e.xplane.pb")


def test_the_join_on_a_recorded_chip_trace(tmp_path, capsys):
    """Twelve consecutive requests of a traced run of the one-client
    Count cell on a v5e (PR 24, seed 2147484777, Python tracer off): the
    anchor, their ``pilosa:*`` annotations and the twelve launches they
    caused (all three-operand, two programs), beside their own profile
    blocks. The device's clock read 1.7 ms early in that capture; within
    these twelve the fastest launch puts it at 1.56."""
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
    assert sorted(trace["modules"]) == sorted(exp["modules"])
    assert all(k.startswith("jit_pilosa_count_batched_k3(")
               for k in trace["modules"])
    ctx = run.Context(log=log, trace=trace, trace_t0=100.0)

    planes = xplane.read_planes(RECORDED, prefix="/host:")
    offset = spans.anchor_offset_ps(planes, 1)
    roots = [next(s for s in r["profile"]["spans"] if s["parentId"] is None)
             for r in exp["requests"]]
    marks = spans.annotations(planes, "pilosa:query")
    assert len(marks) == len(roots) == 12
    for root, mark in zip(roots, marks):
        # Both name one moment; the anchor and the annotation each open
        # a microsecond or two after the clock reading they stand for.
        assert abs(mark - (root["startNs"] * 1000 + offset)) < 5e6

    chains, launches, slack, shift = spans.aligned(ctx)
    assert len(chains) == len(launches) == 12
    assert shift == exp["device_clock_shift_ps"] == 1_563_777_250
    assert slack == exp["device_clock_slack_ps"]
    assert [list(c) for c in chains] == exp["chains"]
    for d0, start, end, f1 in chains:
        assert d0 <= start < end < f1
        assert 0.33e9 < end - start < 0.51e9         # a scan of 3 rows
        assert start - d0 < 1e9 and 0.7e9 < f1 - end < 2e9
    for name in NINE:
        assert run.load_metric(name).read(ctx) \
            == pytest.approx(exp["metrics"][name], rel=1e-9), name
    note = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert note["anchor_skew_us"]["max"] < 50
    assert note["parent_self_share_pct"] < 15
    m = exp["metrics"]
    parts = (m["launch_delay_ms.c1"] + m["count_scan_ms.c1"]
             + m["readback_ms.c1"])
    assert parts == pytest.approx(note["dispatch_to_fetch_ms"], rel=0.10)
