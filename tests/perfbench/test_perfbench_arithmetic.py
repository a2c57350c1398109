"""The benchmark's own arithmetic: percentiles from a request log with
failed requests, the byte counts against hand-worked shapes,
the PQL reader, the peaks table, and the trace reduction on a synthetic
and on a recorded ``.xplane.pb``."""
import json
import math
import os

import numpy as np
import pytest

from perfbench import run
from perfbench.lib import bytes_model, layer, peaks, pql, stats, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def _log(ms, wrong=()):
    t, out = 100.0, []
    for i, m in enumerate(ms):
        out.append({"t0": t, "t1": t + m / 1000.0, "ok": True,
                    "correct": i not in wrong})
        t += m / 1000.0
    return out


@pytest.mark.parametrize("ms, q, want", [
    ([10.0], 50, 10.0),
    ([10.0, 20.0], 50, 15.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    (list(range(1, 101)), 95, 95.05),
    (list(range(1, 101)), 50, 50.5),
])
def test_percentile_matches_numpy(ms, q, want):
    got = stats.percentile([float(x) for x in ms], q)
    assert got == pytest.approx(want)
    assert got == pytest.approx(float(np.percentile(ms, q)))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_failed_requests_count_as_missing_every_limit():
    log = _log([10.0] * 20, wrong={3, 7})
    lat = stats.latencies_ms(log)
    assert sorted(lat)[-2:] == [math.inf, math.inf]
    # 2 of 20 are missing: the median stands, the 95th percentile is lost.
    assert stats.percentile(lat, 50) == pytest.approx(10.0)
    assert stats.percentile(lat, 95) == math.inf
    assert stats.percentile(lat, 85) == pytest.approx(10.0)


@pytest.mark.parametrize("query, slices, want", [
    ('Count(Bitmap(frame="geo", rowID=1))', 954, 954 * 131072),
    ('Count(Intersect(Bitmap(frame="a", rowID=1), Bitmap(frame="b", rowID=2)))',
     954, 2 * 954 * 131072),
    ('Count(Intersect(Bitmap(frame="a", rowID=1), Difference('
     'Bitmap(frame="b", rowID=2), Bitmap(frame="c", rowID=3))))',
     954, 3 * 954 * 131072),
    ('Count(Xor(Bitmap(frame="a", rowID=1), Bitmap(frame="b", rowID=2)))',
     1, 262144),
])
def test_count_bytes_by_hand(query, slices, want):
    tree = pql.parse(query).children[0]
    assert bytes_model.count_bytes(tree, slices) == want


def test_count_bytes_of_the_documented_query_at_1b_columns():
    # 3 operands x 954 slices x 128 KiB = 375 MB: 0.458 ms at 819 GB/s.
    tree = pql.parse('Count(Intersect(Bitmap(frame="behavior", rowID=11), '
                     'Difference(Bitmap(frame="device", rowID=2), '
                     'Bitmap(frame="geo", rowID=840))))').children[0]
    need = bytes_model.count_bytes(tree, 954)
    assert need == 375_128_064
    bw = peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert need / bw == pytest.approx(0.458e-3, rel=1e-2)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes"] == 16e9


def test_pql_reader():
    call = pql.parse('TopN(Bitmap(frame="fingerprint", rowID=42), '
                     'frame="fingerprint", n=50, tanimotoThreshold=70)')
    assert call.name == "TopN"
    assert call.args == {"frame": "fingerprint", "n": 50,
                         "tanimotoThreshold": 70}
    assert call.children[0].args == {"frame": "fingerprint", "rowID": 42}
    assert len(pql.leaves(pql.parse(
        'Count(Union(Bitmap(frame="a", rowID=1), Intersect('
        'Bitmap(frame="b", rowID=2), Bitmap(frame="c", rowID=3))))'))) == 3
    with pytest.raises(ValueError):
        pql.parse('Count(Bitmap(frame="a", rowID=1)) trailing')


# ---------------------------------------------------------------- xplane

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


def _plane(name, lines, names):
    body = _field(2, name.encode())
    for mid, text in names.items():
        meta = _field(1, mid) + _field(2, text.encode())
        body += _field(4, _field(1, mid) + _field(2, meta))
    for lname, t0_ns, events in lines:
        ln = _field(2, lname.encode()) + _field(3, t0_ns)
        for mid, off_ps, dur_ps in events:
            ln += _field(4, _field(1, mid) + _field(2, off_ps)
                         + _field(3, dur_ps))
        body += _field(3, ln)
    return _field(1, body)


@pytest.fixture
def synthetic_trace(tmp_path):
    names = {1: "jit_count(1)", 2: "fusion.1", 3: "copy.2"}
    ms = 10 ** 9                                   # picoseconds
    dev0 = _plane("/device:TPU:0", [
        ("XLA Modules", 1000, [(1, 0, 3 * ms), (1, 10 * ms, 3 * ms)]),
        # Two overlapping operations, then one alone: union 3 + 2 ms.
        ("XLA Ops", 1000, [(2, 0, 2 * ms), (3, 1 * ms, 2 * ms),
                           (2, 10 * ms, 2 * ms)]),
    ], names)
    dev1 = _plane("/device:TPU:1", [
        ("XLA Ops", 1000, [(2, 0, 1 * ms)]),
    ], names)
    host = _plane("/host:CPU", [("python", 1000, [(2, 0, 50 * ms)])], names)
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(host + dev0 + dev1)
    return str(tmp_path)


def test_trace_reduction_on_a_synthetic_trace(synthetic_trace):
    path = xplane.find_xplane(synthetic_trace)
    planes = xplane.read_planes(path)
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/device:TPU:1"]
    out = xplane.reduce_device(planes)
    # Chip 0 is busy 5 ms, chip 1 for 1 ms: 3 ms on average.
    assert out["busy_s"] == pytest.approx(3e-3)
    assert out["chips"] == 2
    assert out["window_s"] == pytest.approx(13e-3)
    assert out["modules"] == {"jit_count(1)": [pytest.approx(6e-3), 2]}
    assert out["ops"]["fusion.1"] == [pytest.approx(5e-3), 3]
    assert out["ops"]["copy.2"] == [pytest.approx(2e-3), 1]
    # The device waited 7 ms for the second launch of the program.
    assert out["gaps_by_next"] == [("jit_count(1)", pytest.approx(7e-3))]
    # Starts are on the epoch clock: line start (ns) plus offset (ps).
    assert out["span_ps"][0] == 1000 * 1000


def test_trace_without_device_operations_reduces_to_none(tmp_path):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(
        _plane("/host:CPU", [("python", 0, [(1, 0, 5)])], {1: "f"}))
    planes = xplane.read_planes(xplane.find_xplane(str(tmp_path)))
    assert planes == [] and xplane.reduce_device(planes) is None


@pytest.mark.parametrize("intervals, want", [
    ([], 0), ([(0, 5)], 5), ([(0, 5), (5, 5)], 10), ([(0, 5), (2, 1)], 5),
    ([(10, 5), (0, 5), (3, 4)], 12),
])
def test_union_of_intervals(intervals, want):
    assert xplane.union_ps(intervals) == want


RECORDED = os.path.join(os.path.dirname(os.path.dirname(HERE)), "perfbench",
                        "recorded", "count_c1_tpu_v5e.xplane.pb")


def test_trace_reduction_on_the_recorded_chip_trace():
    """The device planes of a traced run of the one-client Count cell on
    a v5e (PR 23, seed 2147480002). Read by hand in the profiler's own
    summary: 92 program launches of six programs, one operation each,
    45 over two operands and 47 over three; 38.5 ms busy in 4.93 s."""
    planes = xplane.read_planes(RECORDED)
    assert [p["name"] for p in planes] == [
        "/device:TPU:0", "/device:CUSTOM:Megascale Trace"]
    out = xplane.reduce_device(planes)
    expected = json.load(open(RECORDED + ".expected.json"))
    assert out["chips"] == expected["chips"] == 1
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(0.0385, abs=5e-5)
    assert out["window_s"] == pytest.approx(4.9312, abs=1e-4)
    assert len(out["modules"]) == 6
    assert sum(v[1] for v in out["modules"].values()) == 92
    by_operands = {}
    for name, (seconds, launches) in out["ops"].items():
        k = name.count("u32[954,32768]")
        by_operands[k] = by_operands.get(k, 0) + launches
        # 125 MB an operand: no launch beats the 819 GB/s of the chip.
        assert seconds / launches > k * 954 * 131072 / 819e9
    assert by_operands == {2: 45, 3: 47}
    assert sum(s for _, s in out["gaps_by_next"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)


# ----------------------------------------------------------------- layer

def _span(name, sid, parent, ms):
    return {"name": name, "spanId": sid, "parentId": parent,
            "durationMs": ms}


def _profiled(t0, ms, pql, root_ms, plan_ms):
    spans = [_span("parse", "p", "q", 1.0), _span("slice", "s", "c", 2.0),
             _span("call:Count", "c", "q", root_ms - 2.0),
             _span("query", "q", None, root_ms)]
    return {"t0": t0, "t1": t0 + ms / 1000.0, "ok": True, "pql": pql,
            "profile": {"spans": spans, "resources": {
                "planMs": plan_ms, "fallbackChain": []}}}


def test_root_span_is_the_one_without_a_parent_and_self_time_adds_up():
    r = _profiled(0.0, 12.0, "q", 10.0, 0.5)
    assert layer.root_span(r["profile"])["name"] == "query"
    own = layer.self_seconds([r, {"profile": None}])
    # query 10 = parse 1 + call 8 + 1 own; call 8 = slice 2 + 6 own.
    assert own == {"parse": pytest.approx(1e-3), "slice": pytest.approx(2e-3),
                   "call:Count": pytest.approx(6e-3),
                   "query": pytest.approx(1e-3)}
    ctx = run.Context(log=[r, _profiled(1.0, 20.0, "q", 11.0, 1.5)])
    assert layer.http_outside_ms(ctx) == pytest.approx((2.0 + 9.0) / 2)
    assert layer.plan_ms(ctx) == pytest.approx(1.0)
    assert layer.error_hops(ctx) == 0


def test_serial_share_counts_tier_notes():
    a = _profiled(0.0, 10.0, "q", 9.0, 0.0)
    a["profile"]["resources"]["servedBy"] = {"serial": 2}
    b = _profiled(1.0, 10.0, "q", 9.0, 0.0)
    b["profile"]["resources"]["servedBy"] = {"serial": 1, "batched": 1}
    c = _profiled(2.0, 10.0, "q", 9.0, 0.0)       # no tier note at all
    assert layer.serial_share_pct(run.Context(log=[a, b, c])) \
        == pytest.approx(75.0)
    assert layer.serial_share_pct(run.Context(log=[c])) is None


def test_roofline_takes_the_requests_and_launches_inside_the_interval():
    two = 'Count(Union(Bitmap(frame="a", rowID=1), Bitmap(frame="b", rowID=2)))'
    log = [_profiled(t, 100.0, two, 90.0, 0.0)
           for t in (9.0, 10.1, 10.7, 11.4, 12.6, 13.2, 20.0)]
    s = 10 ** 12
    # The capture was armed at 10.0 on the client's clock; the device's
    # events span 0.05-3.45 s of the trace, the interval 0.55-2.95 s.
    launches = [(int(0.05 * s), s // 1000), (int(0.8 * s), s // 1000),
                (int(1.5 * s), s // 1000), (int(2.9495 * s), s // 1000),
                (int(3.449 * s), s // 1000)]
    trace = {"span_ps": (int(0.05 * s), int(3.45 * s)), "busy_s": 0.005,
             "window_s": 3.4, "launches": launches}
    ctx = run.Context(log=log, trace=trace, trace_t0=10.0,
                      device={"deviceKind": "TPU v5 lite"},
                      config={"shape": {"slices": 954}})
    assert layer.traced_interval(ctx) == (int(0.55 * s), int(2.95 * s))
    assert [r["t0"] for r in layer.traced_requests(ctx)] == [10.7, 11.4, 12.6]
    need = 3 * 2 * 954 * 131072           # three requests, two operands each
    device_s = 0.001 + 0.001 + 0.0005     # the fourth launch is cut in half
    assert layer.roofline_pct(ctx, layer.count_bytes_of(ctx)) \
        == pytest.approx(100.0 * need / 819e9 / device_s)
    assert layer.device_idle_pct(ctx) == pytest.approx(100 * (1 - 0.005 / 3.4))
    ctx.trace = None                      # no trace: nothing to read
    assert layer.roofline_pct(ctx, layer.count_bytes_of(ctx)) is None
    assert layer.device_idle_pct(ctx) is None


class _Echo:
    """A reference that says 1 to every query."""

    def answers(self, pqls, control=False):
        return [1] * len(pqls)

    def explain(self, query, got, want):
        return {"query": query, "got": got, "want": want}


@pytest.mark.parametrize("sample, wrong_at, want_wrong", [
    (None, 7, 1),           # every answer compared: the wrong one is found
    (400, 7, 1),            # a sample no smaller than the window: the same
    (40, None, 0),          # a sample of sound answers
])
def test_compare_every_answer_or_a_seeded_sample(tmp_path, capsys, sample,
                                                 wrong_at, want_wrong):
    log = [{"ok": True, "pql": f"q{i}", "result": 1, "status": 200}
           for i in range(100)]
    log.append({"ok": False, "pql": "lost", "result": None, "status": 0,
                "body": b"timed out"})
    if wrong_at is not None:
        log[wrong_at]["result"] = 2
    picked, wrong, failed = run.compare(_Echo(), log, str(tmp_path), sample, 5)
    assert (len(picked), wrong, failed) == (min(sample or 100, 100),
                                            want_wrong, 1)
    assert [r["correct"] for r in log].count(False) == want_wrong + 1
    again, _, _ = run.compare(_Echo(), log, str(tmp_path), sample, 5)
    assert [r["pql"] for r in again] == [r["pql"] for r in picked]
    other, _, _ = run.compare(_Echo(), log, str(tmp_path), 40, 6)
    assert sample != 40 or [r["pql"] for r in other] \
        != [r["pql"] for r in picked]
    assert '"mismatch_report"' in capsys.readouterr().out


def test_the_control_goes_through_the_same_judgement(tmp_path):
    class Half(_Echo):
        def answers(self, pqls, control=False):
            return [2 if control and i % 2 else 1 for i in range(len(pqls))]

    log = [{"ok": True, "pql": f"q{i}", "result": 1, "status": 200}
           for i in range(10)]
    picked, wrong, failed, control = run.compare(
        Half(), log, str(tmp_path), None, 5, control=True)
    assert (len(picked), wrong, failed, control) == (10, 0, 0, 5)
    assert run.verdict(len(picked), wrong, failed) is True
    assert run.verdict(len(picked), control, failed) is False
    assert run.verdict(0, 0, 0) is False          # nothing compared
    assert all(r["correct"] for r in log)         # the run's own marks stay
