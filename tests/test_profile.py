"""Continuous profiler and analytic device cost attribution (PR 19):
trie bounds + two-generation decay under fake clocks, folded-format
golden, subsystem classification, the NOP single-attribute-read
contract through tracing._finish, and XLA cost_analysis capture/fold
on the CPU backend."""
import json
import os
import sys

import pytest

from pilosa_tpu import tracing
from pilosa_tpu.observe import devprof as devprof_mod
from pilosa_tpu.observe import kerneltime as kt
from pilosa_tpu.observe import profiler as profiler_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_tiers():
    """Process-global profiler tiers restored after every test (the
    test_observe discipline) — an enable here must not leak."""
    prev_prof, prev_dev = profiler_mod.ACTIVE, devprof_mod.ACTIVE
    yield
    if profiler_mod.ACTIVE is not prev_prof \
            and profiler_mod.ACTIVE.enabled:
        profiler_mod.ACTIVE.stop()
    profiler_mod.ACTIVE = prev_prof
    devprof_mod.ACTIVE = prev_dev


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ------------------------------------------------------ trie + decay


def test_trie_bounds_overflow_conserved():
    clk = FakeClock()
    p = profiler_mod.Profiler(sample_hz=0, _clock=clk, max_nodes=4)
    deep = tuple(f"m:f{i}" for i in range(6))
    p._ingest("serving", deep)
    # subsystem root + 3 frame nodes hit the cap; the tail frames are
    # attributed to the deepest existing prefix, counted as overflow.
    assert p._nodes == 4
    assert p.overflow == 1
    assert p.samples == 1
    p._ingest("serving", deep)
    assert p._nodes == 4
    assert p.overflow == 2
    assert p.samples == 2
    # The sample count is conserved at the truncated prefix.
    rows = p._walk()
    assert sum(c for _s, _p, c in rows) == 2
    (sub, path, count) = rows[0]
    assert sub == "serving" and count == 2
    assert path == deep[:3]


def test_two_generation_decay_and_prune():
    clk = FakeClock()
    p = profiler_mod.Profiler(sample_hz=0, _clock=clk, gen_seconds=10.0)
    p._ingest("serving", ("h:dispatch",))
    clk.t = 11.0
    p._ingest("serving", ("h:dispatch",))  # rotation #1, then count
    assert p.generations == 1
    # cur=1 (just ingested) + prev=1 (rotated) both visible.
    assert p._walk()[0][2] == 2
    clk.t = 22.0
    p._ingest("background", ("m:loop",))  # rotation #2: serving cur->prev
    clk.t = 33.0
    p._ingest("background", ("m:loop",))  # rotation #3: serving pruned
    assert p.generations == 3
    subs = {s for s, _p, _c in p._walk()}
    assert subs == {"background"}
    # Lifetime counters stay monotonic through pruning.
    assert p.samples == 4
    assert p._by_subsystem["serving"] == 2


def test_folded_golden():
    clk = FakeClock()
    p = profiler_mod.Profiler(sample_hz=0, _clock=clk)
    p._ingest("serving", ("handler:dispatch", "executor:execute"))
    p._ingest("serving", ("handler:dispatch", "executor:execute"))
    p._ingest("fan-out", ("fanpool:run",))
    assert p.folded() == (
        "serving;handler:dispatch;executor:execute 2\n"
        "fan-out;fanpool:run 1")
    assert p.folded(limit=1) == (
        "serving;handler:dispatch;executor:execute 2")


def test_snapshot_shares_and_metrics():
    clk = FakeClock()
    p = profiler_mod.Profiler(sample_hz=7.0, _clock=clk)
    for _ in range(3):
        p._ingest("serving", ("h:d",))
    p._ingest("background", ("m:l",))
    snap = p.snapshot()
    assert snap["enabled"] and snap["sampleHz"] == 7.0
    assert snap["windowSamples"] == 4
    assert snap["subsystems"]["serving"]["windowShare"] == 0.75
    assert snap["topStacks"][0]["stack"] == "serving;h:d"
    m = p.metrics()
    assert m["samples_total"] == 4
    assert m["samples_total;subsystem:serving"] == 3
    assert m["sample_hz"] == 7.0
    d = p.digest(k=1)
    assert d["subsystems"]["background"] == 0.25
    assert len(d["topStacks"]) == 1


def test_window_top_ring_bounds():
    clk = FakeClock()
    p = profiler_mod.Profiler(sample_hz=0, _clock=clk)
    for t, sub in ((1.0, "serving"), (2.0, "serving"),
                   (3.0, "background")):
        clk.t = t
        p._ingest(sub, ("a:b",))
    top = p.window_top(0.5, 2.5)
    assert top == [{"stack": "serving;a:b", "samples": 2}]
    assert p.window_top(10.0, 20.0) == []


# ------------------------------------------------------ classification


def test_classify_stack_seams_leaf_first():
    assert profiler_mod.classify(
        "x", [("/a/utils/fanpool.py", "run")]) == "fan-out"
    assert profiler_mod.classify(
        "x", [("/a/executor.py", "_co_flush")]) == "coalescer"
    assert profiler_mod.classify(
        "x", [("/env/jax/core.py", "bind")]) == "device-dispatch"
    assert profiler_mod.classify(
        "x", [("/a/server/handler.py", "dispatch")]) == "serving"
    assert profiler_mod.classify(
        "x", [("/a/ingest/loader.py", "feed")]) == "ingest"
    assert profiler_mod.classify(
        "x", [("/a/rebalancer.py", "step")]) == "rebalance"
    # Leaf-first: a serving thread deep inside a kernel dispatch is
    # device-dispatch time — the innermost activity claims the sample.
    frames = [("/a/server/handler.py", "dispatch"),
              ("/env/jax/core.py", "bind")]
    assert profiler_mod.classify("x", frames) == "device-dispatch"


def test_classify_name_seams_and_fallback():
    neutral = [("/somewhere/else.py", "work")]
    assert profiler_mod.classify(
        "Thread-3 (process_request_thread)", neutral) == "serving"
    assert profiler_mod.classify("fanpool-worker", neutral) == "fan-out"
    assert profiler_mod.classify("bg-heat", neutral) == "background"
    assert profiler_mod.classify("MainThread", neutral) == "background"
    assert profiler_mod.classify(None, neutral) == "background"


# ------------------------------------------------------- NOP contract


class _CountingNop:
    """Counts .enabled reads; ANY other surface touched is a failure
    — the disabled tier must cost one attribute read, nothing more."""

    def __init__(self):
        self.reads = 0

    @property
    def enabled(self):
        self.reads += 1
        return False

    def __getattr__(self, name):
        raise AssertionError(
            f"disabled profiler surface touched: {name}")


def test_nop_costs_one_attribute_read_on_slow_trace():
    probe = _CountingNop()
    profiler_mod.ACTIVE = probe
    tr = tracing.Tracer(ring_size=4, slow_threshold=0.0)
    with tr.start("q"):
        pass
    assert tr.ring_len(slow=True) == 1
    assert probe.reads == 1
    # No profile block lands on the slow trace when disabled.
    assert "profile" not in tr.recent(1)[0]


def test_nop_surfaces_answer():
    nop = profiler_mod.NOP
    assert not nop.enabled
    assert nop.folded() == ""
    assert nop.snapshot() == {"enabled": False}
    assert nop.window_top(0, 1) == []
    assert nop.collect(0.01) == {"enabled": False}
    assert nop.metrics() == {}
    dnop = devprof_mod.NOP
    assert not dnop.enabled
    assert dnop.analytic("x") is None
    assert dnop.summary() == {"enabled": False}
    with pytest.raises(devprof_mod.Unsupported):
        dnop.device_capture("/tmp/x", 1.0)


def test_slow_trace_carries_profile_window():
    p = profiler_mod.Profiler(sample_hz=0)  # real perf_counter clock
    profiler_mod.ACTIVE = p
    tr = tracing.Tracer(ring_size=4, slow_threshold=0.0)
    with tr.start("q"):
        # A sample lands inside [perf0, perf0+dur] — exactly what the
        # sampler thread would have recorded during the query.
        p._ingest("serving", ("handler:dispatch",))
    doc = tr.recent(1)[0]
    assert doc["profile"] == [
        {"stack": "serving;handler:dispatch", "samples": 1}]


# ---------------------------------------------- analytic cost capture


def test_cost_analysis_capture_and_fold_cpu():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    dp = devprof_mod.DevProfiler()
    fn = jax.jit(
        lambda a, b: jnp.sum(jax.lax.population_count(a & b)
                             .astype(jnp.int32)))
    args = (jnp.zeros(64, jnp.uint32), jnp.ones(64, jnp.uint32))
    dp.note_compile("count_and", "dense*dense", "<=1KB", fn, args)
    if dp.summary()["unsupported"]:
        pytest.skip("backend lacks cost_analysis")
    got = dp.lookup("count_and", "dense*dense", "<=1KB")
    assert got is not None and got["bytes"] > 0
    row = {"op": "count_and", "cell": "dense*dense", "bucket": "<=1KB"}
    dp.fold([row])
    assert row["analyticBytes"] == got["bytes"]
    assert row["analyticFlops"] == got["flops"]
    a = dp.analytic("count_and")
    assert a["flops"] == got["flops"]
    assert dp.summary()["captured"] == 1
    # Claimed GIL-atomically: a second note for the same cell is free.
    dp.note_compile("count_and", "dense*dense", "<=1KB", fn, args)
    assert dp.summary()["captured"] == 1


def test_kernel_snapshot_carries_analytic():
    dp = devprof_mod.enable()
    dp._cells[("count_and", "dense*dense", "<=1KB")] = {
        "flops": 10.0, "bytes": 5.0}
    obs = kt.KernelObservatory()
    obs.note("count_and", "dense*dense", "<=1KB", 0.001)
    snap = obs.snapshot()
    (row,) = snap["cells"]
    assert row["analyticFlops"] == 10.0
    assert row["analyticBytes"] == 5.0
    assert row["arithmeticIntensity"] == 2.0
    assert snap["analytic"]["captured"] == 1


# ------------------------------------------------- the device capture


def _http(method, url, body=None):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url, data=body, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _wait_state(url, want, seconds=60.0):
    import time

    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        _, st = _http("GET", url)
        if st["state"] == want:
            return st
        time.sleep(0.05)
    raise AssertionError(f"capture never reached {want!r}: {st}")


def test_device_capture_starts_the_trace_with_the_python_tracer_off(
        tmp_path, monkeypatch):
    import jax

    seen = {}

    def start_trace(log_dir, *args, profiler_options=None, **kw):
        seen["dir"] = log_dir
        seen["python"] = profiler_options.python_tracer_level
        seen["host"] = profiler_options.host_tracer_level

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    dp = devprof_mod.DevProfiler()
    out = dp.device_capture(str(tmp_path), 0.1)
    assert out == {"dir": str(tmp_path), "seconds": 0.1, "id": 1}
    # The Python tracer is what slowed a traced server ninefold; the
    # host tracer has to stay on, the span mirror writes through it.
    assert seen == {"dir": str(tmp_path), "python": 0, "host": 2}
    assert tracing._CAPTURE[1] == {"dir": str(tmp_path), "id": 1}
    with pytest.raises(RuntimeError, match="already armed"):
        dp.device_capture(str(tmp_path), 0.1)
    assert dp.finish_capture(timeout=30)
    assert tracing._CAPTURE is None
    assert dp.capture_state() == {"state": "done", "dir": str(tmp_path),
                                  "id": 1, "file": None}
    assert devprof_mod.NOP.capture_state()["state"] == "idle"
    assert devprof_mod.NOP.finish_capture() is True


def test_capture_on_the_cpu_holds_anchor_and_spans_and_says_its_state(
        tmp_path):
    """A real capture on the CPU backend: the state route walks idle,
    armed, done; a profile served while armed names the capture and its
    spans carry their monotonic start; the host plane of the file holds
    the anchor and a ``pilosa:query`` annotation that the anchor puts
    within a fraction of a millisecond of the span's own start."""
    from perfbench.lib import spans as spans_mod
    from perfbench.lib import xplane
    from pilosa_tpu.server.server import Server

    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        b = f"http://{s.host}"
        _http("POST", f"{b}/index/i", b"{}")
        _http("POST", f"{b}/index/i/frame/f", b"{}")
        _http("POST", f"{b}/index/i/query",
              b'SetBit(frame="f", rowID=1, columnID=1)')
        route = f"{b}/debug/profile/device"
        assert _http("GET", route) == (200, {"state": "idle", "dir": None,
                                             "file": None})
        trace_dir = str(tmp_path / "trace")
        status, armed = _http("POST", f"{route}?seconds=1&dir={trace_dir}")
        assert status == 200 and armed["id"] == 1
        _, st = _http("GET", route)
        assert st["state"] == "armed" and st["dir"] == trace_dir
        assert _http("POST", f"{route}?seconds=1")[0] == 409
        _, doc = _http("POST", f"{b}/index/i/query?profile=true",
                       b'Count(Bitmap(frame="f", rowID=1))')
        prof = doc["profile"]
        assert prof["capture"] == {"dir": trace_dir, "id": 1}
        assert all("startNs" in sp for sp in prof["spans"])
        done = _wait_state(route, "done")
        assert done["dir"] == trace_dir and os.path.getsize(done["file"]) > 0
        assert done["file"] == xplane.find_xplane(trace_dir)
        # Armed no more: the next profile is plain again.
        _, doc = _http("POST", f"{b}/index/i/query?profile=true",
                       b'Count(Bitmap(frame="f", rowID=1))')
        assert "capture" not in doc["profile"]
        assert "startNs" not in doc["profile"]["spans"][0]
    finally:
        s.close()
    planes = xplane.read_planes(done["file"], prefix="/host:")
    offset = spans_mod.anchor_offset_ps(planes, 1)
    assert offset is not None
    assert spans_mod.anchor_offset_ps(planes, 2) is None
    root = next(sp for sp in prof["spans"] if sp["parentId"] is None)
    marks = spans_mod.annotations(planes, "pilosa:query")
    assert marks
    mapped = root["startNs"] * 1000 + offset
    assert min(abs(m - mapped) for m in marks) < 200e6     # 200 us, in ps


def test_sigterm_mid_capture_exits_clean(tmp_path):
    """A server that is told to stop while a capture is armed stops the
    capture, lets the profiler write its file, and exits 0."""
    import signal
    import subprocess
    import time

    from pilosa_tpu.testing import free_ports

    port = free_ports(1)[0]
    env = dict(os.environ, PYTHONPATH=ROOT, PILOSA_DRAIN_TIMEOUT="2")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server", "-d",
         str(tmp_path / "d"), "--bind", f"127.0.0.1:{port}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        route = f"http://127.0.0.1:{port}/debug/profile/device"
        deadline = time.monotonic() + 90
        while True:
            assert proc.poll() is None, "server died during boot"
            assert time.monotonic() < deadline, "server did not come up"
            try:
                if _http("GET", route)[0] == 200:
                    break
            except OSError:
                time.sleep(0.25)
        trace_dir = str(tmp_path / "trace")
        assert _http("POST", f"{route}?seconds=20&dir={trace_dir}")[0] == 200
        time.sleep(0.3)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
    from perfbench.lib import xplane

    path = xplane.find_xplane(trace_dir)
    assert path and os.path.getsize(path) > 0
