"""Distributed query tracing (pilosa_tpu/tracing.py) + the
observability satellites: span nesting, ring eviction, header
propagation through Handler.dispatch and across a real 2-node
cluster, the slow-query flight recorder on /metrics, prometheus
exposition edge cases, statsd client-side sampling, and the config
TOML round trip."""
import json
import urllib.request

import pytest

from pilosa_tpu import SLICE_WIDTH, tracing
from pilosa_tpu.server.server import Server
from pilosa_tpu.testing import free_ports


def http(method, url, body=None, ctype="application/json", headers=None):
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", ctype)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def jget(url):
    status, data, _ = http("GET", url)
    assert status == 200, data
    return json.loads(data)


def base(s):
    return f"http://{s.host}"


# ----------------------------------------------------------- unit: tracer


def test_span_nesting_and_tree():
    tr = tracing.Tracer(ring_size=8)
    with tr.start("query", index="i"):
        with tracing.span("parse"):
            pass
        with tracing.span("call:Count"):
            with tracing.span("slice", slice=0):
                pass
            with tracing.span("slice", slice=1):
                pass
    assert tracing.active_span() is None
    d = tr.recent(1)[0]
    assert {s["name"] for s in d["spans"]} == {
        "query", "parse", "call:Count", "slice"}
    (root,) = d["roots"]
    assert root["name"] == "query"
    kids = [c["name"] for c in root["children"]]
    assert kids == ["parse", "call:Count"]
    count_node = root["children"][1]
    assert [c["tags"]["slice"] for c in count_node["children"]] == [0, 1]
    assert all(s["durationMs"] is not None for s in d["spans"])


def test_ring_eviction():
    tr = tracing.Tracer(ring_size=4)
    for i in range(10):
        with tr.start("q", n=i):
            pass
    assert tr.ring_len() == 4
    got = [t["roots"][0]["tags"]["n"] for t in tr.recent(10)]
    assert got == [9, 8, 7, 6]  # newest first, oldest evicted


def test_slow_ring_and_stats():
    from pilosa_tpu.stats import ExpvarStatsClient, prometheus_exposition

    stats = ExpvarStatsClient()
    tr = tracing.Tracer(ring_size=8, slow_threshold=0.0, stats=stats)
    with tr.start("q"):
        pass
    assert tr.ring_len(slow=True) == 1
    snap = stats.snapshot()
    assert snap["slow_queries_total"] == 1
    assert snap["query_latency_seconds_count"] == 1
    expo = prometheus_exposition(snap)
    assert "pilosa_slow_queries_total 1" in expo
    assert 'pilosa_query_latency_seconds_bucket{le="5.0"} 1' in expo
    # Prometheus histogram_quantile() needs an explicit +Inf bucket.
    assert 'pilosa_query_latency_seconds_bucket{le="+Inf"} 1' in expo


def test_nop_paths_record_nothing():
    # Module-level span() with no active trace is the shared nop CM.
    assert tracing.span("anything", x=1) is tracing.NOP_SPAN
    assert tracing.child_of(None, "x") is tracing.NOP_SPAN
    assert tracing.trace_headers() is None
    with tracing.NOP_SPAN as sp:
        sp.tag(a=1)  # must not blow up
    nop = tracing.NopTracer()
    with nop.start("q"):
        pass
    assert nop.recent() == [] and nop.ring_len() == 0


def test_stitch_merges_cross_node_spans():
    tr_a, tr_b = tracing.Tracer(), tracing.Tracer()
    with tr_a.start("query") as root:
        with tracing.span("node.remote", host="b") as fan:
            fan_id = fan.span_id
        tid = root.trace.trace_id
    # The "remote" node adopts the propagated ids.
    with tr_b.start("query.remote", trace_id=tid, parent_id=fan_id):
        with tracing.span("slice", slice=3):
            pass
    stitched = tracing.stitch(tr_a.recent(1) + tr_b.recent(1))
    assert stitched["traceId"] == tid
    (root_node,) = stitched["roots"]
    fan_node = next(c for c in root_node["children"]
                    if c["name"] == "node.remote")
    assert fan_node["children"][0]["name"] == "query.remote"
    with pytest.raises(ValueError):
        tracing.stitch(tr_a.recent(1)
                       + [{"traceId": "other", "spans": []}])


# ------------------------------------------ handler round trip (1 node)


@pytest.fixture
def traced_server(tmp_path):
    s = Server(str(tmp_path / "data"), bind="localhost:0",
               trace_enabled=True, trace_slow_threshold=0.0).open()
    yield s
    s.close()


def _seed(s, slices=2):
    b = base(s)
    http("POST", f"{b}/index/i", b"{}")
    http("POST", f"{b}/index/i/frame/f", b"{}")
    for sl in range(slices):
        http("POST", f"{b}/index/i/query",
             f'SetBit(frame="f", rowID=1, columnID={sl * SLICE_WIDTH + 1})'
             .encode())


def test_header_adoption_through_dispatch(traced_server):
    """A query arriving with propagated trace headers records its
    trace under the REMOTE ids — the round trip the coordinator's
    fan-out performs, exercised through Handler.dispatch."""
    h = traced_server.handler
    _seed(traced_server)
    status, _, payload = h.dispatch(
        "POST", "/index/i/query", {},
        b'Count(Bitmap(frame="f", rowID=1))',
        {"X-Pilosa-Trace-Id": "feedbeeffeedbeef",
         "X-Pilosa-Span-Id": "cafecafecafecafe"})[:3]
    assert status == 200, payload
    traces = h.tracer.recent(5, trace_id="feedbeeffeedbeef")
    assert traces, "remote trace id was not adopted"
    d = traces[0]
    roots = d["roots"]
    assert roots[0]["name"] == "query.remote"
    assert roots[0]["parentId"] == "cafecafecafecafe"
    names = {s["name"] for s in d["spans"]}
    assert "parse" in names and "call:Count" in names


def test_profile_inline_and_response_header(traced_server):
    _seed(traced_server)
    status, data, hdrs = http(
        "POST", f"{base(traced_server)}/index/i/query?profile=true",
        b'Count(Bitmap(frame="f", rowID=1))')
    assert status == 200
    doc = json.loads(data)
    assert doc["results"] == [2]
    prof = doc["profile"]
    assert prof["traceId"] == hdrs["X-Pilosa-Trace-Id"]
    assert prof["roots"][0]["name"] == "query"
    assert any(s["name"] == "parse" for s in prof["spans"])


def test_profile_without_global_tracing(tmp_path):
    """?profile=true on a tracing-disabled server: ephemeral recorder,
    span tree in the response, nothing retained server-side."""
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        _seed(s)
        status, data, _ = http(
            "POST", f"{base(s)}/index/i/query?profile=true",
            b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        assert json.loads(data)["profile"]["roots"]
        assert s.handler.tracer is tracing.NOP
        out = jget(f"{base(s)}/debug/traces")
        assert out == {"enabled": False, "slowThresholdMs": 250.0,
                       "summary": {}, "traces": []}
    finally:
        s.close()


def test_debug_traces_and_slow_metrics(traced_server):
    _seed(traced_server)
    b = base(traced_server)
    status, data, _ = http("POST", f"{b}/index/i/query",
                           b'Count(Bitmap(frame="f", rowID=1))')
    assert status == 200
    out = jget(f"{b}/debug/traces")
    assert out["enabled"] and out["traces"]
    # slow-threshold 0 ⇒ every query is slow: flight recorder + metric.
    slow = jget(f"{b}/debug/traces?slow=true")
    assert slow["traces"]
    _, expo, _ = http("GET", f"{b}/metrics")
    assert b"pilosa_slow_queries_total" in expo
    assert b"pilosa_query_latency_seconds_bucket" in expo


def test_diagnostics_flush_includes_perf_summary(traced_server, tmp_path):
    from pilosa_tpu.diagnostics import Diagnostics

    _seed(traced_server)
    http("POST", f"{base(traced_server)}/index/i/query",
         b'Count(Bitmap(frame="f", rowID=1))')
    sink = tmp_path / "diag.jsonl"
    d = Diagnostics(server=traced_server, sink_path=str(sink))
    rec = d.flush()
    assert rec["SlowQueries"] >= 1
    assert rec["TracingSummary"]["slowQueries"] >= 1
    assert "QueryLatencyP50Ms" in rec
    assert json.loads(sink.read_text().splitlines()[0]) == rec


# --------------------------------------------- distributed stitch (2 nodes)


def test_distributed_fanout_trace_stitches(tmp_path):
    """Acceptance: a fan-out query with tracing enabled yields ONE
    trace tree — coordinator + remote spans stitched by the propagated
    trace id — with per-slice spans >= the slice count; the same query
    with tracing disabled takes the nop path (no ring growth)."""
    ports = free_ports(2)
    hosts = [f"localhost:{p}" for p in ports]
    servers = [
        Server(str(tmp_path / f"n{i}"), bind=hosts[i], cluster_hosts=hosts,
               replica_n=1, anti_entropy_interval=0, polling_interval=0,
               trace_enabled=True, trace_slow_threshold=30.0).open()
        for i in range(2)
    ]
    try:
        a, b = servers
        for s in servers:
            # Pin the serial per-slice path so every slice gets a span
            # (the batched path runs one fused program per node).
            s.executor._force_path = "serial"
        http("POST", f"{base(a)}/index/i", b"{}")
        http("POST", f"{base(a)}/index/i/frame/f", b"{}")
        n_slices = 6
        for sl in range(n_slices):
            status, data, _ = http(
                "POST", f"{base(a)}/index/i/query",
                f'SetBit(frame="f", rowID=1, columnID={sl * SLICE_WIDTH + 1})'
                .encode())
            assert status == 200, data

        status, data, hdrs = http("POST", f"{base(a)}/index/i/query",
                                  b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200 and json.loads(data)["results"] == [n_slices]
        tid = hdrs["X-Pilosa-Trace-Id"]

        # Gather the trace's pieces from EACH node's ring and stitch.
        pieces = []
        for s in servers:
            out = jget(f"{base(s)}/debug/traces?traceId={tid}")
            pieces.extend(out["traces"])
        assert len(pieces) >= 2, "remote node recorded no adopted trace"
        stitched = tracing.stitch(pieces)
        assert stitched["traceId"] == tid
        (root,) = stitched["roots"]  # ONE tree: remote roots resolved
        assert root["name"] == "query"

        names = [s["name"] for s in stitched["spans"]]
        assert names.count("slice") >= n_slices
        assert "node.remote" in names and "node.local" in names
        assert "remote.round" in names
        assert any(n == "query.remote" for n in names)

        # Remote spans sit UNDER the coordinator's fan-out span.
        def find(node, name):
            if node["name"] == name:
                return node
            for c in node["children"]:
                hit = find(c, name)
                if hit is not None:
                    return hit
            return None

        fan = find(root, "node.remote")
        assert fan is not None and find(fan, "query.remote") is not None

        # Tracing disabled ⇒ nop path, no ring growth.
        ports2 = free_ports(2)
        hosts2 = [f"localhost:{p}" for p in ports2]
        plain = [
            Server(str(tmp_path / f"p{i}"), bind=hosts2[i],
                   cluster_hosts=hosts2, replica_n=1,
                   anti_entropy_interval=0, polling_interval=0).open()
            for i in range(2)
        ]
        try:
            http("POST", f"{base(plain[0])}/index/i", b"{}")
            http("POST", f"{base(plain[0])}/index/i/frame/f", b"{}")
            for sl in range(n_slices):
                http("POST", f"{base(plain[0])}/index/i/query",
                     f'SetBit(frame="f", rowID=1, columnID='
                     f'{sl * SLICE_WIDTH + 1})'.encode())
            status, data, hdrs = http(
                "POST", f"{base(plain[0])}/index/i/query",
                b'Count(Bitmap(frame="f", rowID=1))')
            assert status == 200 and json.loads(data)["results"] == [n_slices]
            assert "X-Pilosa-Trace-Id" not in hdrs
            for s in plain:
                assert s.handler.tracer is tracing.NOP
                assert s.handler.tracer.ring_len() == 0
                assert jget(f"{base(s)}/debug/traces")["traces"] == []
        finally:
            for s in plain:
                s.close()
    finally:
        for s in servers:
            s.close()


# ---------------------------------------------------- exposition edge cases


def test_prometheus_exposition_edge_cases():
    from pilosa_tpu.stats import prometheus_exposition

    snap = {
        "Plain": 3,
        "Quoted;who:say \"hi\"": 1,
        "Newline;msg:a\nb": 2,
        "Comma;list:a,b": 4,       # comma splits the tag list: must
        "BoolSkipped": True,       # still render a parseable line
        "StrSkipped": "nope",
        "Float": 1.5,
    }
    out = prometheus_exposition(
        snap, namespaced=(("grp", {"x": 7, "skip": False}),))
    lines = out.strip().splitlines()
    assert "pilosa_Plain 3" in lines
    assert 'pilosa_Quoted{who="say \\"hi\\""} 1' in lines
    assert 'pilosa_Newline{msg="a\\nb"} 2' in lines
    assert "pilosa_grp_x 7" in lines
    assert not any("BoolSkipped" in ln or "StrSkipped" in ln
                   or "grp_skip" in ln for ln in lines)
    comma = next(ln for ln in lines if ln.startswith("pilosa_Comma"))
    # Exposition-format sanity for the degraded comma case: every label
    # is key="value" and the sample value survives.
    import re

    m = re.fullmatch(r'pilosa_Comma\{([^}]*)\} 4', comma)
    assert m, comma
    for label in m.group(1).split(","):
        assert re.fullmatch(r'\w*="[^"]*"', label), label


def test_statsd_rate_sampling_deterministic():
    from pilosa_tpu.stats import StatsdClient

    sent = []

    class _Sock:
        def sendto(self, payload, addr):
            sent.append(payload.decode())

    rolls = iter([0.05, 0.95, 0.05, 0.95])
    c = StatsdClient(_sock=_Sock(), _rand=lambda: next(rolls))
    c.count("hits", 1, rate=0.1)   # 0.05 < 0.1 → sent
    c.count("hits", 1, rate=0.1)   # 0.95 ≥ 0.1 → dropped
    c.timing("lat", 0.5, rate=0.5)  # 0.05 < 0.5 → sent
    c.gauge("g", 2, rate=0.5)       # 0.95 ≥ 0.5 → dropped
    assert sent == ["hits:1|c|@0.1", "lat:500|ms|@0.5"]
    c.count("always", 1)            # rate=1.0 never consults _rand
    assert sent[-1] == "always:1|c"
    # with_tags children inherit the seam (and the socket).
    rolls2 = iter([0.01])
    c2 = StatsdClient(_sock=_Sock(), _rand=lambda: next(rolls2))
    c2.with_tags("k:v").count("tagged", 1, rate=0.9)
    assert sent[-1] == "tagged:1|c|@0.9|#k:v"


# ------------------------------------------------- config TOML round trip


def test_config_imports_and_loads_on_this_interpreter(tmp_path):
    """The module must import and parse TOML, and what it generates
    must round-trip through the same reader."""
    import pilosa_tpu.config as cfgmod

    p = tmp_path / "c.toml"
    p.write_text('bind = "localhost:7777"\n\n[trace]\n  enabled = true\n'
                 '  slow-threshold = 0.5\n')
    cfg = cfgmod.Config.load(str(p), env={})
    assert cfg.bind == "localhost:7777"
    assert cfg.trace["enabled"] is True
    assert cfg.trace["slow-threshold"] == 0.5
    # The generated config round-trips through the same reader.
    p2 = tmp_path / "rt.toml"
    p2.write_text(cfg.to_toml())
    rt = cfgmod.Config.load(str(p2), env={})
    assert rt.trace == cfg.trace


# ------------------------------- spans down to dispatch / wait / fetch

PAIR = 'Count(Intersect(Bitmap(frame="f", rowID={a}), Bitmap(frame="f", rowID={b})))'

# parent -> children of one batched Count under ?profile=true, in
# order; a name ending in "?" is there only where its parent's work
# missed a cache.
SPAN_TREE = {
    "query": ["parse", "call:Count", "encode"],
    "call:Count": ["count.plan", "result.memo", "costmodel.estimate",
                   "exec.route", "plan_and_stage", "kernel:count_batched",
                   "reduce", "costmodel.record"],
    "plan_and_stage": ["plan.tree", "stacks.memo", "stacks.build?"],
    "stacks.build": ["build.frags", "build.window", "build.args"],
    "kernel:count_batched": ["kernel.fn", "kernel.dispatch", "kernel.wait",
                             "kernel.fetch"],
}


def _seed_rows(s, rows=(1, 2, 3), slices=2):
    b = base(s)
    http("POST", f"{b}/index/i", b"{}")
    http("POST", f"{b}/index/i/frame/f", b"{}")
    for sl in range(slices):
        for r in rows:
            http("POST", f"{b}/index/i/query",
                 f'SetBit(frame="f", rowID={r}, '
                 f'columnID={sl * SLICE_WIDTH + r})'.encode())


def _profiled(s, pql):
    status, data, _ = http("POST", f"{base(s)}/index/i/query?profile=true",
                           pql.encode())
    assert status == 200, data
    return json.loads(data)


def _check_tree(node):
    want = SPAN_TREE.get(node["name"])
    got = [c["name"] for c in node["children"]]
    if want is not None:
        assert got == [w.rstrip("?") for w in want
                       if not w.endswith("?") or w.rstrip("?") in got], \
            (node["name"], got)
    end = node["start"] + node["durationMs"] / 1000.0
    for c in node["children"]:
        # Starts are exact, durations rounded to the microsecond.
        assert c["start"] >= node["start"], (node["name"], c["name"])
        assert c["start"] + c["durationMs"] / 1000.0 <= end + 2e-6, \
            (node["name"], c["name"])
        _check_tree(c)


def test_batched_count_span_tree_and_stack_builds(tmp_path):
    """One Count through the batched path under ?profile=true: the
    span tree down to dispatch / wait / fetch with every child inside
    its parent; ``stackBuilds`` counts the stacks the first query of
    two fresh rows builds and is 0 for the next query over them."""
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        _seed_rows(s)
        doc = _profiled(s, PAIR.format(a=1, b=2))
        assert doc["results"] == [0]
        prof = doc["profile"]
        (root,) = prof["roots"]
        assert root["name"] == "query" and root["parentId"] is None
        assert root["tags"]["httpParseMs"] >= 0
        _check_tree(root)
        by_name = {sp["name"]: sp for sp in prof["spans"]}
        assert by_name["count.plan"]["tags"] == {"tier": "static"}
        assert by_name["result.memo"]["tags"] == {"kind": "count_res",
                                                  "hit": False}
        assert by_name["exec.route"]["tags"] == {"choice": "batched"}
        assert by_name["stacks.memo"]["tags"] == {"hit": False}
        assert by_name["kernel.fn"]["tags"] == {"compile": True}
        assert "stacks.build" in by_name
        assert "startNs" not in root and "capture" not in prof
        res = prof["resources"]
        assert res["stackBuilds"] == 2 and res["oomFallbacks"] == 0
        assert res["servedBy"] == {"batched": 1} and res["planMs"] > 0
        # The same rows the other way round: another query, no build.
        again = _profiled(s, PAIR.format(a=2, b=1))["profile"]
        _check_tree(again["roots"][0])
        assert again["resources"]["stackBuilds"] == 0
        assert again["resources"]["servedBy"] == {"batched": 1}
        # The query itself again: the result memo answers, and says so.
        memo = _profiled(s, PAIR.format(a=1, b=2))["profile"]
        tags = {sp["name"]: sp["tags"] for sp in memo["spans"]}
        assert tags["result.memo"]["hit"] is True
        assert "plan_and_stage" not in tags
    finally:
        s.close()


def test_new_span_sites_cost_nothing_without_a_trace(tmp_path, monkeypatch):
    """No trace active: every new site gets the shared no-op, the
    launch's dispatch / wait / fetch cut makes no span either, and
    nothing of the capture mirror runs."""
    from pilosa_tpu.ops import bitops

    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        _seed_rows(s)
        seen, launched = [], []
        real_span, real_launch = tracing.span, bitops.launch

        def spy(name, **tags):
            out = real_span(name, **tags)
            seen.append((name, out))
            return out

        def spy_launch(name, *a, **k):
            launched.append(name)
            return real_launch(name, *a, **k)

        def no_span(*a, **k):
            raise AssertionError("a span was made without a trace")

        monkeypatch.setattr(tracing, "span", spy)
        monkeypatch.setattr(bitops, "launch", spy_launch)
        monkeypatch.setattr(tracing.Span, "__init__", no_span)
        assert tracing._CAPTURE is None and tracing.active_span() is None
        status, _, payload = s.handler.dispatch(
            "POST", "/index/i/query", {}, PAIR.format(a=1, b=3).encode(),
            {})[:3]
        assert status == 200 and json.loads(payload) == {"results": [0]}
        assert all(out is tracing.NOP_SPAN for _, out in seen)
        assert {"count.plan", "result.memo", "exec.route", "plan.tree",
                "stacks.memo", "stacks.build", "build.frags",
                "build.window", "build.args", "reduce", "encode"} \
            <= {name for name, _ in seen}
        assert "count_batched" in launched
        assert {"kernel.fn", "kernel.dispatch", "kernel.wait",
                "kernel.fetch"} <= {name for name, _ in seen}
    finally:
        s.close()


def test_resource_exhausted_counts_an_oom_fallback(tmp_path, monkeypatch):
    """A batched call the device refuses for memory still answers (per
    slice), still records ``batched:error``, and is counted: in the
    query's resources and in /debug/vars."""
    from pilosa_tpu.ops import bitops

    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        _seed_rows(s)
        real_launch = bitops.launch

        def failing(error):
            def launch(name, *a, **k):
                if name == "count_batched":
                    raise RuntimeError(error)
                return real_launch(name, *a, **k)
            return launch

        monkeypatch.setattr(bitops, "launch", failing(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "125042688 bytes."))
        doc = _profiled(s, PAIR.format(a=1, b=2))
        assert doc["results"] == [0]
        res = doc["profile"]["resources"]
        assert res["oomFallbacks"] == 1
        assert "batched:error" in res["fallbackChain"]
        assert res["servedBy"] == {"serial": 1}
        assert jget(f"{base(s)}/debug/vars")["oomFallbacks"] == 1

        monkeypatch.setattr(bitops, "launch",
                            failing("INTERNAL: something else"))
        res = _profiled(s, PAIR.format(a=1, b=3))["profile"]["resources"]
        assert res["oomFallbacks"] == 0
        assert "batched:error" in res["fallbackChain"]
        assert jget(f"{base(s)}/debug/vars")["oomFallbacks"] == 1
    finally:
        s.close()


def test_program_names_say_tier_and_operands():
    from pilosa_tpu import executor as executor_mod

    assert executor_mod.program_name("count_batched", 3) \
        == "pilosa_count_batched_k3"
    assert executor_mod.program_name("sum_batched", 0) \
        == "pilosa_sum_batched_k0"
    assert executor_mod.program_name("topn_rows", 64) \
        == "pilosa_topn_rows_k16p"
    three = ("Intersect", [("leaf", 0), ("Difference",
                                         [("leaf", 1), ("leaf", 2)])])
    assert executor_mod._plan_operands(three) == 3
    assert executor_mod._plan_operands(("empty",)) == 0
    assert executor_mod._plan_operands(None) == 0
    assert executor_mod._plan_operands(
        ("Union", [("leaf", 0), ("bsi", 1, (2, 3), "between", None, 4)])) == 4


def test_batched_count_program_carries_its_name(tmp_path):
    """What the device trace shows for a two- and a three-operand
    Count: the lowered module's name."""
    s = Server(str(tmp_path / "d"), bind="localhost:0").open()
    try:
        _seed_rows(s)
        _profiled(s, PAIR.format(a=1, b=2))
        _profiled(s, 'Count(Intersect(Bitmap(frame="f", rowID=1), '
                     'Difference(Bitmap(frame="f", rowID=2), '
                     'Bitmap(frame="f", rowID=3))))')
        import jax
        import jax.numpy as jnp

        modules = set()
        for key, fn in s.executor._batched_cache.items():
            if len(key) != 3:
                continue              # another tier's program
            _, padded_n, width32 = key
            operands = int(fn.__wrapped__.__name__.rsplit("_k", 1)[1])
            stack = jax.ShapeDtypeStruct((padded_n, width32), jnp.uint32)
            text = fn.lower(*[stack] * operands).as_text()
            modules.add(text.split("module @", 1)[1].split()[0])
        assert modules == {"jit_pilosa_count_batched_k2",
                           "jit_pilosa_count_batched_k3"}
    finally:
        s.close()
