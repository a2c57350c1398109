"""QoS & admission control (pilosa_tpu/qos.py): deadline propagation
through the serving stack, priority load shedding, per-client quotas,
and peer circuit breakers — unit tests for each mechanism plus the
cluster acceptance scenarios from the issue (deadline expiry mid
fan-out must 504 within the budget; a saturated gate must shed with
429/503 + Retry-After while in-flight queries complete)."""
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import qos
from pilosa_tpu.server.server import Server
from pilosa_tpu.testing import free_ports


def http(method, url, body=None, headers=None):
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


# ------------------------------------------------------------- units

def test_token_bucket_refill_and_retry_after():
    clock = [0.0]
    b = qos.TokenBucket(rate=2.0, burst=2.0, now=clock[0])
    assert b.try_take(clock[0]) == 0.0
    assert b.try_take(clock[0]) == 0.0
    wait = b.try_take(clock[0])
    assert wait == pytest.approx(0.5)  # 1 token at 2/s
    clock[0] += 0.5
    assert b.try_take(clock[0]) == 0.0


def test_client_quotas_per_client_and_overrides():
    clock = [0.0]
    q = qos.ClientQuotas(default_qps=1.0, default_burst=1.0,
                         overrides={"vip": 0}, clock=lambda: clock[0])
    q.allow("a")
    with pytest.raises(qos.ShedError) as ei:
        q.allow("a")
    assert ei.value.status == 429 and ei.value.retry_after > 0
    q.allow("b")            # independent bucket
    for _ in range(10):
        q.allow("vip")      # qps 0 override = unlimited
    clock[0] += 1.0
    q.allow("a")            # refilled
    assert q.snapshot()["deniedTotal"] == 1


def test_quotas_disabled_by_default():
    q = qos.ClientQuotas()   # default qps 0 = off
    for _ in range(100):
        q.allow("anyone")


def test_quota_eviction_is_not_a_reset(monkeypatch):
    """Hitting the bucket-table bound must not refill every live
    client's quota (the old clear() did): full buckets evict
    losslessly, an exhausted slow-refill bucket survives and keeps
    denying."""
    monkeypatch.setattr(qos.ClientQuotas, "MAX_CLIENTS", 8)
    clock = [0.0]
    q = qos.ClientQuotas(default_qps=1.0, default_burst=1.0,
                         overrides={"limited": 0.01},
                         clock=lambda: clock[0])
    q.allow("limited")
    with pytest.raises(qos.ShedError):
        q.allow("limited")           # empty; refill takes ~100 s
    for i in range(32):              # churn ids past the table bound;
        clock[0] += 1.0              # 1 s apart so churned buckets
        q.allow(f"new-{i}")          # refill to full (lossless evict)
    with pytest.raises(qos.ShedError):
        q.allow("limited")           # live throttle state survived
    assert len(q._buckets) <= 8


def test_admission_gate_sheds_when_queue_full():
    g = qos.AdmissionGate(max_concurrent=1, queue_length=0,
                          queue_timeout=0.05)
    assert g.acquire() == 0.0
    with pytest.raises(qos.ShedError) as ei:
        g.acquire()
    assert ei.value.status == 503
    g.release()
    assert g.acquire() == 0.0
    g.release()


def test_admission_gate_internal_never_queues():
    g = qos.AdmissionGate(max_concurrent=1, queue_length=0,
                          queue_timeout=0.05)
    g.acquire()
    # Internal fan-out admits even at capacity — it must never park
    # behind (or be shed with) user traffic.
    assert g.acquire(priority=qos.PRIO_INTERNAL) == 0.0
    g.release()
    g.release()


def test_admission_gate_priority_handoff():
    """A released slot goes to the highest-priority earliest waiter:
    interactive overtakes batch that queued first."""
    g = qos.AdmissionGate(max_concurrent=1, queue_length=8,
                          queue_timeout=5.0)
    g.acquire()
    order = []
    started = threading.Barrier(3)

    def waiter(prio, name):
        started.wait()
        if name == "interactive":
            time.sleep(0.1)  # batch queues FIRST, interactive still wins
        g.acquire(priority=prio)
        order.append(name)
        time.sleep(0.02)
        g.release()

    threads = [
        threading.Thread(target=waiter, args=(qos.PRIO_BATCH, "batch")),
        threading.Thread(target=waiter,
                         args=(qos.PRIO_INTERACTIVE, "interactive")),
    ]
    for t in threads:
        t.start()
    started.wait()
    time.sleep(0.3)   # both parked in the queue
    g.release()       # hand-off begins
    for t in threads:
        t.join(timeout=10)
    assert order == ["interactive", "batch"]


def test_admission_gate_queue_timeout_sheds():
    g = qos.AdmissionGate(max_concurrent=1, queue_length=4,
                          queue_timeout=0.05)
    g.acquire()
    t0 = time.perf_counter()
    with pytest.raises(qos.ShedError) as ei:
        g.acquire()
    assert time.perf_counter() - t0 < 2.0
    assert ei.value.status == 503
    assert g.snapshot()["shedQueueTimeout"] == 1
    g.release()


def test_breaker_lifecycle():
    clock = [0.0]
    b = qos.PeerBreakers(threshold=3, cooldown=5.0,
                         clock=lambda: clock[0])
    host = "peer:10101"
    assert b.allow(host)
    for _ in range(2):
        b.record_failure(host)
    assert b.allow(host)          # under threshold: still closed
    b.record_failure(host)        # 3rd consecutive: opens
    assert not b.allow(host)
    assert b.is_open(host)
    assert host in b.open_hosts()
    clock[0] += 5.0               # cooldown elapses -> half-open
    assert b.allow(host)          # the single probe slot
    assert not b.allow(host)      # concurrent request: refused
    b.record_failure(host)        # probe failed -> reopens
    assert not b.allow(host)
    clock[0] += 5.0
    assert b.allow(host)
    b.record_success(host)        # probe succeeded -> closed
    assert b.allow(host) and b.allow(host)
    assert not b.open_hosts()
    m = b.metrics()
    assert m["breaker_open_total"] == 2
    assert m[f"breaker_state;peer:{host}"] == 0


def test_breaker_abort_probe_releases_half_open_slot():
    """An inconclusive half-open probe (budget expired mid-flight)
    must release the probe slot — not wedge the peer in HALF_OPEN."""
    clock = [0.0]
    b = qos.PeerBreakers(threshold=1, cooldown=5.0,
                         clock=lambda: clock[0])
    b.record_failure("h")
    clock[0] += 5.0
    assert b.allow("h")           # the half-open probe slot
    assert not b.allow("h")       # held
    b.abort_probe("h")            # probe ended with no verdict
    assert b.allow("h")           # next request takes the slot
    b.record_success("h")
    assert b.snapshot()["h"]["state"] == "closed"


def test_breaker_success_resets_failure_streak():
    b = qos.PeerBreakers(threshold=3)
    b.record_failure("h")
    b.record_failure("h")
    b.record_success("h")         # consecutive counter resets
    b.record_failure("h")
    b.record_failure("h")
    assert b.allow("h")


def test_deadline_scope_nests_tighter_only():
    # Deadlines are monotonic-clock instants in-process; only the
    # X-Pilosa-Deadline wire format is wall-clock.
    outer = time.monotonic() + 100
    inner = time.monotonic() + 200
    with qos.deadline_scope(outer):
        assert qos.current_deadline() == outer
        with qos.deadline_scope(inner):   # looser: outer wins
            assert qos.current_deadline() == outer
        with qos.deadline_scope(time.monotonic() - 1):
            with pytest.raises(qos.DeadlineExceeded):
                qos.check_deadline()
        assert qos.current_deadline() == outer
    assert qos.current_deadline() is None


# --------------------------------------------------- single-node HTTP

@pytest.fixture
def qserver(tmp_path):
    s = Server(str(tmp_path / "data"), bind="localhost:0",
               qos={"enabled": True, "max-concurrent": 1,
                    "queue-length": 0, "queue-timeout": 0.2,
                    # Default qps 0 (unlimited) so only the "greedy"
                    # client is rate-limited — the shed test's
                    # anonymous bursts must hit the GATE, not a quota.
                    "quotas": {"greedy": 0.5}}).open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    http("POST", f"{base}/index/i/frame/f", b"{}")
    http("POST", f"{base}/index/i/query",
         b'SetBit(frame="f", rowID=1, columnID=2)')
    yield s, base
    s.close()


def test_shed_under_load_429_503_with_retry_after(qserver):
    """Saturate the 1-slot gate from threads: in-flight queries
    complete normally, the overflow sheds 503 + Retry-After."""
    s, base = qserver
    release = threading.Event()
    in_handler = threading.Event()
    orig = s.executor.execute

    def slow_execute(*a, **kw):
        in_handler.set()
        release.wait(10)
        return orig(*a, **kw)

    s.executor.execute = slow_execute
    results = []

    def query():
        results.append(http("POST", f"{base}/index/i/query",
                            b'Count(Bitmap(frame="f", rowID=1))'))

    holder = threading.Thread(target=query)
    holder.start()
    assert in_handler.wait(10)        # one query holds the only slot
    shed = [http("POST", f"{base}/index/i/query",
                 b'Count(Bitmap(frame="f", rowID=1))')
            for _ in range(3)]
    release.set()
    holder.join(timeout=10)
    s.executor.execute = orig

    status, body, _ = results[0]
    assert status == 200 and json.loads(body)["results"] == [1]
    for status, body, headers in shed:
        assert status == 503
        assert b"overloaded" in body
        assert float(headers["Retry-After"]) > 0
    out = json.loads(http("GET", f"{base}/debug/qos")[1])
    assert out["gate"]["shedQueueFull"] == 3
    assert out["shedTotal"] == 3


def test_client_quota_429(qserver):
    s, base = qserver
    hdr = {"X-Pilosa-Client-Id": "greedy"}
    q = b'Count(Bitmap(frame="f", rowID=1))'
    first = http("POST", f"{base}/index/i/query", q, hdr)
    assert first[0] == 200
    second = http("POST", f"{base}/index/i/query", q, hdr)
    assert second[0] == 429
    assert float(second[2]["Retry-After"]) > 0
    # A different client has its own bucket.
    assert http("POST", f"{base}/index/i/query", q,
                {"X-Pilosa-Client-Id": "other"})[0] == 200


def test_expired_deadline_504(qserver):
    s, base = qserver
    q = b'Count(Bitmap(frame="f", rowID=1))'
    status, body, _ = http(
        "POST", f"{base}/index/i/query", q,
        # Wire format is unix-epoch WALL clock (converted to
        # monotonic server-side).  pilint: disable=deadline-clock
        {qos.DEADLINE_HEADER: str(time.time() - 1)})
    assert status == 504 and b"deadline exceeded" in body
    status, _, _ = http("POST", f"{base}/index/i/query", q)
    assert status == 200
    # The query is now response-cached — expiry must still 504:
    # deadline semantics cannot depend on cache state.
    status, body, _ = http(
        "POST", f"{base}/index/i/query", q,
        # Wire format is unix-epoch WALL clock (converted to
        # monotonic server-side).  pilint: disable=deadline-clock
        {qos.DEADLINE_HEADER: str(time.time() - 1)})
    assert status == 504 and b"deadline exceeded" in body


def test_bad_timeout_400(qserver):
    s, base = qserver
    q = b'Count(Bitmap(frame="f", rowID=1))'
    assert http("POST", f"{base}/index/i/query?timeout=bogus", q)[0] == 400
    assert http("POST", f"{base}/index/i/query?timeout=-1", q)[0] == 400
    # NaN/inf parse as floats but fail every expiry comparison — they
    # must 400, not run unbounded while wearing a deadline.
    assert http("POST", f"{base}/index/i/query?timeout=nan", q)[0] == 400
    assert http("POST", f"{base}/index/i/query?timeout=inf", q)[0] == 400
    assert http("POST", f"{base}/index/i/query", q,
                {qos.DEADLINE_HEADER: "nan"})[0] == 400


def test_metrics_export_qos_series(qserver):
    s, base = qserver
    # Mint a breaker entry so the per-peer state series exists.
    s.qos.breakers.record_failure("peer:1")
    body = http("GET", f"{base}/metrics")[1].decode()
    assert "pilosa_qos_shed_total" in body
    assert "pilosa_qos_queue_depth" in body
    assert 'pilosa_qos_breaker_state{peer="peer:1"} 0' in body
    out = json.loads(http("GET", f"{base}/debug/vars")[1])
    assert out["qos"]["enabled"] is True


def test_qos_disabled_is_nop(tmp_path):
    """Default config: nop tier — queries serve, /debug/qos answers
    disabled, /metrics has no qos series."""
    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    http("POST", f"{base}/index/i/frame/f", b"{}")
    assert s.qos is qos.NOP
    assert s.client.breakers is None
    status, body, _ = http("POST", f"{base}/index/i/query",
                           b'SetBit(frame="f", rowID=1, columnID=9)')
    assert status == 200
    assert json.loads(http("GET", f"{base}/debug/qos")[1]) == {
        "enabled": False}
    assert "pilosa_qos" not in http("GET", f"{base}/metrics")[1].decode()
    s.close()


def test_oversized_body_413(tmp_path):
    s = Server(str(tmp_path / "data"), bind="localhost:0",
               max_body_size=1024).open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    import http.client as hc

    host, port = s.host.rsplit(":", 1)
    # Raw socket: send headers declaring an oversized body, read the
    # refusal WITHOUT sending the body (the server must answer from
    # the Content-Length alone, never buffering).
    conn = hc.HTTPConnection(host, int(port), timeout=10)
    conn.putrequest("POST", "/index/i/query")
    conn.putheader("Content-Length", str(1 << 20))
    conn.putheader("Content-Type", "application/json")
    conn.endheaders()
    resp = conn.getresponse()
    assert resp.status == 413
    assert b"too large" in resp.read()
    conn.close()
    # At the limit: accepted.
    status, _, _ = http("POST", f"{base}/index/i/query", b" " * 100)
    assert status == 400  # parsed (empty query), not 413
    # Garbage Content-Length: 400, not a dropped connection.
    conn = hc.HTTPConnection(host, int(port), timeout=10)
    conn.putrequest("POST", "/index/i/query")
    conn.putheader("Content-Length", "banana")
    conn.endheaders()
    assert conn.getresponse().status == 400
    conn.close()
    # Fragment restore is exempt from the cap (backup tars are big);
    # an oversized declared body reaches the handler (and 400s on the
    # garbage payload, not 413).
    status, body, _ = http("POST",
                           f"{base}/fragment/data?index=i&frame=f",
                           b"x" * 4096)
    assert status != 413
    s.close()
    # 0 disables the limit entirely (docs/configuration.md contract).
    from pilosa_tpu.config import Config

    cfg = Config()
    cfg.max_body_size = 0
    cfg.validate()


def test_negative_content_length_400(tmp_path):
    """Content-Length: -1 must 400, never reach rfile.read(-1) (an
    unbounded until-EOF buffer past the 413 gate)."""
    import http.client as hc

    s = Server(str(tmp_path / "data"), bind="localhost:0",
               max_body_size=1024).open()
    host, port = s.host.rsplit(":", 1)
    conn = hc.HTTPConnection(host, int(port), timeout=10)
    conn.putrequest("POST", "/index/i/query")
    conn.putheader("Content-Length", "-1")
    conn.endheaders()
    assert conn.getresponse().status == 400
    conn.close()
    s.close()


def test_input_definition_malformed_frame_400(tmp_path):
    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    status, body, _ = http(
        "POST", f"{base}/index/i/input-definition/x",
        json.dumps({"frames": [{}],
                    "fields": [{"name": "columnID",
                                "primaryKey": True}]}).encode())
    assert status == 400 and b"missing field: name" in body
    s.close()


def test_keyerror_is_500_not_400(tmp_path):
    """A genuine handler bug (internal KeyError) must surface as 500;
    a missing request field is explicit 400 validation."""
    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    http("POST", f"{base}/index/i/frame/f", b"{}")
    # Missing required fields in the body -> explicit 400.
    status, body, _ = http("POST", f"{base}/import",
                           json.dumps({"frame": "f"}).encode())
    assert status == 400 and b"missing field: index" in body
    status, body, _ = http("POST", f"{base}/import-value",
                           json.dumps({"index": "i", "frame": "f"}).encode())
    assert status == 400 and b"missing field" in body
    # attr-diff blocks missing id/checksum: caller's 400 too.
    status, body, _ = http("POST", f"{base}/index/i/attr/diff",
                           json.dumps({"blocks": [{}]}).encode())
    assert status == 400 and b"missing field: id" in body
    status, body, _ = http("POST", f"{base}/index/i/frame/f/attr/diff",
                           json.dumps({"blocks": [{"id": 1}]}).encode())
    assert status == 400 and b"missing field: checksum" in body
    # An internal bug raising KeyError -> 500, not the caller's fault.
    def buggy(params, qp, body, headers):
        raise KeyError("internal-dict-key")
    s.handler.get_version = buggy
    s.handler.routes = s.handler._build_routes()
    status, body, _ = http("GET", f"{base}/version")
    assert status == 500
    s.close()


# -------------------------------------------------------- cluster

def test_deadline_expiry_mid_fanout_504_within_budget(tmp_path):
    """2-node cluster, one node stalls: the coordinator must return
    504 within the request budget — not after the flat 30 s internal
    client timeout."""
    from pilosa_tpu import SLICE_WIDTH

    ports = free_ports(2)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    qcfg = {"enabled": True}
    release = threading.Event()
    servers = [
        Server(str(tmp_path / f"n{i}"), bind=hosts[i], cluster_hosts=hosts,
               replica_n=1, anti_entropy_interval=0, polling_interval=0,
               qos=qcfg).open()
        for i in range(2)
    ]
    try:
        base = f"http://{servers[0].host}"
        http("POST", f"{base}/index/i", b"{}")
        http("POST", f"{base}/index/i/frame/f", b"{}")
        # Bits across enough slices that both nodes own some.
        bits = "".join(
            f'SetBit(frame="f", rowID=1, columnID={c * SLICE_WIDTH})'
            for c in range(8))
        status, _, _ = http("POST", f"{base}/index/i/query", bits.encode())
        assert status == 200

        for s in servers[1:]:
            orig = s.executor.execute

            def stalled(*a, _orig=orig, **kw):
                release.wait(20)   # longer than the budget, < test timeout
                return _orig(*a, **kw)

            s.executor.execute = stalled

        t0 = time.perf_counter()
        status, body, _ = http(
            "POST", f"{base}/index/i/query?timeout=1.5",
            b'Count(Bitmap(frame="f", rowID=1))')
        elapsed = time.perf_counter() - t0
        release.set()
        assert status == 504, body
        assert b"deadline exceeded" in body
        # Well within the budget's order of magnitude — NOT the flat
        # 30 s client timeout.
        assert elapsed < 10
    finally:
        release.set()
        for s in servers:
            s.close()


def test_breaker_opens_on_dead_peer_and_fails_fast(tmp_path):
    """Repeated transport failures to a dead peer open its breaker;
    the next call fails immediately (no dial), and the executor's
    up-front routing skips the dead host when replicas cover it."""
    from pilosa_tpu.cluster.client import ClientError, InternalClient
    from pilosa_tpu.cluster.cluster import Cluster, Node

    (dead_port,) = free_ports(1)
    dead = Node(f"127.0.0.1:{dead_port}")
    brk = qos.PeerBreakers(threshold=3, cooldown=60.0)
    client = InternalClient(timeout=2, breakers=brk)
    for _ in range(3):
        with pytest.raises(ClientError):
            client._do("GET", f"http://{dead.host}/id")
    assert brk.is_open(dead.host)
    t0 = time.perf_counter()
    with pytest.raises(ClientError) as ei:
        client._do("GET", f"http://{dead.host}/id")
    assert ei.value.breaker_open
    assert time.perf_counter() - t0 < 0.1   # no dial, instant refusal
    # Probes bypass the breaker (the recovery path still dials).
    assert client.probe(dead, timeout=1) is False
    # Routing: healthy_nodes drops the open-breaker peer.
    cluster = Cluster(nodes=[Node("up:1"), dead])
    cluster.breakers = brk
    assert cluster.healthy_nodes() == [Node("up:1")]
    assert cluster.status()["breakerOpen"] == [dead.host]
    client.close()


def test_budget_timeout_does_not_open_breaker():
    """A deadline-bounded timeout proves the budget spent, not the
    peer dead: it must not feed the breaker. A health-timeout (the
    configured client timeout, no deadline) still does."""
    import socket as sk

    from pilosa_tpu.cluster.client import ClientError, InternalClient
    from pilosa_tpu.cluster.cluster import Node

    srv = sk.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)   # accepts connections, never answers
    host = f"127.0.0.1:{srv.getsockname()[1]}"
    node = Node(host)
    try:
        brk = qos.PeerBreakers(threshold=1, cooldown=60.0)
        client = InternalClient(timeout=30, breakers=brk)
        with pytest.raises(qos.DeadlineExceeded):
            client.execute_query(node, "i", 'Count(Bitmap(rowID=1))',
                                 remote=True,
                                 deadline=time.monotonic() + 0.2)
        assert not brk.is_open(host)    # budget timeout: no breaker
        client.close()
        client2 = InternalClient(timeout=0.2, breakers=brk)
        with pytest.raises(ClientError):
            client2.execute_query(node, "i", 'Count(Bitmap(rowID=1))',
                                  remote=True)
        assert brk.is_open(host)        # health timeout: opens
        client2.close()
    finally:
        srv.close()


def test_breaker_half_open_recovery(tmp_path):
    """After the cooldown one probe goes through; a success closes the
    breaker and normal traffic resumes."""
    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    try:
        from pilosa_tpu.cluster.client import InternalClient
        from pilosa_tpu.cluster.cluster import Node

        brk = qos.PeerBreakers(threshold=1, cooldown=0.05)
        client = InternalClient(timeout=2, breakers=brk)
        node = Node(s.host)
        brk.record_failure(s.host)          # open immediately
        assert brk.is_open(s.host)
        time.sleep(0.06)                    # cooldown elapses
        status, _, _ = client._do("GET", f"http://{s.host}/id")
        assert status == 200                # half-open probe succeeded
        assert not brk.is_open(s.host)
        assert brk.snapshot()[s.host]["state"] == "closed"
        client.close()
    finally:
        s.close()


def test_internal_priority_bypasses_saturated_gate(tmp_path):
    """A remote (internal fan-out) query admits even when the gate is
    saturated with user traffic — stamped by the internal client."""
    s = Server(str(tmp_path / "data"), bind="localhost:0",
               qos={"enabled": True, "max-concurrent": 1,
                    "queue-length": 0, "queue-timeout": 0.2}).open()
    base = f"http://{s.host}"
    http("POST", f"{base}/index/i", b"{}")
    http("POST", f"{base}/index/i/frame/f", b"{}")
    http("POST", f"{base}/index/i/query",
         b'SetBit(frame="f", rowID=1, columnID=2)')
    release = threading.Event()
    in_handler = threading.Event()
    orig = s.executor.execute
    stalled_once = threading.Event()

    def slow_execute(index, query, **kw):
        # Only the FIRST query stalls (it occupies the gate's one
        # slot); the internal-priority query must run through.
        if not stalled_once.is_set():
            stalled_once.set()
            in_handler.set()
            release.wait(10)
        return orig(index, query, **kw)

    s.executor.execute = slow_execute
    t = threading.Thread(target=http, args=(
        "POST", f"{base}/index/i/query",
        b'Count(Bitmap(frame="f", rowID=1))'))
    t.start()
    assert in_handler.wait(10)
    # user-class overflow sheds...
    assert http("POST", f"{base}/index/i/query",
                b'Count(Bitmap(frame="f", rowID=1))')[0] == 503
    # ...but the internal class admits.
    status, _, _ = http("POST", f"{base}/index/i/query",
                        b'Count(Bitmap(frame="f", rowID=1))',
                        {qos.PRIORITY_HEADER: "internal"})
    assert status == 200
    release.set()
    t.join(timeout=10)
    s.executor.execute = orig
    s.close()
