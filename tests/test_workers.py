"""Multi-process serving: worker frontends, plan relay, worker-local
read execution with epoch-driven replica refresh (server/workers.py,
server/worker.py, server/worker_exec.py; ref: goroutine-per-conn
serving, server.go:205-217).

The deterministic tests bind a LONE worker to its own port (no
SO_REUSEPORT roulette): every request provably crosses the worker.
"""
import http.client
import json
import os
import socket
import subprocess
import sys
import time
import uuid

import pytest

from pilosa_tpu.server.server import Server
from pilosa_tpu.server.workers import PlanServer


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _post(conn, path, body):
    conn.request("POST", path, body=body.encode())
    r = conn.getresponse()
    data = r.read()
    return r.status, dict(r.getheaders()), data


def _wait_listening(port, timeout=30):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            c = socket.create_connection(("127.0.0.1", port), timeout=1)
            c.close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"worker on :{port} never came up")


def _spawn_worker(port, sock_path, extra=(), env_extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if "--exec-reads" in extra:
        env["PILOSA_TPU_READ_ONLY"] = "1"  # as WorkerPool does
    env.update(dict(env_extra))
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.server.worker",
         "--bind", f"127.0.0.1:{port}", "--socket", sock_path,
         *extra], env=env)
    _wait_listening(port)
    return proc


@pytest.fixture
def master(tmp_path):
    server = Server(str(tmp_path / "data"), bind="127.0.0.1:0")
    server.open()
    yield server
    server.close()


def test_worker_relays_all_routes(master, tmp_path):
    """A relay-only worker forwards every verb/route verbatim and the
    master's responses come back byte-identical."""
    sock = f"/tmp/pilosa_test_{uuid.uuid4().hex[:8]}.sock"
    plan = PlanServer(master.handler.dispatch, sock).open()
    port = _free_port()
    proc = _spawn_worker(port, sock)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        st, _, _ = _post(conn, "/index/i", "{}")
        assert st == 200
        st, _, _ = _post(conn, "/index/i/frame/f", "{}")
        assert st == 200
        for col in (1, 2, 3):
            st, _, body = _post(
                conn, "/index/i/query",
                f'SetBit(frame="f", rowID=7, columnID={col})')
            assert st == 200 and json.loads(body)["results"] == [True]
        st, hdrs, body = _post(conn, "/index/i/query",
                               'Count(Bitmap(frame="f", rowID=7))')
        assert st == 200 and json.loads(body)["results"] == [3]
        assert "X-Pilosa-Served-By" not in hdrs  # relay, not local exec
        # Non-query routes relay too (schema via worker == via master).
        conn.request("GET", "/schema")
        r = conn.getresponse()
        via_worker = r.read()
        assert r.status == 200
        assert json.loads(via_worker)["indexes"][0]["name"] == "i"
        # Unknown route → master's 404 through the relay.
        conn.request("GET", "/definitely-not-a-route")
        r = conn.getresponse()
        r.read()
        assert r.status == 404
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        plan.close()


def test_worker_exec_serves_reads_locally(master, tmp_path):
    """Exec-reads worker: scalar read trees answer from the worker's
    replica (header-tagged), writes relay to the master, and the
    published epoch makes the SAME connection see its own writes."""
    from pilosa_tpu.storage import fragment as fragment_mod

    epoch_path = os.path.join(master.data_dir, ".mutation_epoch")
    fragment_mod.publish_epochs(epoch_path)
    sock = f"/tmp/pilosa_test_{uuid.uuid4().hex[:8]}.sock"
    plan = PlanServer(master.handler.dispatch, sock).open()

    # Seed BEFORE the worker starts (its replica opens at spawn).
    idx = master.holder.create_index("i")
    idx.create_frame("f")
    idx.frame("f").import_bits([1, 1, 1], [10, 20, 30])

    port = _free_port()
    # Pin the cost model to 'local': this test proves the replica-
    # refresh SEMANTICS deterministically; the model's own choices are
    # covered by the cost-model tests below.
    proc = _spawn_worker(port, sock,
                         extra=["--data-dir", master.data_dir,
                                "--exec-reads"],
                         env_extra=[("PILOSA_TPU_WORKER_PATH", "local")])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        st, hdrs, body = _post(conn, "/index/i/query",
                               'Count(Bitmap(frame="f", rowID=1))')
        assert st == 200 and json.loads(body)["results"] == [3]
        assert hdrs.get("X-Pilosa-Served-By") == "worker"

        # A write on the same connection relays to the master...
        st, hdrs, body = _post(conn, "/index/i/query",
                               'SetBit(frame="f", rowID=1, columnID=40)')
        assert st == 200 and json.loads(body)["results"] == [True]
        assert "X-Pilosa-Served-By" not in hdrs
        # ...and the next read sees it — served locally once the
        # worker's throttled refresh runs (stale windows RELAY, so the
        # value is correct either way; retry until the local path
        # proves the refresh happened).
        deadline = time.monotonic() + 15
        attempt = 0
        while True:
            # Unique body per retry: an identical repeat would be
            # served from the response CACHE ("worker-cache") and
            # never prove the replica refresh happened.
            attempt += 1
            st, hdrs, body = _post(
                conn, "/index/i/query",
                'Count(Bitmap(frame="f", rowID=1))' + " " * attempt)
            assert st == 200 and json.loads(body)["results"] == [4]
            if hdrs.get("X-Pilosa-Served-By") == "worker":
                break
            assert time.monotonic() < deadline, "refresh never caught up"
            time.sleep(0.1)

        # TopN relays (rank caches are master-owned)...
        st, hdrs, body = _post(conn, "/index/i/query",
                               'TopN(frame="f", n=1)')
        assert st == 200
        assert "X-Pilosa-Served-By" not in hdrs
        # ...as do Bitmap-rooted trees (attr-bearing responses).
        st, hdrs, body = _post(conn, "/index/i/query",
                               'Bitmap(frame="f", rowID=1)')
        assert st == 200
        assert "X-Pilosa-Served-By" not in hdrs
        assert json.loads(body)["results"][0]["bits"] == [10, 20, 30, 40]

        # Schema DDL (new frame) + write + read through the epoch.
        st, _, _ = _post(conn, "/index/i/frame/g", "{}")
        assert st == 200
        st, _, _ = _post(conn, "/index/i/query",
                         'SetBit(frame="g", rowID=2, columnID=5)')
        assert st == 200
        deadline = time.monotonic() + 15
        attempt = 0
        while True:
            attempt += 1  # unique body: dodge the response cache
            st, hdrs, body = _post(
                conn, "/index/i/query",
                'Count(Bitmap(frame="g", rowID=2))' + " " * attempt)
            assert st == 200 and json.loads(body)["results"] == [1]
            if hdrs.get("X-Pilosa-Served-By") == "worker":
                break
            assert time.monotonic() < deadline, "refresh never caught up"
            time.sleep(0.1)
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        plan.close()


def test_worker_response_cache_replays_and_invalidates(master, tmp_path):
    """The worker's epoch-validated response cache: identical read
    queries replay from the worker (tagged header) without a master
    round trip; a write moves the published epoch and the next read
    re-executes; write bodies are never cached."""
    from pilosa_tpu.storage import fragment as fragment_mod

    fragment_mod.publish_epochs(
        os.path.join(master.data_dir, ".mutation_epoch"))
    sock = f"/tmp/pilosa_test_{uuid.uuid4().hex[:8]}.sock"
    plan = PlanServer(master.handler.dispatch, sock).open()
    idx = master.holder.create_index("i")
    idx.create_frame("f")
    idx.frame("f").import_bits([1, 1], [10, 20])
    port = _free_port()
    # Relay-only worker + cache (no --exec-reads): the TPU-shaped mode.
    proc = _spawn_worker(port, sock, extra=["--data-dir",
                                            master.data_dir])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        q = 'Count(Bitmap(frame="f", rowID=1))'
        st, hdrs, body = _post(conn, "/index/i/query", q)
        assert st == 200 and json.loads(body)["results"] == [2]
        assert "X-Pilosa-Served-By" not in hdrs  # miss: relayed
        st, hdrs, body = _post(conn, "/index/i/query", q)
        assert st == 200 and json.loads(body)["results"] == [2]
        assert hdrs.get("X-Pilosa-Served-By") == "worker-cache"
        # Write (relayed, never cached) → epoch moved → next read is a
        # recomputation with the new value, then cached again.
        st, hdrs, _ = _post(conn, "/index/i/query",
                            'SetBit(frame="f", rowID=1, columnID=30)')
        assert st == 200 and "X-Pilosa-Served-By" not in hdrs
        st, hdrs, body = _post(conn, "/index/i/query", q)
        assert st == 200 and json.loads(body)["results"] == [3]
        assert "X-Pilosa-Served-By" not in hdrs
        st, hdrs, body = _post(conn, "/index/i/query", q)
        assert json.loads(body)["results"] == [3]
        assert hdrs.get("X-Pilosa-Served-By") == "worker-cache"
        # Repeating the SAME SetBit must NOT replay: second application
        # reports False (the bit exists now).
        st, _, body = _post(conn, "/index/i/query",
                            'SetBit(frame="f", rowID=1, columnID=30)')
        assert json.loads(body)["results"] == [False]
        # Query-string params (list-valued in parse_qs) must key the
        # cache, not crash it — and distinct params are distinct keys.
        for _ in range(2):
            st, hdrs, body = _post(conn, "/index/i/query?slices=0", q)
            assert st == 200 and json.loads(body)["results"] == [3], body
        assert hdrs.get("X-Pilosa-Served-By") == "worker-cache"
        # Worker-local observability route.
        conn.request("GET", "/debug/worker")
        r = conn.getresponse()
        dbg = json.loads(r.read())
        assert r.status == 200 and dbg["mode"] == "relay"
        assert dbg["cache"]["hits"] >= 2 and dbg["cache"]["entries"] >= 1
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        plan.close()


def test_multinode_cluster_workers_cache_cold_never_stale(tmp_path):
    """PR 5: on a multi-node cluster, worker-local EXECUTION stays
    gated off (the replica executor has no cluster fan-out), but the
    worker response cache now runs, validated against the published
    (local total, cluster epoch version) pair — and a version of 0
    (no confirmed peer visibility yet) means COLD: correct results via
    relay, never a stale replay."""
    from pilosa_tpu.testing import free_ports

    ports = free_ports(2)
    hosts = [f"localhost:{p}" for p in ports]
    servers = [Server(str(tmp_path / f"n{i}"), bind=hosts[i],
                      cluster_hosts=hosts, replica_n=2,
                      anti_entropy_interval=0, polling_interval=0,
                      workers=1).open()
               for i in range(2)]
    try:
        assert servers[0].worker_pool is not None
        # Replica data files + published epochs ride along for the
        # cache; exec-reads stays single-node-only.
        assert servers[0].worker_pool.data_dir is not None
        assert servers[0].worker_pool.exec_reads is False
        assert servers[0].worker_pool.cluster_epochs is True
        host, port = servers[0].host.rsplit(":", 1)
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        assert _post(conn, "/index/i", "{}")[0] == 200
        assert _post(conn, "/index/i/frame/f", "{}")[0] == 200
        _post(conn, "/index/i/query",
              'SetBit(frame="f", rowID=1, columnID=3)')
        for _ in range(3):
            st, hdrs, body = _post(conn, "/index/i/query",
                                   'Count(Bitmap(frame="f", rowID=1))')
            assert st == 200 and json.loads(body)["results"] == [1]
        # A further write must be visible on the very next read —
        # whatever tier (worker cache, master cache, relay) answered.
        _post(conn, "/index/i/query",
              'SetBit(frame="f", rowID=1, columnID=99)')
        st, hdrs, body = _post(conn, "/index/i/query",
                               'Count(Bitmap(frame="f", rowID=1))')
        assert st == 200 and json.loads(body)["results"] == [2]
    finally:
        for s in servers:
            s.close()


def test_server_spawns_and_reaps_workers(tmp_path):
    """Server(workers=N) forms the REUSEPORT group; every connection —
    whoever lands it — answers correctly; close() reaps the pool."""
    server = Server(str(tmp_path / "data"), bind="127.0.0.1:0", workers=2)
    os.environ.pop("PILOSA_TPU_WORKER_EXEC", None)
    server.open()
    try:
        port = int(server.host.rsplit(":", 1)[1])
        deadline = time.monotonic() + 60
        while server.worker_pool.alive() < 2 and time.monotonic() < deadline:
            time.sleep(0.2)
        assert server.worker_pool.alive() == 2
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        assert _post(conn, "/index/i", "{}")[0] == 200
        assert _post(conn, "/index/i/frame/f", "{}")[0] == 200
        assert _post(conn, "/index/i/query",
                     'SetBit(frame="f", rowID=1, columnID=9)')[0] == 200
        # Fresh connections spread across the group; all must agree.
        for _ in range(10):
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            st, _, body = _post(c, "/index/i/query",
                                'Count(Bitmap(frame="f", rowID=1))')
            assert st == 200 and json.loads(body)["results"] == [1]
            c.close()
    finally:
        server.close()
    assert server.worker_pool.alive() == 0


# ---------------------------------------------------------------- codec

def test_frame_codec_roundtrip():
    """The relay codec carries exactly the shapes the relay uses:
    request 5-tuples (dict query params with list values, bytes
    bodies) and 3/4-tuple responses."""
    from pilosa_tpu.server.workers import pack, unpack

    frames = [
        ("POST", "/index/i/query", {"shards": ["0", "3"]},
         b'Count(Bitmap(frame="f", rowID=1))', {"Accept": "app/json"}),
        (200, "application/json", b'{"results": [1]}'),
        (200, "application/json", b"x" * 4096,
         {"X-Pilosa-Served-By": "worker"}),
        ("GET", "/status", None, b"", {}),
        (None, True, False, -1, 2 ** 62, "", b"", [], (), {}),
        {"nested": [{"deep": (1, "two", b"three")}]},
    ]
    for f in frames:
        assert unpack(pack(f)) == f


def test_frame_codec_rejects_malformed():
    """Truncated / oversized / garbage input raises FrameError — never
    executes anything, never returns half an object."""
    from pilosa_tpu.server.workers import FrameError, pack, unpack

    good = pack(("POST", "/q", None, b"body", {"H": "v"}))
    for i in range(1, len(good)):
        with pytest.raises(FrameError):
            unpack(good[:i])           # every truncation point
    with pytest.raises(FrameError):
        unpack(good + b"\x00")         # trailing bytes
    with pytest.raises(FrameError):
        unpack(b"Z")                   # unknown tag
    with pytest.raises(FrameError):
        unpack(b"")                    # empty
    with pytest.raises(FrameError):
        unpack(b"L\xff\xff\xff\xff")   # count exceeds frame
    with pytest.raises(FrameError):
        unpack(b"D\xff\xff\xff\x7f")   # dict count exceeds frame
    with pytest.raises(FrameError):
        unpack(b"S\x04\x00\x00\x00\xff\xfe\xfd\xfc")  # bad utf-8
    deep = pack(b"x")
    for _ in range(40):                # nesting past _MAX_DEPTH
        deep = b"L\x01\x00\x00\x00" + deep
    with pytest.raises(FrameError):
        unpack(deep)
    # A dict key that is hashable by TAG but not by content (tuple
    # wrapping a list) must raise FrameError, not TypeError.
    bad_key = pack({"k": 1}).replace(
        b"S\x01\x00\x00\x00k", b"U\x01\x00\x00\x00L\x00\x00\x00\x00")
    with pytest.raises(FrameError):
        unpack(bad_key)


def test_frame_codec_random_fuzz():
    """Random bytes must either decode to a plain value or raise
    FrameError — no other exception type, no hang. Seeded: the test is
    deterministic."""
    import random

    from pilosa_tpu.server.workers import FrameError, unpack

    rng = random.Random(0xF0A7)
    tags = b"NTFISBLUD"
    for trial in range(3000):
        n = rng.randrange(0, 24)
        raw = bytes(rng.randrange(256) for _ in range(n))
        if trial % 3 == 0 and raw:  # bias towards valid-looking tags
            raw = bytes([tags[rng.randrange(len(tags))]]) + raw[1:]
        try:
            unpack(raw)
        except FrameError:
            pass


def test_workers_module_has_no_pickle():
    """The relay transport must stay a closed data codec (advice r4:
    pickle.loads of attacker frames = code execution)."""
    import pilosa_tpu.server.worker as worker_mod
    import pilosa_tpu.server.workers as workers_mod

    for mod in (workers_mod, worker_mod):
        with open(mod.__file__) as f:
            src = f.read()
        assert "import pickle" not in src
        assert "pickle." not in src


@pytest.fixture
def master_with_plan(tmp_path):
    """A master that actually opens the plan socket (workers=1)."""
    server = Server(str(tmp_path / "data"), bind="127.0.0.1:0", workers=1)
    server.open()
    yield server
    server.close()


def test_plan_server_survives_garbage_frames(master_with_plan):
    """Garbage on the plan socket drops THAT connection; the server
    keeps answering well-formed frames from others."""
    from pilosa_tpu.server.workers import read_frame, write_frame

    sock_path = master_with_plan.plan_server.sock_path
    bad = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    bad.connect(sock_path)
    bad.sendall(b"\x10\x00\x00\x00" + b"\xde\xad\xbe\xef" * 4)
    # The server must close the poisoned connection.
    bad.settimeout(10)
    assert bad.recv(1) == b""
    bad.close()

    good = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    good.connect(sock_path)
    try:
        write_frame(good, ("GET", "/status", None, b"", {}))
        resp = read_frame(good)
        assert resp[0] == 200
    finally:
        good.close()


def test_plan_socket_lives_in_private_dir(master_with_plan):
    """Advice r4 (medium): the plan socket must sit inside a
    fresh 0700 directory, not at a predictable world-writable path."""
    import stat

    sock_path = master_with_plan.plan_server.sock_path
    d = os.path.dirname(sock_path)
    assert stat.S_IMODE(os.stat(d).st_mode) == 0o700
    assert stat.S_IMODE(os.stat(sock_path).st_mode) == 0o600


def test_write_markers_cover_write_calls():
    """Every pql.ast.WRITE_CALLS entry must trip the response cache's
    never-cache gate (advice r4: a future write call must not be
    silently cached and replayed)."""
    from pilosa_tpu.pql.ast import WRITE_CALLS
    from pilosa_tpu.server.worker import ResponseCache

    for name in WRITE_CALLS:
        body = f'{name}(frame="f", rowID=1, columnID=2)'.encode()
        assert any(m in body for m in ResponseCache._WRITE_MARKERS), name


# ----------------------------------------------------------- cost model

def test_cost_model_wide_relays_narrow_serves_locally():
    """The deployment asymmetry the model exists for (VERDICT r4 #3):
    the master owns a device that crushes wide-window scans, the
    worker's CPU wins narrow/cached reads. Feed both arms real-ish
    samples and assert the steady-state split — wide bucket relays,
    narrow bucket serves locally — with neither permanently parked
    (loser re-measured on schedule)."""
    from pilosa_tpu.server.worker_exec import RelayCostModel

    m = RelayCostModel()
    wide = ("Count(Bitmap)", 14)    # 2^14 slices: device territory
    narrow = ("Count(Bitmap)", 1)   # one slice: host-cache territory

    def drive(key, local_s, relay_s, n=200):
        served = {"local": 0, "relay": 0}
        for _ in range(n):
            c = m.choose(key)
            served[c] += 1
            m.record(key, "l" if c == "local" else "r",
                     local_s if c == "local" else relay_s)
        return served

    wide_served = drive(wide, local_s=2.0, relay_s=0.02)
    narrow_served = drive(narrow, local_s=0.001, relay_s=0.01)
    # Steady state: the winning arm dominates.
    assert wide_served["relay"] > 0.9 * sum(wide_served.values())
    assert narrow_served["local"] > 0.8 * sum(narrow_served.values())
    # Catastrophic local (100x) backs off the wide key's local probing.
    snap = m.snapshot()["keys"]
    assert snap["Count(Bitmap)/2^14slices"]["remeasureEvery"] > \
        RelayCostModel.REMEASURE_EVERY
    # Never-lose: the losing arm still holds a (recent) measurement on
    # both keys — neither path is permanently abandoned.
    assert snap["Count(Bitmap)/2^14slices"]["localMs"] is not None
    assert snap["Count(Bitmap)/2^1slices"]["relayMs"] is not None


def test_cost_model_recovers_when_master_slows():
    """Aged minima + loser re-measure: a key settled on relay must
    drift back to local once relay times degrade (e.g. master device
    lost, or master overloaded)."""
    from pilosa_tpu.server.worker_exec import RelayCostModel

    m = RelayCostModel()
    key = ("Count(Bitmap)", 4)
    for _ in range(60):  # settle on relay
        c = m.choose(key)
        m.record(key, "l" if c == "local" else "r",
                 0.05 if c == "local" else 0.002)
    late = {"local": 0, "relay": 0}
    for _ in range(600):  # relay now 10x worse than local
        c = m.choose(key)
        late[c] += 1
        m.record(key, "l" if c == "local" else "r",
                 0.005 if c == "local" else 0.05)
    # The model must have flipped: local dominates the late window.
    assert late["local"] > late["relay"], late


def test_cost_model_integration_exposed_in_debug(master, tmp_path):
    """Unpinned exec-reads worker on a CPU master: after exploration
    the model (a) keeps answering correctly on both arms and (b)
    exposes its choices + arm minima via /debug/worker."""
    from pilosa_tpu.storage import fragment as fragment_mod

    epoch_path = os.path.join(master.data_dir, ".mutation_epoch")
    fragment_mod.publish_epochs(epoch_path)
    sock = str(tmp_path / "plan.sock")
    plan = PlanServer(master.handler.dispatch, sock).open()
    idx = master.holder.create_index("i")
    idx.create_frame("f")
    idx.frame("f").import_bits([1, 1, 1], [10, 20, 30])

    port = _free_port()
    proc = _spawn_worker(port, sock,
                         extra=["--data-dir", master.data_dir,
                                "--exec-reads"],
                         env_extra=[("PILOSA_TPU_WORKER_CACHE", "0")])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        for i in range(24):
            # Unique texts, one shape: every request reaches the model
            # (cache disabled) and lands on the same (shape, bucket).
            st, hdrs, body = _post(
                conn, "/index/i/query",
                f'Count(Bitmap(frame="f", rowID=1))' + " " * i)
            assert st == 200 and json.loads(body)["results"] == [3]
        conn.request("GET", "/debug/worker")
        r = conn.getresponse()
        dbg = json.loads(r.read())
        cm = dbg["cost_model"]
        assert cm["forced"] is None
        assert cm["choices"]["local"] > 0
        assert cm["choices"]["relay_cost"] > 0
        (key_stats,) = cm["keys"].values()
        assert key_stats["localMs"] is not None
        assert key_stats["relayMs"] is not None
        assert key_stats["queries"] == 24
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        plan.close()
