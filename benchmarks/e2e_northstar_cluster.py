"""Multi-node north star: the billion-column serving claim on a REAL
2-node replicated cluster (VERDICT r4 weak #4 — the 10B numbers were
single-node; executor.go:1444-1575's mapReduce is inherently the
multi-node path).

Two `Server`s with replica_n=2 over HTTP: every query lands on node A,
whose executor runs its primary slice subset locally (windowed batched
device stacks, discovery memos) and fans the rest to node B as a
remote subquery over the wire (protobuf data plane) — per query. Both
nodes hold identical replica data, built directly on each holder
(what a converged anti-entropy pass produces; the replicated write
path would serialize a 1B-column build through single SetBits).

Measured shapes mirror benchmarks/e2e_northstar.py: warm/cold
Count(Intersect) and warm/cold TopN. "Cold" disables epoch-validated
RESULT memos on BOTH nodes; the TopN discovery memo (a prelude-class
memo, like device stack caches) stays on, now valid on clusters
because each node memoizes only its own slice subset under its own
epoch (executor._topn_discovery_memoized).

Env knobs:
  NORTHSTAR_SLICES   — slice count (default 954 ≈ 1.0e9 columns)
  NORTHSTAR_SECONDS  — per-query-shape measure window (default 10)
  NORTHSTAR_NODES    — cluster size (default 2; replica_n stays 2)
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("PILOSA_TPU_HOST_BYTES", str(64 << 20))
os.environ.setdefault("PILOSA_TPU_STACK_BYTES", str(256 << 20))

import numpy as np  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

N_SLICES = int(os.environ.get("NORTHSTAR_SLICES", "954"))
SECONDS = float(os.environ.get("NORTHSTAR_SECONDS", "10"))
N_NODES = int(os.environ.get("NORTHSTAR_NODES", "2"))

import http.client  # noqa: E402
import socket  # noqa: E402


class _NoDelayConn(http.client.HTTPConnection):
    def connect(self):
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


_conn = None
_host = None


def post(path, data):
    global _conn
    if _conn is None:
        host, _, port = _host.rpartition(":")
        _conn = _NoDelayConn(host, int(port), timeout=300)
    _conn.request("POST", path, body=data.encode())
    r = _conn.getresponse()
    body = r.read()
    if r.status != 200:
        raise RuntimeError(f"{path}: HTTP {r.status}: {body[:300]!r}")
    return json.loads(body)


def build(servers):
    """Each node builds ONLY the slices it replicates (per the
    cluster's ownership function) — what a converged replica_n=2
    layout actually holds on disk. Content is seeded PER SLICE so the
    same slice is byte-identical on every replica regardless of which
    subset a node builds. Snapshotted and evicted, as
    e2e_northstar.py."""
    t0 = time.perf_counter()
    file_bytes = 0
    for server in servers:
        holder = server.holder
        # _if_not_exists: node A's DDL broadcast may have created the
        # schema on B before B's direct build reaches this line.
        idx = holder.create_index_if_not_exists("ns")
        idx.create_frame_if_not_exists("f")
        frame = idx.frame("f")
        for s in range(N_SLICES):
            if not any(n.host == server.host
                       for n in server.cluster.fragment_nodes("ns", s)):
                continue
            rng = np.random.default_rng(42 + s)
            base = s * SLICE_WIDTH
            rows, cols = [], []
            for rid, n in ((1, 300), (2, 200), (3, 100)):
                c = rng.choice(4000, size=n, replace=False)
                rows.extend([rid] * n)
                cols.extend((base + c).tolist())
            frame.import_bits(rows, cols)
            frag = holder.fragment("ns", "f", "standard", s)
            frag.snapshot()
            file_bytes += os.path.getsize(frag.path)
            frag.unload()
    build_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "northstar2_build_s", "value": round(build_s, 1),
        "unit": (f"s ({N_NODES} nodes replica_n=2 x {N_SLICES} slices, "
                 f"{N_SLICES * SLICE_WIDTH / 1e9:.2f}B columns, "
                 f"{file_bytes / 1e6:.1f} MB on disk across replicas)")}))


def measure(name, pql, check, label="warm repeated query", prefix=True):
    out = post("/index/ns/query", pql)   # warm (compile + stacks)
    assert check(out["results"][0]), out
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < SECONDS:
        out = post("/index/ns/query", pql)
        n += 1
    dt = time.perf_counter() - t0
    assert check(out["results"][0]), out
    qps = round(n / dt, 1)
    metric = f"northstar2_{name}_qps" if prefix else name
    print(json.dumps({
        "metric": metric, "value": qps,
        "unit": (f"q/s over HTTP, {N_NODES}-node replica_n=2, {label} "
                 f"({N_SLICES} slices)")}))
    return qps


def measure_cluster_warmth(servers):
    """PR 5 acceptance phase: the SAME cluster's repeat-query rate
    with every warm tier on (epoch-vector-validated response replay +
    result memos) vs the fully cold fan-out path (response cache
    detached, result memos off — every query re-executes the cluster
    map/reduce). Emits ``cluster_warm_qps`` / ``cluster_cold_qps`` and
    their ratio; the warm phase also asserts a nonzero replay hit
    rate so the number can never silently measure the cold path."""
    q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))')
    expect = post("/index/ns/query", q)["results"][0]
    check = lambda v: v == expect  # noqa: E731

    warm = measure("cluster_warm_qps", q, check,
                   label="warm: cluster response replay + memos",
                   prefix=False)
    cache = servers[0].handler._resp_cache
    assert cache is not None and cache.hits > 0, \
        "warm phase never replayed from the cluster response cache"

    saved = [s.handler._resp_cache for s in servers]
    for s in servers:
        s.handler._resp_cache = None
        s.executor._result_memo_off = True
    try:
        cold = measure("cluster_cold_qps", q, check,
                       label="cold: full fan-out, caches off",
                       prefix=False)
    finally:
        for s, c in zip(servers, saved):
            s.handler._resp_cache = c
            s.executor._result_memo_off = False
    print(json.dumps({
        "metric": "cluster_warm_over_cold", "value":
        round(warm / cold, 1) if cold else 0.0,
        "unit": (f"x (warm replay vs cold fan-out, {N_NODES}-node "
                 f"replica_n=2, {N_SLICES} slices; acceptance >= 3x)")}))


def main():
    import jax

    from pilosa_tpu.server.server import Server
    from pilosa_tpu.testing import free_ports

    global _host
    d = tempfile.mkdtemp(prefix="northstar2_")
    ports = free_ports(N_NODES)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    servers = [Server(os.path.join(d, f"n{i}"), bind=hosts[i],
                      cluster_hosts=hosts, replica_n=2,
                      anti_entropy_interval=0, polling_interval=0).open()
               for i in range(N_NODES)]
    _host = servers[0].host
    try:
        build(servers)
        first = post("/index/ns/query",
                     'Count(Intersect(Bitmap(frame="f", rowID=1), '
                     'Bitmap(frame="f", rowID=2)))')["results"][0]
        assert first > 0
        measure("count_intersect",
                'Count(Intersect(Bitmap(frame="f", rowID=1), '
                'Bitmap(frame="f", rowID=2)))',
                lambda v: v == first)
        for s in servers:
            s.executor._result_memo_off = True
        try:
            measure("count_intersect_cold",
                    'Count(Intersect(Bitmap(frame="f", rowID=1), '
                    'Bitmap(frame="f", rowID=2)))',
                    lambda v: v == first,
                    label="cold: result memos off both nodes")
            measure("topn_cold",
                    'TopN(frame="f", n=3)',
                    lambda v: [p["id"] for p in v] == [1, 2, 3],
                    label="cold: result memos off both nodes "
                          "(per-node discovery memos on)")
        finally:
            for s in servers:
                s.executor._result_memo_off = False
        measure("topn",
                'TopN(frame="f", n=3)',
                lambda v: [p["id"] for p in v] == [1, 2, 3])
        measure_cluster_warmth(servers)
        print(json.dumps({
            "metric": "northstar2_backend", "value": 1,
            "unit": jax.default_backend()}))
    finally:
        for s in servers:
            s.close()


if __name__ == "__main__":
    main()
