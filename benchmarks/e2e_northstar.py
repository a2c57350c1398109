"""North star through the REAL serving path: a billion-column sparse
index served over HTTP with explicit host- and device-memory caps.

benchmarks/count10b.py generates rows directly on device — no holder,
no fragments, no governor, no windowed batching. This benchmark
is the capability claim end-to-end ("billions of objects … real time",
docs/introduction.md:15-17): it builds a DISK-BACKED index spanning
>= 1 billion columns (954 slices of 2^20), evicts everything, then
serves Count(Intersect) and TopN over HTTP through the executor's
windowed batching, window-aware device stacks, container-granular lazy
reads, and the host-memory governor.

Env knobs (defaults chosen to finish on the CPU backend in minutes):
  NORTHSTAR_SLICES   — slice count (default 954 ≈ 1.0e9 columns)
  NORTHSTAR_SECONDS  — per-query-shape measure window (default 10)
  PILOSA_TPU_HOST_BYTES / PILOSA_TPU_STACK_BYTES — the caps under test
    (defaults here: 64 MB host, 256 MB device stacks)

Prints JSON lines: build stats, then q/s + resident bytes per shape.
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
BIND = "127.0.0.1:10141"


import http.client  # noqa: E402
import socket  # noqa: E402


class _NoDelayConn(http.client.HTTPConnection):
    """NODELAY inside connect() so http.client's silent auto-reconnect
    (after any server-side close) keeps the option — setting it only
    on first connect would quietly reintroduce the ~40 ms Nagle tax
    for the rest of the run."""

    def connect(self):
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


_conn = None


def post(path, data):
    """Keep-alive client with TCP_NODELAY — what real ecosystem
    clients (go-pilosa et al.) do; a fresh urllib connection per
    request measured connection setup, not serving."""
    global _conn
    if _conn is None:
        host, _, port = BIND.rpartition(":")
        _conn = _NoDelayConn(host, int(port), timeout=120)
    _conn.request("POST", path, body=data.encode())
    r = _conn.getresponse()
    body = r.read()
    if r.status != 200:
        raise RuntimeError(f"{path}: HTTP {r.status}: {body[:300]!r}")
    return json.loads(body)


def build(server):
    """Sparse clustered data: 3 rows per slice, bits clustered in the
    low columns of each slice (the common low-id clustering that
    window-aware stacks exploit), snapshotted to disk and evicted."""
    rng = np.random.default_rng(42)
    holder = server.holder
    idx = holder.create_index("ns")
    idx.create_frame("f")
    frame = idx.frame("f")
    t0 = time.perf_counter()
    file_bytes = 0
    for s in range(N_SLICES):
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
        "metric": "northstar_build_s", "value": round(build_s, 1),
        "unit": f"s ({N_SLICES} slices, {N_SLICES * SLICE_WIDTH / 1e9:.2f}B "
                f"columns, {file_bytes / 1e6:.1f} MB on disk)"}))


def measure(server, name, pql, check, label="warm repeated query"):
    gov = server.holder.governor
    out = post("/index/ns/query", pql)   # warm (compile + stacks)
    assert check(out["results"][0]), out
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < SECONDS:
        out = post("/index/ns/query", pql)
        n += 1
    dt = time.perf_counter() - t0
    assert check(out["results"][0]), out
    print(json.dumps({
        "metric": f"northstar_{name}_qps", "value": round(n / dt, 1),
        # "warm repeated": the SAME query loops — the dashboard
        # pattern — so epoch-validated memos legitimately serve it;
        # any write to the index invalidates them. The cold variant
        # disables result memos and re-executes per query.
        "unit": (f"q/s over HTTP, {label} ({N_SLICES} "
                 f"slices; resident "
                 f"{(gov.resident_bytes() if gov else -1) / 1e6:.1f} MB "
                 f"host)")}))


def main():
    import jax

    d = tempfile.mkdtemp(prefix="northstar_")
    from pilosa_tpu.server.server import Server

    server = Server(os.path.join(d, "data"), bind=BIND)
    server.open()
    try:
        build(server)
        # Count(Intersect(row1, row2)): per slice, |row1 ∩ row2| varies
        # with the random draw — require a positive, stable value.
        first = post("/index/ns/query",
                     'Count(Intersect(Bitmap(frame="f", rowID=1), '
                     'Bitmap(frame="f", rowID=2)))')["results"][0]
        assert first > 0
        measure(server, "count_intersect",
                'Count(Intersect(Bitmap(frame="f", rowID=1), '
                'Bitmap(frame="f", rowID=2)))',
                lambda v: v == first)
        # COLD path: result memos off — every query re-executes the
        # full windowed batched pipeline (the ad-hoc query shape, vs
        # the warm dashboard shape above).
        server.executor._result_memo_off = True
        try:
            measure(server, "count_intersect_cold",
                    'Count(Intersect(Bitmap(frame="f", rowID=1), '
                    'Bitmap(frame="f", rowID=2)))',
                    lambda v: v == first,
                    label="cold: result memos off")
        finally:
            server.executor._result_memo_off = False
        measure(server, "topn",
                'TopN(frame="f", n=3)',
                lambda v: [p["id"] for p in v] == [1, 2, 3])
        print(json.dumps({
            "metric": "northstar_backend", "value": 1,
            "unit": jax.default_backend()}))
    finally:
        server.close()


if __name__ == "__main__":
    main()
