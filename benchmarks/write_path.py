"""Write-path benchmark: SetBit op/sec + bulk import throughput.

The reference's only online benchmark tool is `pilosa bench set-bit`
(ref: ctl/bench.go:30-107), which POSTs N random SetBit PQL calls and
prints op/sec; its bulk path is `pilosa import` (ref: ctl/import.go,
fragment.go:1266 Fragment.Import). This harness measures our analogs:

  1. set-bit over HTTP      — N SetBit calls per request batch, like
                              `bench set-bit` (MaxWritesPerRequest=5000)
  2. import over HTTP       — protobuf ImportRequest → /import
  3. import direct          — Frame.import_bits (no HTTP), the
                              hot loop of ref fragment.go:1266
  4. CSV parse              — native C++ fast parser vs Python

Run: python benchmarks/write_path.py [--n 200000]
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.server import wireproto as wp  # noqa: E402


def http(method, url, body=None, ctype="application/json"):
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", ctype)
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, resp.read()


def bench_setbit_http(base, n, batch=5000, max_row=1000, max_col=1_000_000):
    rng = np.random.default_rng(0)
    rows = rng.integers(0, max_row, size=n)
    cols = rng.integers(0, max_col, size=n)
    t0 = time.perf_counter()
    for off in range(0, n, batch):
        q = "\n".join(
            f'SetBit(frame="f", rowID={r}, columnID={c})'
            for r, c in zip(rows[off:off + batch], cols[off:off + batch]))
        http("POST", f"{base}/index/i/query", q.encode(), "text/plain")
    return n / (time.perf_counter() - t0)


def bench_setfield_http(base, n, batch=5000, max_col=1_000_000):
    rng = np.random.default_rng(2)
    cols = rng.choice(max_col, size=min(n, max_col), replace=False)
    vals = rng.integers(0, 1001, size=len(cols))
    t0 = time.perf_counter()
    for off in range(0, len(cols), batch):
        q = "\n".join(
            f'SetFieldValue(frame="g", columnID={c}, v={v})'
            for c, v in zip(cols[off:off + batch], vals[off:off + batch]))
        http("POST", f"{base}/index/i/query", q.encode(), "text/plain")
    return len(cols) / (time.perf_counter() - t0)


def bench_read_after_write(base, cycles=30, max_col=1_000_000):
    """Mixed workload: one 2-bit write then one Count over the index's
    slices (2 at this dataset's shape) — the incremental stack-repair
    path (ms per write+read cycle, steady state)."""
    rng = np.random.default_rng(3)
    q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))')
    # Warm one full write+read cycle so the repair kernels' one-time
    # jit compiles stay out of the timed loop.
    c = int(rng.integers(0, max_col))
    http("POST", f"{base}/index/i/query",
         (f'SetBit(frame="f", rowID=1, columnID={c})\n'
          f'SetBit(frame="f", rowID=2, columnID={c})').encode(),
         "text/plain")
    http("POST", f"{base}/index/i/query", q.encode(), "text/plain")
    t0 = time.perf_counter()
    for _ in range(cycles):
        c = int(rng.integers(0, max_col))
        http("POST", f"{base}/index/i/query",
             (f'SetBit(frame="f", rowID=1, columnID={c})\n'
              f'SetBit(frame="f", rowID=2, columnID={c})').encode(),
             "text/plain")
        http("POST", f"{base}/index/i/query", q.encode(), "text/plain")
    return (time.perf_counter() - t0) / cycles * 1000


def bench_import_http(base, n, max_row=1000):
    rng = np.random.default_rng(1)
    rows = rng.integers(0, max_row, size=n, dtype=np.uint64)
    cols = rng.integers(0, SLICE_WIDTH, size=n, dtype=np.uint64)
    payload = wp.encode_import_request(
        "i", "f", 0, rows.tolist(), cols.tolist(), [])
    t0 = time.perf_counter()
    http("POST", f"{base}/import", payload, "application/x-protobuf")
    return n / (time.perf_counter() - t0)


def bench_import_direct(holder, n, max_row=1000):
    """Cold (first batch: row allocation + initial snapshot) and warm
    (steady-state re-import) throughput of the Frame.import_bits hot
    loop (ref: fragment.go:1266)."""
    rng = np.random.default_rng(2)
    rows = rng.integers(0, max_row, size=n, dtype=np.uint64)
    cols = rng.integers(SLICE_WIDTH, 2 * SLICE_WIDTH, size=n,
                        dtype=np.uint64)
    frame = holder.index("i").frame("f")
    t0 = time.perf_counter()
    frame.import_bits(rows, cols)
    cold = n / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    frame.import_bits(rows, cols)
    warm = n / (time.perf_counter() - t0)
    return cold, warm


def bench_csv_parse(n, max_row=1000):
    from pilosa_tpu import native
    rng = np.random.default_rng(3)
    rows = rng.integers(0, max_row, size=n)
    cols = rng.integers(0, SLICE_WIDTH, size=n)
    blob = "".join(f"{r},{c}\n" for r, c in zip(rows, cols)).encode()
    t0 = time.perf_counter()
    out = native.parse_csv(blob)
    dt = time.perf_counter() - t0
    assert out is not None and len(out) == n, "native parser unavailable"
    return n / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="pilosa-bench-")
    srv = Server(f"{tmp}/data", bind="localhost:0").open()
    try:
        base = f"http://{srv.host}"
        http("POST", f"{base}/index/i", b"{}")
        http("POST", f"{base}/index/i/frame/f", b"{}")
        http("POST", f"{base}/index/i/frame/g",
             json.dumps({"options": {
                 "rangeEnabled": True,
                 "fields": [{"name": "v", "type": "int",
                             "min": 0, "max": 1000}]}}).encode())

        cold, warm = bench_import_direct(srv.holder, args.n)
        out = {
            "setbit_http_ops": bench_setbit_http(base, min(args.n, 50_000)),
            "setfield_http_ops": bench_setfield_http(
                base, min(args.n, 50_000)),
            "import_http_bits": bench_import_http(base, args.n),
            "import_direct_cold_bits": cold,
            "import_direct_warm_bits": warm,
            "csv_parse_rows": bench_csv_parse(args.n),
        }
        raw = bench_read_after_write(base)
        for k, v in out.items():
            print(f"{k:22s} {v:12,.0f}/s")
        print(f"{'read_after_write_ms':22s} {raw:12.1f}")
        out["read_after_write_ms"] = raw
        print(json.dumps({k: round(v, 1) for k, v in out.items()}))
    finally:
        srv.close()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
