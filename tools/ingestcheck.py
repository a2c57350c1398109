"""Bulk-ingest smoke check (`make ingestcheck`).

Boots a real in-process server and proves the PR's three contracts:

1. **Bit-exact**: the same random dataset loaded through the legacy
   /import route and through POST /index/<i>/ingest produces
   identical fragment digests (plus a timestamped batch: every
   time-quantum view digest matches too).
2. **>=10x**: sustained bits-ingested/sec through the ingest route is
   at least 10x the legacy import path (both over HTTP, legacy at its
   max-writes-per-request batch cadence — the loop every serving
   milestone was loaded through).
3. **Back-pressure**: with a saturated QoS admission gate the route
   sheds with 503 + Retry-After at the ingest priority, and recovers.

Plus: containers land compressed (the ingested fragment reports
ARRAY/RUN blocks with ZERO conversions — no post-hoc churn).

Exit 0 = all pass; any failure exits 1 with a message.
"""
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.ingest import codec  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.server import wireproto as wp  # noqa: E402

FAILURES = []


def check(ok, msg):
    tag = "ok" if ok else "FAIL"
    print(f"  [{tag}] {msg}")
    if not ok:
        FAILURES.append(msg)


def http(method, url, body=None, ctype="application/json",
         headers=None, timeout=60):
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", ctype)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def load_legacy(base, index, frame, rows, cols, batch=5000):
    """The legacy loader: per-slice /import posts at the
    max-writes-per-request cadence."""
    slices = cols // SLICE_WIDTH
    order = np.argsort(slices, kind="stable")
    rows, cols, slices = rows[order], cols[order], slices[order]
    bounds = np.flatnonzero(np.diff(slices)) + 1
    t0 = time.perf_counter()
    for g in np.split(np.arange(len(rows)), bounds):
        if not len(g):
            continue
        s = int(slices[g[0]])
        for off in range(0, len(g), batch):
            sel = g[off:off + batch]
            body = wp.encode_import_request(
                index, frame, s, rows[sel].tolist(),
                cols[sel].tolist(), [])
            st, data, _ = http("POST", f"{base}/import", body,
                               "application/x-protobuf")
            assert st == 200, (st, data)
    return time.perf_counter() - t0


def load_ingest(base, index, frame, rows, cols, batch=1_000_000):
    t0 = time.perf_counter()
    for off in range(0, len(rows), batch):
        body = codec.encode_bits(frame, rows[off:off + batch],
                                 cols[off:off + batch])
        st, data, _ = http("POST", f"{base}/index/{index}/ingest",
                           body, codec.CONTENT_TYPE)
        assert st == 200, (st, data)
    return time.perf_counter() - t0


def total_count(base, index, frame, n_rows):
    q = "\n".join(f'Count(Bitmap(rowID={r}, frame="{frame}"))'
                  for r in range(n_rows)).encode()
    st, data, _ = http("POST", f"{base}/index/{index}/query", q,
                       "text/plain")
    assert st == 200, data
    return sum(json.loads(data)["results"])


def main():
    n = int(os.environ.get("INGESTCHECK_BITS", "250000"))
    n_rows = int(os.environ.get("INGESTCHECK_ROWS", "1024"))
    n_slices = 2
    tmp = tempfile.mkdtemp(prefix="ingestcheck-")
    srv = Server(os.path.join(tmp, "srv"), bind="localhost:0",
                 qos={"enabled": True, "max-concurrent": 8,
                      "queue-length": 16}).open()
    base = f"http://{srv.host}"
    try:
        rng = np.random.default_rng(7)
        # A representative bitmap-index shape: ~1k distinct rows
        # (attributes/terms) — where the legacy path's per-request
        # recount scan (O(touched rows x window) per 5000 bits) is the
        # documented write-path pathology the batch install removes.
        rows = rng.integers(0, n_rows, n).astype(np.uint64)
        cols = rng.integers(0, n_slices * SLICE_WIDTH,
                            n).astype(np.uint64)

        for idx in ("legacy", "fast", "wl", "wf"):
            http("POST", f"{base}/index/{idx}", b"{}")
            http("POST", f"{base}/index/{idx}/frame/f", b"{}")

        print(f"ingestcheck: {n} bits, {n_slices} slices, "
              f"{n_rows} rows")
        # Warm both paths' one-time costs (jit compiles, first-touch
        # code paths) out of the timed runs — into throwaway indexes
        # so the timed loads hit fresh fragments, like a real bulk
        # load.
        load_legacy(base, "wl", "f", rows[:30000], cols[:30000])
        load_ingest(base, "wf", "f", rows[:30000], cols[:30000])

        t_legacy = load_legacy(base, "legacy", "f", rows, cols)
        t_ingest = load_ingest(base, "fast", "f", rows, cols)
        bps_legacy = n / t_legacy
        bps_ingest = n / t_ingest
        speedup = bps_ingest / bps_legacy
        print(f"  legacy import: {bps_legacy:,.0f} bits/s "
              f"({t_legacy:.2f}s)")
        print(f"  ingest route:  {bps_ingest:,.0f} bits/s "
              f"({t_ingest:.2f}s)")
        check(speedup >= 10,
              f"ingest >= 10x legacy import (got {speedup:.1f}x)")

        # Bit-exact: identical sampled counts and identical per-slice
        # digests.
        c1 = total_count(base, "legacy", "f", 64)
        c2 = total_count(base, "fast", "f", 64)
        check(c1 == c2 and c1 > 0,
              f"bit-exact sampled counts (legacy={c1}, ingest={c2})")
        dig = []
        for idx in ("legacy", "fast"):
            d = {}
            for s in range(n_slices):
                st, data, _ = http(
                    "GET", f"{base}/fragment/digest?index={idx}"
                           f"&frame=f&view=standard&slice={s}")
                d[s] = json.loads(data).get("digest")
            dig.append(d)
        check(dig[0] == dig[1], "bit-exact fragment digests")

        # Time-quantum views through the batch path.
        http("POST", f"{base}/index/legacy/frame/t",
             json.dumps({"options": {"timeQuantum": "YMD"}}).encode())
        http("POST", f"{base}/index/fast/frame/t",
             json.dumps({"options": {"timeQuantum": "YMD"}}).encode())
        ts = (1_500_000_000
              + rng.integers(0, 3, 2000) * 86400).astype(np.int64)
        trows = rng.integers(0, 8, 2000).astype(np.uint64)
        tcols = rng.integers(0, SLICE_WIDTH, 2000).astype(np.uint64)
        body = wp.encode_import_request(
            "legacy", "t", 0, trows.tolist(), tcols.tolist(),
            ts.tolist())
        st, data, _ = http("POST", f"{base}/import", body,
                           "application/x-protobuf")
        assert st == 200, data
        st, data, _ = http(
            "POST", f"{base}/index/fast/ingest",
            codec.encode_bits("t", trows, tcols, ts),
            codec.CONTENT_TYPE)
        assert st == 200, data
        st, data, _ = http("GET",
                           f"{base}/index/legacy/frame/t/views")
        views_l = json.loads(data)["views"]
        st, data, _ = http("GET", f"{base}/index/fast/frame/t/views")
        views_f = json.loads(data)["views"]
        tq_ok = views_l == views_f and len(views_l) > 1
        for v in views_l:
            for s in range(1):
                st, d1, _ = http(
                    "GET", f"{base}/fragment/digest?index=legacy"
                           f"&frame=t&view={v}&slice={s}")
                st, d2, _ = http(
                    "GET", f"{base}/fragment/digest?index=fast"
                           f"&frame=t&view={v}&slice={s}")
                tq_ok = tq_ok and d1 == d2
        check(tq_ok, f"time-quantum views bit-exact "
                     f"({len(views_l)} views)")

        # Compressed landing: the ingested index reports compressed
        # blocks with zero conversions (no post-hoc churn).
        st, data, _ = http("GET", f"{base}/debug/memory")
        mem = json.loads(data)
        conv = mem.get("containerConversionsTotal", 0)
        st, data, _ = http("GET", f"{base}/debug/vars")
        seeded = json.loads(data)["ingest"]["containersSeeded"]
        n_seeded = sum(seeded.values())
        check(n_seeded > 0 and conv == 0,
              f"containers land compressed, zero conversions "
              f"(seeded={n_seeded}, conversions={conv})")

        # Back-pressure: saturate the gate; ingest must shed 503 with
        # Retry-After, then recover once the gate drains.
        release = threading.Event()
        entered = []
        real = srv.ingest.ingest_bits

        def slow(*a, **kw):
            entered.append(1)
            release.wait(20)
            return real(*a, **kw)

        srv.ingest.ingest_bits = slow
        threads = []
        body = codec.encode_bits("f", [1], [1])
        results = []

        def post():
            results.append(http(
                "POST", f"{base}/index/fast/ingest", body,
                codec.CONTENT_TYPE))

        # 8 slots + 16 queue = 24; the 30th must shed fast.
        for _ in range(30):
            t = threading.Thread(target=post)
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 10
        shed = None
        while time.monotonic() < deadline and shed is None:
            done = [r for r in results if r[0] == 503]
            if done:
                shed = done[0]
            time.sleep(0.02)
        release.set()
        for t in threads:
            t.join(30)
        srv.ingest.ingest_bits = real
        check(shed is not None and "Retry-After" in shed[2],
              "saturated gate sheds ingest with 503 + Retry-After")
        st, _, _ = http("POST", f"{base}/index/fast/ingest", body,
                        codec.CONTENT_TYPE)
        check(st == 200, "route recovers after back-pressure")

        if FAILURES:
            print(f"ingestcheck: {len(FAILURES)} FAILURE(S)")
            return 1
        print("ingestcheck: all checks passed "
              f"(ingest {speedup:.1f}x legacy)")
        return 0
    finally:
        srv.close()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
