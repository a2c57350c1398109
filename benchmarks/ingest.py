"""Streaming bulk-ingest benchmark — sustained bits-ingested/sec under
concurrent query load (ISSUE 11 acceptance).

Measures the legacy import path (per-slice POST /import at the
max-writes-per-request cadence — the request-sized loop every serving
milestone was loaded through) against the streaming ingest route
(POST /index/<i>/ingest, one columnar binary batch through the device
pack/classify pipeline), both while a closed-loop client hammers
Count(Intersect) queries against the SAME index being written — the
production shape where the write path competes with serving.

Two workload shapes:

- ``wide``  — 1,024 distinct rows (a representative bitmap index:
  attributes/terms), where the legacy path's per-request recount scan
  (O(touched rows x window) per 5,000 bits) dominates;
- ``narrow`` — 64 distinct rows, the shape most favorable to the
  legacy path (its per-request overheads amortize over few rows).

Reports bits/s + sustained q/s during each phase, the headline ratio
(wide shape, under load), and the compressed-landing evidence
(containers seeded by format, zero conversion churn), as JSONL rows on
stdout.

Run: python benchmarks/ingest.py [--bits 250000]
"""
import argparse
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

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.ingest import codec  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.server import wireproto as wp  # noqa: E402


def http(method, url, body=None, ctype="application/json", timeout=300):
    req = urllib.request.Request(url, data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", ctype)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def load_legacy(base, index, rows, cols, batch=5000):
    """The legacy loader: per-slice /import posts at the
    max-writes-per-request cadence (protobuf — its fastest wire)."""
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
                index, "f", s, rows[sel].tolist(), cols[sel].tolist(),
                [])
            st, data = http("POST", f"{base}/import", body,
                            "application/x-protobuf")
            assert st == 200, (st, data)
    return time.perf_counter() - t0


def load_ingest(base, index, rows, cols, batch=1_000_000):
    t0 = time.perf_counter()
    for off in range(0, len(rows), batch):
        body = codec.encode_bits("f", rows[off:off + batch],
                                 cols[off:off + batch])
        st, data = http("POST", f"{base}/index/{index}/ingest", body,
                        codec.CONTENT_TYPE)
        assert st == 200, (st, data)
    return time.perf_counter() - t0


class QueryLoad:
    """Closed-loop Count(Intersect) client against one index."""

    def __init__(self, base, index):
        self.base = base
        self.index = index
        self.n = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        q = (b'Count(Intersect(Bitmap(rowID=1, frame="f"), '
             b'Bitmap(rowID=2, frame="f")))')
        while not self._stop.is_set():
            http("POST", f"{self.base}/index/{self.index}/query", q,
                 "text/plain")
            self.n += 1

    def __enter__(self):
        self._t.start()
        time.sleep(0.3)
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(30)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", type=int, default=250_000)
    ap.add_argument("--slices", type=int, default=2)
    opts = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="ingest-bench-")
    srv = Server(os.path.join(tmp, "srv"), bind="localhost:0").open()
    base = f"http://{srv.host}"
    rng = np.random.default_rng(7)
    n = opts.bits
    seq = [0]

    def fresh():
        seq[0] += 1
        name = f"x{seq[0]}"
        http("POST", f"{base}/index/{name}", b"{}")
        http("POST", f"{base}/index/{name}/frame/f", b"{}")
        return name

    results = {}
    try:
        for shape, n_rows in (("wide", 1024), ("narrow", 64)):
            rows = rng.integers(0, n_rows, n).astype(np.uint64)
            cols = rng.integers(0, opts.slices * SLICE_WIDTH,
                                n).astype(np.uint64)
            # Warm one-time costs into throwaway indexes.
            load_legacy(base, fresh(), rows[:30000], cols[:30000])
            load_ingest(base, fresh(), rows[:30000], cols[:30000])
            for mode, loader in (("legacy", load_legacy),
                                 ("ingest", load_ingest)):
                name = fresh()
                # Seed so the concurrent queries have real work, then
                # measure the load with the query client hammering the
                # SAME index.
                load_ingest(base, name, rows[:30000], cols[:30000])
                with QueryLoad(base, name) as ql:
                    q0, t0 = ql.n, time.perf_counter()
                    dt = loader(base, name, rows, cols)
                    qps = (ql.n - q0) / (time.perf_counter() - t0)
                bps = n / dt
                results[(shape, mode)] = (bps, qps)
                print(f"{shape:7s} {mode:7s} under load: "
                      f"{bps:>12,.0f} bits/s | {qps:7.0f} q/s "
                      f"({dt:.2f}s)")

        st, v = http("GET", f"{base}/debug/vars")
        ing = json.loads(v)["ingest"]
        st, m = http("GET", f"{base}/debug/memory")
        conv = json.loads(m).get("containerConversionsTotal", 0)
        rows_out = []
        for (shape, mode), (bps, qps) in sorted(results.items()):
            rows_out.append({
                "metric": f"ingest_{shape}_{mode}_bps",
                "value": round(bps, 1),
                "unit": f"bits/s under concurrent query load "
                        f"({qps:.0f} q/s sustained)"})
        wide = results[("wide", "ingest")][0] / \
            results[("wide", "legacy")][0]
        narrow = results[("narrow", "ingest")][0] / \
            results[("narrow", "legacy")][0]
        rows_out.append({"metric": "ingest_speedup_wide",
                         "value": round(wide, 1),
                         "unit": "x vs legacy import, 1024-row shape "
                                 "under query load (bar >= 10x)"})
        rows_out.append({"metric": "ingest_speedup_narrow",
                         "value": round(narrow, 1),
                         "unit": "x vs legacy import, 64-row shape "
                                 "under query load"})
        rows_out.append({
            "metric": "ingest_containers_seeded",
            "value": sum(ing["containersSeeded"].values()),
            "unit": f"compressed containers landed at install "
                    f"({ing['containersSeeded']}); "
                    f"conversions={conv} (no churn)"})
        print()
        for r in rows_out:
            print(json.dumps(r))
        print(f"\nheadline: ingest {wide:.1f}x legacy (wide shape, "
              f"under concurrent query load); containers land "
              f"compressed with {conv} conversions")
        return 0 if wide >= 10 else 1
    finally:
        srv.close()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
