"""A/B: intra-pod fan-out as ONE collective program vs per-node HTTP.

The acceptance benchmark: an in-process pod (default 4 nodes) on an
8-device CPU-emulated mesh serves warm Count(Intersect) at equal
slice counts through both data planes —

- **mesh**: the query compiles to one shard_map + psum program over
  sharded slice stacks (cluster/meshplane.py); asserted to be exactly
  ONE collective launch per query,
- **http**: the same cluster with the plane detached — the
  goroutine-per-node-analog thread fan-out with JSON over sockets.

Both arms run with result memos and response caches OFF so every
query pays its full fan-out path; answers are asserted bit-exact.
The headline is per-query fan-out latency (and its ratio — the
acceptance bar is >= 5x), measured at the executor so HTTP client
overhead of the BENCHMARK harness itself is out of both arms.

MESH_FANOUT_SLICES (default 64) sets the slice count;
MESH_FANOUT_NODES (default 4) the pod size; MESH_FANOUT_N (default
200) the timed queries per arm. The JSONL rows go to stdout.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

try:
    from benchmarks import _ledger  # noqa: E402
except ImportError:  # pragma: no cover — ledger is best-effort
    _ledger = None

N_SLICES = int(os.environ.get("MESH_FANOUT_SLICES", "64"))
N_NODES = int(os.environ.get("MESH_FANOUT_NODES", "4"))
N_QUERIES = int(os.environ.get("MESH_FANOUT_N", "200"))
QUERY = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))')


def seed(cluster):
    import urllib.request

    import numpy as np

    from pilosa_tpu import SLICE_WIDTH

    host = cluster.hosts[0]

    def post(path, body):
        req = urllib.request.Request(
            f"http://{host}{path}", data=body.encode(), method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.read()

    post("/index/i", "{}")
    post("/index/i/frame/f", "{}")
    # Columns cluster in a 2^16-wide band per slice — the window-
    # economy shape both data planes stage narrowly (executor
    # _union_window / meshplane._window), so the A/B isolates FAN-OUT
    # cost rather than full-slice-width popcount time.
    band = 1 << 16
    rng = np.random.default_rng(5)
    shared = rng.choice(band, 2000, replace=False)
    for s in range(N_SLICES):
        base = s * SLICE_WIDTH
        for r in (1, 2):
            cols = np.unique(np.concatenate([
                shared[:1000],
                rng.choice(band, 1500, replace=False)])) + base
            post("/index/i/query", "\n".join(
                f'SetBit(frame="f", rowID={r}, columnID={c})'
                for c in cols.tolist()))


def timed(ex, n):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = ex.execute("i", QUERY)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return out[0], {
        "mean_ms": sum(lat) / len(lat) * 1e3,
        "p50_ms": lat[len(lat) // 2] * 1e3,
        "p99_ms": lat[int(len(lat) * 0.99)] * 1e3,
    }


def main():
    from pilosa_tpu.testing import ServerCluster

    cluster = ServerCluster(N_NODES, mesh={"enabled": True})
    try:
        seed(cluster)
        ex = cluster[0].executor
        # Replay tiers off: per-query fan-out cost is the subject.
        for srv in cluster:
            srv.executor._result_memo_off = True
            srv.handler._resp_cache = None

        plane = ex.meshplane
        # Warm both arms (compiles, stack staging, plan cache).
        ex.execute("i", QUERY)
        launches0 = plane._stats["launches"]["count"]
        mesh_count, mesh = timed(ex, N_QUERIES)
        launches = plane._stats["launches"]["count"] - launches0
        one_launch = launches == N_QUERIES

        for srv in cluster:
            srv.executor.meshplane = None
        ex.execute("i", QUERY)  # warm the HTTP arm
        http_count, http = timed(ex, N_QUERIES)
        for srv in cluster:
            srv.executor.meshplane = srv.meshplane

        speedup = http["mean_ms"] / mesh["mean_ms"]
        rows = [
            {"metric": "mesh_fanout_slices", "value": N_SLICES,
             "unit": f"slices over 8 virtual CPU devices, "
                     f"{N_NODES}-node in-process pod, {N_QUERIES} "
                     f"warm queries per arm"},
            {"metric": "mesh_fanout_collective_ms",
             "value": round(mesh["mean_ms"], 3),
             "unit": "ms/query warm Count(Intersect), one shard_map+"
                     "psum program per query (p50 "
                     f"{mesh['p50_ms']:.3f}, p99 {mesh['p99_ms']:.3f})"},
            {"metric": "mesh_fanout_http_ms",
             "value": round(http["mean_ms"], 3),
             "unit": "ms/query same queries via per-node HTTP fan-out "
                     f"(p50 {http['p50_ms']:.3f}, p99 "
                     f"{http['p99_ms']:.3f})"},
            {"metric": "mesh_fanout_speedup",
             "value": round(speedup, 2),
             "unit": "x lower per-query fan-out latency (bar >= 5x)"},
        ]
        for row in rows:
            print(json.dumps(row))
        if _ledger is not None:
            _ledger.record_rows("mesh_fanout", rows,
                                knobs={"slices": N_SLICES,
                                       "nodes": N_NODES,
                                       "queries": N_QUERIES})

        ok = True
        if mesh_count != http_count:
            print(f"FAIL bit-exactness: mesh={mesh_count} "
                  f"http={http_count}")
            ok = False
        if not one_launch:
            print(f"FAIL one-collective-per-query: {launches} launches "
                  f"for {N_QUERIES} queries")
            ok = False
        if speedup < 5.0:
            print(f"FAIL speedup {speedup:.2f}x < 5x bar")
            ok = False
        if ok:
            print(f"PASS bit-exact ({mesh_count}), one collective "
                  f"launch per query, {speedup:.1f}x over HTTP")
        return 0 if ok else 1
    finally:
        cluster.close()


if __name__ == "__main__":
    sys.exit(main())
