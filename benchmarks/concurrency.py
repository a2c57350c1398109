"""Concurrent-client serving throughput: 1/8/32 clients against one
node, mixed Count / TopN / SetBit.

Round-2 gap (VERDICT Missing #4): the reference serves every query on
all cores via goroutines (server.go:205-217 http.Serve); ours is
Python's ThreadingHTTPServer under the GIL with device dispatch
serialized — and the only prior measurement (688 q/s at 1 client,
618 q/s at 10, CPU backend) showed zero scaling. This benchmark records
QPS vs client count; the executor's cross-query count coalescing
(group-commit batching at the dispatch mouth) is what scaling rides on:
while one fused device program runs (GIL released inside XLA), newly
arrived queries accumulate and dispatch as the next single program.

Env: CONCURRENCY_SECONDS per point (default 8), CONCURRENCY_SLICES
(default 64). This process boots the server in-process and so owns the
chip; the client drivers are stdlib-only children.

Prints one JSON line per (clients, mix) point.
"""
import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()
# This benchmark measures DISPATCH scaling (GIL, coalescing, stack
# repair under writes); its clients repeat identical queries, which the
# whole-result memos — and in worker mode the workers' response
# cache — would otherwise serve as dict lookups. Warm dashboard
# throughput is northstar's metric, not this one's.
os.environ.setdefault("PILOSA_TPU_RESULT_MEMO", "0")
os.environ.setdefault("PILOSA_TPU_WORKER_CACHE", "0")

SECONDS = float(os.environ.get("CONCURRENCY_SECONDS", "8"))
N_SLICES = int(os.environ.get("CONCURRENCY_SLICES", "64"))
# "count" | "mixed" | "both": lets A/B drivers (concurrency_ab.py) buy
# only the points a given arm needs from the chip-window budget.
MODES = os.environ.get("CONCURRENCY_MODES", "both")
if MODES not in ("count", "mixed", "both"):
    # A typo'd mode would build + warm, measure NOTHING, and exit 0 —
    # an invisible hole in a chip-window artifact. Fail loudly.
    raise SystemExit(f"CONCURRENCY_MODES={MODES!r} not in "
                     "count|mixed|both")
# Worker frontend processes (server/workers.py): HTTP transport (and,
# on the CPU backend, read execution) fans across worker processes
# while the master keeps the device. Default: 4 when the host has the
# cores for them — on a 1-core host (this sandbox) extra processes
# only add scheduler churn, so the default stays single-process and
# the architecture is proven by tests/test_workers.py instead.
WORKERS = int(os.environ.get(
    "PILOSA_TPU_WORKERS", "4" if (os.cpu_count() or 1) >= 4 else "0"))
BIND = "127.0.0.1:10143"

COUNT_Q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
           'Bitmap(frame="f", rowID=2)))')
TOPN_Q = 'TopN(frame="f", n=3)'


def post(path, data):
    req = urllib.request.Request(f"http://{BIND}{path}",
                                 data=data.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def build(server):
    rng = np.random.default_rng(5)
    idx = server.holder.create_index("c")
    idx.create_frame("f")
    frame = idx.frame("f")
    for s in range(N_SLICES):
        base = s * SLICE_WIDTH
        for rid, n in ((1, 400), (2, 300), (3, 200)):
            c = rng.choice(8000, size=n, replace=False)
            frame.import_bits([rid] * n, (base + c).tolist())


def widen(server):
    """The mixed workload writes to random columns, which widens
    column windows to the full slice within the first few writes
    anyway; pre-widen (top column of every slice) so the mixed timed
    windows measure steady-state serving, not the bounded
    once-per-lifetime width-bucket compiles the widening triggers.
    Runs AFTER the count-only points — narrow windows ARE the steady
    state for a read-only workload."""
    frame = server.holder.index("c").frame("f")
    for s in range(N_SLICES):
        frame.import_bits([1], [s * SLICE_WIDTH + SLICE_WIDTH - 1])


def _drive(n_clients, mode, seconds):
    """Drive n_clients via SUBPROCESS client drivers (_conc_client.py)
    — client HTTP work must not share the bench process's GIL with the
    master server, or 32 client threads would measure their own
    serialization instead of the server's (the reference's bench
    clients are separate processes too). Clients spread over up to 8
    processes; a shared start timestamp is the cross-process barrier.
    -> (queries, wall)."""
    import subprocess

    n_procs = min(8, n_clients)
    per = [n_clients // n_procs + (1 if i < n_clients % n_procs else 0)
           for i in range(n_procs)]
    start_ts = time.time() + 1.0 + 0.15 * n_procs
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_conc_client.py")
    procs = [subprocess.Popen(
        [sys.executable, script, BIND, mode, str(k), str(start_ts),
         str(seconds)], stdout=subprocess.PIPE) for k in per]
    total = 0
    for p in procs:
        out, _ = p.communicate(timeout=seconds + 120)
        assert p.returncode == 0, f"client driver rc={p.returncode}"
        total += int(out.split()[-1])
    assert total > 0, "client drivers issued zero queries (late start?)"
    return total, seconds


def run_point(name, n_clients, mode):
    """A short untimed warm pass runs the SAME client count first so
    one-off costs a real server pays once per lifetime — XLA compiles
    for each power-of-two coalesced batch bucket this concurrency
    level produces, stack-cache fills, path-model convergence — land
    outside the measured window (executor_qps warms the same way; on
    an accelerator one compile is tens of seconds against an 8 s
    window)."""
    _drive(n_clients, mode, min(3.0, SECONDS))
    queries, dt = _drive(n_clients, mode, SECONDS)
    qps = queries / dt
    print(json.dumps({
        "metric": f"concurrency_{name}_{n_clients}c_qps",
        "value": round(qps, 1),
        "unit": f"q/s ({n_clients} clients, {N_SLICES} slices, "
                f"{WORKERS} workers)"}))
    return qps


def main():
    d = tempfile.mkdtemp(prefix="conc_")
    from pilosa_tpu.server.server import Server

    server = Server(os.path.join(d, "data"), bind=BIND, workers=WORKERS)
    server.open()
    try:
        build(server)
        # Warm both query shapes (compile + stacks).
        post("/index/c/query", COUNT_Q)
        post("/index/c/query", TOPN_Q)

        results = {}
        if MODES in ("count", "both"):
            for n in (1, 8, 32):
                results[n] = run_point("count", n, "count")
        if MODES in ("mixed", "both"):
            widen(server)
            for n in (1, 8, 32):
                run_point("mixed", n, "mixed")
        if results:
            print(json.dumps({
                "metric": "concurrency_count_scaling_32c_vs_1c",
                "value": round(results[32] / max(results[1], 1e-9), 2),
                "unit": f"x (count-only QPS, 32 clients vs 1, "
                        f"{WORKERS} workers)"}))
    finally:
        server.close()


if __name__ == "__main__":
    main()
