"""Micro-batching smoke: the cross-query coalescer on a COMPRESSED
index (PR 12), wired into ``make test`` as ``make batchcheck``.

Phase 1 (engine): a concurrent mixed-format count workload (sparse
ARRAY rows, a RUN row, empty rows, every count op, single-leaf
counts) against an evicted compressed-container index with the tick
window open, asserting:

- nonzero FUSED groups actually served from the container-lane tier
  (the path that used to decline every all-compressed plan),
- zero unexpected densifications (container_conversions_total flat —
  lanes never stage compressed rows densely),
- every fused result bit-exact against the serial compressed kernels
  (coalesce-compressed=false is the same serial path, cross-checked
  for a sample),
- the coalesce ops surfaces moved (coalesce_metrics / snapshot).

Phase 2 (HTTP): a saturated QoS gate back-pressures the same workload
— max-concurrent=1 with a tiny queue must shed overflow with 503 +
Retry-After while every accepted response stays bit-exact, and the
server recovers (a quiet follow-up query answers 200).

Small and CPU-only by design: a few slices, a few dozen queries.
"""
import json
import os
import sys
import tempfile
import threading
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402

N_SLICES = 3
PAIRS = [(1, 2), (1, 3), (2, 3), (1, 5), (2, 5), (3, 4), (4, 5)]


def build_compressed(holder):
    """Sparse + run rows spread over full slices, snapshotted and
    evicted — the 100B-shape compressed serving tier (count100b's
    capture shape at smoke scale)."""
    import numpy as np

    idx = holder.create_index("bc")
    idx.create_frame("f")
    frame = idx.frame("f")
    rng = np.random.default_rng(12)
    for s in range(N_SLICES):
        base = s * SLICE_WIDTH
        for rid, n in ((1, 500), (2, 300), (3, 150)):
            c = rng.choice(SLICE_WIDTH, size=n, replace=False)
            frame.import_bits([rid] * n, (base + c).tolist())
        start = int(rng.integers(0, SLICE_WIDTH - 3000))
        c = np.arange(start, start + 2000)
        frame.import_bits([5] * len(c), (base + c).tolist())
        # row 4 stays empty
    for v in frame.views.values():
        for frag in list(v.fragments.values()):
            frag.snapshot()
            frag.unload()
    return frame


def queries():
    out = []
    for op in ("Intersect", "Union", "Difference", "Xor"):
        out.extend(
            f'Count({op}(Bitmap(frame="f", rowID={a}), '
            f'Bitmap(frame="f", rowID={b})))' for a, b in PAIRS)
    out.extend(f'Count(Bitmap(frame="f", rowID={r}))'
               for r in (1, 2, 4, 5))
    return out


def phase_engine(fails):
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import containers
    from pilosa_tpu.storage.holder import Holder

    d = tempfile.mkdtemp(prefix="batchcheck_")
    holder = Holder(os.path.join(d, "data")).open()
    build_compressed(holder)
    serial = Executor(holder)
    serial._force_path = "serial"
    e = Executor(holder)
    e._force_path = "batched"
    e._co_enabled_memo = True
    e.set_coalesce_config(max_wait_us=5000)

    qs = queries() * 2
    want = {q: serial.execute("bc", q)[0] for q in set(qs)}
    conv0 = containers.conversions_total()
    results, errors = {}, []
    barrier = threading.Barrier(len(qs))

    def run(q, i):
        try:
            barrier.wait(timeout=30)
            results[i] = e.execute("bc", q)[0]
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append(repr(exc)[:200])

    threads = [threading.Thread(target=run, args=(q, i))
               for i, q in enumerate(qs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    if errors:
        fails.append(f"engine workload errors: {errors[:3]}")
    bad = [(q, results.get(i), want[q]) for i, q in enumerate(qs)
           if results.get(i) != want[q]]
    if bad:
        fails.append(f"fused results not bit-exact: {bad[:5]}")
    st = e._co_stats
    if st["compressed_fused"] < 2:
        fails.append(f"no compressed fusion happened: {st}")
    if st["max_group"] < 2:
        fails.append(f"no multi-query group formed: {st}")
    if st["lane_launches"] < 1:
        fails.append(f"no lane launches recorded: {st}")
    conv = containers.conversions_total() - conv0
    if conv != 0:
        fails.append(f"unexpected densifications during lanes: {conv}")
    m = e.coalesce_metrics()
    if m["compressed_fused_queries_total"] != st["compressed_fused"]:
        fails.append(f"metrics/stats disagree: {m} vs {st}")
    print(f"batchcheck engine: {len(qs)} queries, "
          f"{st['rounds']} ticks, max group {st['max_group']}, "
          f"{st['compressed_fused']} compressed-fused, "
          f"{st['lane_launches']} lane launches, "
          f"{conv} densifications")
    holder.close()


def phase_qos(fails):
    """Saturated-gate back-pressure: one execution slot, a tiny
    queue, a burst of concurrent queries — overflow must shed 503 +
    Retry-After, accepted answers must stay bit-exact, and the gate
    must recover."""
    from pilosa_tpu.server.server import Server

    d = tempfile.mkdtemp(prefix="batchcheck_qos_")
    server = Server(os.path.join(d, "data"), bind="localhost:0",
                    qos={"enabled": True, "max-concurrent": 1,
                         "queue-length": 2, "queue-timeout": 0.2}).open()
    server.handler._resp_cache = None  # every query really executes
    server.executor._co_enabled_memo = True
    server.executor._force_path = "batched"
    server.executor.set_coalesce_config(max_wait_us=2000)
    base = f"http://{server.host}"

    def post(path, body, timeout=30):
        req = urllib.request.Request(base + path, data=body.encode(),
                                     method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read().decode()

    try:
        build_compressed(server.holder)
        q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
             'Bitmap(frame="f", rowID=2)))')
        want = json.loads(post("/index/bc/query", q)[2])["results"][0]

        oks, sheds, others = [], [], []
        barrier = threading.Barrier(16)

        def client():
            try:
                barrier.wait(timeout=30)
                st, _, body = post("/index/bc/query", q)
                oks.append(json.loads(body)["results"][0])
            except urllib.error.HTTPError as exc:
                if exc.code == 503 and exc.headers.get("Retry-After"):
                    sheds.append(503)
                else:
                    others.append(exc.code)
            except Exception as exc:  # noqa: BLE001 — reported
                others.append(repr(exc)[:120])

        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        if others:
            fails.append(f"unexpected client outcomes: {others[:3]}")
        if not sheds:
            fails.append("saturated gate never shed "
                         "(expected 503 + Retry-After)")
        if not oks:
            fails.append("saturated gate served nothing")
        if any(v != want for v in oks):
            fails.append(f"accepted answers not bit-exact: {oks[:5]} "
                         f"vs {want}")
        # Recovery: the gate drains and a quiet query answers 200.
        st, _, body = post("/index/bc/query", q)
        if st != 200 or json.loads(body)["results"][0] != want:
            fails.append(f"no recovery after shed burst: {st} {body}")
        print(f"batchcheck qos: {len(oks)} served bit-exact, "
              f"{len(sheds)} shed 503+Retry-After, recovered")
    finally:
        server.close()


def main():
    fails = []
    phase_engine(fails)
    phase_qos(fails)
    if fails:
        for f in fails:
            print(f"batchcheck FAIL: {f}", file=sys.stderr)
        return 1
    print("batchcheck OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
