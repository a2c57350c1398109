"""Remote-subquery batching effect under concurrent cluster load
(round 5): N client threads issue distinct Count queries through
coordinator A; every query needs a subquery on peer B. With batching
ON, concurrent subcalls group-commit into multi-call queries — B
serves FEWER wire requests than queries issued. The wire-request
ratio is the structural metric (single-core QPS deltas here are
scheduler noise; the round trips saved are real on any hardware).

Env: RB_CLIENTS (default 8), RB_QUERIES per client (default 50).
"""
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

import numpy as np  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.testing import free_ports  # noqa: E402

CLIENTS = int(os.environ.get("RB_CLIENTS", "8"))
QUERIES = int(os.environ.get("RB_QUERIES", "50"))
N_SLICES = 64


def run_once(batching):
    os.environ["PILOSA_TPU_REMOTE_BATCH"] = "1" if batching else "0"
    d = tempfile.mkdtemp(prefix="rb_")
    ports = free_ports(2)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    servers = [Server(os.path.join(d, f"n{i}"), bind=hosts[i],
                      cluster_hosts=hosts, replica_n=1,
                      anti_entropy_interval=0, polling_interval=0).open()
               for i in range(2)]
    a, b = servers

    def post(host, path, body):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body.encode(), method="POST")
        return json.loads(
            urllib.request.urlopen(req, timeout=60).read() or b"{}")

    try:
        post(a.host, "/index/i", "{}")
        post(a.host, "/index/i/frame/f", "{}")
        rows, cols = [], []
        rng = np.random.default_rng(7)
        for s in range(N_SLICES):
            for rid in range(CLIENTS):
                c = rng.choice(2000, size=20, replace=False)
                rows.extend([rid] * 20)
                cols.extend((s * SLICE_WIDTH + c).tolist())
        a.holder.index("i").frame("f").import_bits(rows, cols)
        b.holder.index("i").frame("f").import_bits(rows, cols)
        # Warm (schema + stacks both sides).
        post(a.host, "/index/i/query", 'Count(Bitmap(frame="f", rowID=0))')

        # Count wire requests at the coordinator's internal client —
        # each execute_query call is one peer round trip.
        wire = {"n": 0}
        orig_eq = a.client.execute_query

        def counting_eq(*args, **kw):
            wire["n"] += 1
            return orig_eq(*args, **kw)

        a.client.execute_query = counting_eq
        stop_err = []

        def client(tid):
            try:
                for k in range(QUERIES):
                    out = post(
                        a.host, "/index/i/query",
                        f'Count(Bitmap(frame="f", rowID={tid}))'
                        + " " * k)  # unique text: dodge memos/caches
                    assert out["results"][0] == 20 * N_SLICES, out
            except Exception as exc:  # noqa: BLE001
                stop_err.append(repr(exc))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert not stop_err, stop_err[:2]
        rb = dict(a.executor._rb_stats)
        return {"queries": CLIENTS * QUERIES,
                "peer_wire_calls": wire["n"],
                "qps": round(CLIENTS * QUERIES / dt, 1),
                "max_batch": rb.get("max_batch", 0)}
    finally:
        for s_ in servers:
            s_.close()


def main():
    off = run_once(batching=False)
    on = run_once(batching=True)
    print(json.dumps({"metric": "remote_batch_off", **off}))
    print(json.dumps({"metric": "remote_batch_on", **on}))
    print(json.dumps({
        "metric": "remote_batch_wire_reduction",
        "value": round(off["peer_wire_calls"]
                       / max(on["peer_wire_calls"], 1), 2),
        "unit": (f"x fewer peer wire requests for the same "
                 f"{on['queries']} queries ({CLIENTS} concurrent "
                 f"clients; max batch {on['max_batch']})")}))


if __name__ == "__main__":
    main()
