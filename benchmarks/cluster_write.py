"""Replicated write path on a real 2-node cluster (round 5): single
SetBit over HTTP (each write applies locally and fans to its replica
synchronously before the ack — ref: executor write fan-out,
executor.go:1444-1535) and the bulk import path (slice-routed
protobuf, client.go:227-276 analog), verified on BOTH replicas.

Env: CLUSTER_WRITE_SETBITS (default 300), CLUSTER_WRITE_SLICES
(default 64, 1000 bits each).
"""
import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

import numpy as np  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.cluster.client import InternalClient  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.testing import free_ports  # noqa: E402

N_SETBITS = int(os.environ.get("CLUSTER_WRITE_SETBITS", "300"))
N_SLICES = int(os.environ.get("CLUSTER_WRITE_SLICES", "64"))
BITS_PER_SLICE = 1000


def main():
    d = tempfile.mkdtemp(prefix="cluster_write_")
    ports = free_ports(2)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    servers = [Server(os.path.join(d, f"n{i}"), bind=hosts[i],
                      cluster_hosts=hosts, replica_n=2,
                      anti_entropy_interval=0, polling_interval=0).open()
               for i in range(2)]
    a, b = servers

    def post(path, body):
        req = urllib.request.Request(f"http://{a.host}{path}",
                                     data=body.encode(), method="POST")
        return json.loads(
            urllib.request.urlopen(req, timeout=60).read() or b"{}")

    try:
        post("/index/i", "{}")
        post("/index/i/frame/f", "{}")

        t0 = time.perf_counter()
        for k in range(N_SETBITS):
            post("/index/i/query",
                 f'SetBit(frame="f", rowID=1, columnID={k})')
        setbit = N_SETBITS / (time.perf_counter() - t0)
        print(json.dumps({
            "metric": "cluster_setbit_http_ops", "value": round(setbit),
            "unit": "replicated SetBit/s over HTTP (2-node replica_n=2;"
                    " ack after local apply + replica fan-out)"}))

        cl = InternalClient()
        total = 0
        t0 = time.perf_counter()
        for s in range(N_SLICES):
            rows = np.repeat(np.arange(8, dtype=np.uint64),
                             BITS_PER_SLICE // 8)
            cols = ((np.arange(BITS_PER_SLICE, dtype=np.uint64) * 31)
                    % SLICE_WIDTH) + s * SLICE_WIDTH
            cl.import_bits(a.cluster, "i", "f", s, rows.tolist(),
                           cols.tolist())
            total += BITS_PER_SLICE
        imp = total / (time.perf_counter() - t0)
        print(json.dumps({
            "metric": "cluster_import_bits", "value": round(imp),
            "unit": f"bits/s ({N_SLICES} slices x {BITS_PER_SLICE}, "
                    "every bit on both replicas)"}))
        cl.close()

        # Replica verification: the bits must exist on BOTH nodes.
        fa = a.holder.fragment("i", "f", "standard", 5)
        fb = b.holder.fragment("i", "f", "standard", 5)
        assert fa is not None and fb is not None
        assert fa.count() == fb.count() == BITS_PER_SLICE, (
            fa.count(), fb.count())
        print(json.dumps({"metric": "cluster_write_verified", "value": 1,
                          "unit": "replica counts equal"}))
    finally:
        for s_ in servers:
            s_.close()


if __name__ == "__main__":
    main()
