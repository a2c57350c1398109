"""Anti-entropy digest pre-check benchmark: a full sync pass over a
2-node replica pair with N identical fragments, with and without the
fragment-level digest short-circuit (VERDICT r3 #4; ref contrast:
syncFragment walks every fragment's block checksums unconditionally,
fragment.go:1703-1782).

The identical case IS the steady state of anti-entropy — every pass
after convergence re-proves agreement — so the digest pass's speedup
bounds the background cost of the 10-minute sync loop at scale.

Fragments carry 256 rows each: the walk's cost is the per-row block
checksum computation on BOTH replicas (lazy full-row streams on
evicted fragments), which is exactly what the digest skips — tiny
1-row fragments would measure only the shared HTTP round trip.

Env: SYNC_SLICES (default 400).
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

import numpy as np  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.server.server import Server  # noqa: E402
from pilosa_tpu.testing import free_ports  # noqa: E402

N = int(os.environ.get("SYNC_SLICES", "400"))
ROWS = 256


def main():
    d = tempfile.mkdtemp(prefix="syncdig_")
    ports = free_ports(2)
    hosts = [f"localhost:{p}" for p in ports]
    servers = [Server(os.path.join(d, f"n{i}"), bind=hosts[i],
                      cluster_hosts=hosts, replica_n=2,
                      anti_entropy_interval=0, polling_interval=0).open()
               for i in range(2)]
    try:
        a, b = servers
        for holder in (a.holder, b.holder):
            idx = holder.create_index("i")
            idx.create_frame("f")
            fr = idx.frame("f")
            r = np.random.default_rng(11)
            for s in range(N):
                rows = np.repeat(np.arange(ROWS, dtype=np.uint64), 4)
                cols = (r.choice(3000, size=ROWS * 4)
                        .astype(np.uint64) + s * SLICE_WIDTH)
                fr.import_bits(rows, cols)
                frag = holder.fragment("i", "f", "standard", s)
                frag.snapshot()
                frag.unload()

        t0 = time.perf_counter()
        a.syncer.sync_holder()
        with_digest = time.perf_counter() - t0

        # Pass 2 = the true steady state: the content-true digest
        # decoded every container ONCE in pass 1 (exactness costs one
        # decode per fragment per process lifetime); unchanged
        # fragments now answer from the version-keyed memo on both
        # replicas.
        t0 = time.perf_counter()
        a.syncer.sync_holder()
        warm = time.perf_counter() - t0

        # Disable the pre-check by forcing a digest mismatch answer.
        orig = a.syncer._fragment_digest_or_empty
        a.syncer._fragment_digest_or_empty = \
            lambda *args, **kw: b"\xff" * 8
        t0 = time.perf_counter()
        a.syncer.sync_holder()
        without = time.perf_counter() - t0
        a.syncer._fragment_digest_or_empty = orig

        print(json.dumps({
            "metric": "sync_identical_pass_digest_s",
            "value": round(with_digest, 2),
            "unit": f"s ({N} identical fragments, 2 replicas, cold)"}))
        print(json.dumps({
            "metric": "sync_identical_pass_digest_warm_s",
            "value": round(warm, 2),
            "unit": "s (pass 2, digest memos warm = steady state)"}))
        print(json.dumps({
            "metric": "sync_identical_pass_blockwalk_s",
            "value": round(without, 2),
            "unit": "s (same pass, digest pre-check bypassed)"}))
        print(json.dumps({
            "metric": "sync_digest_speedup",
            "value": round(without / max(warm, 1e-9), 1),
            "unit": "x (identical-replica steady-state pass)"}))
    finally:
        for s in servers:
            s.close()


if __name__ == "__main__":
    main()
