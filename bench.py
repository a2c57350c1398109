"""Kernel-floor microbenchmark: Count(Intersect(Bitmap, Bitmap)) as one
fused XLA bitwise+popcount program over a 64-slice index (64 x 2^20 =
67.1M columns, BASELINE.json config #5 shape), against a single-thread
NumPy pass of the identical computation.

This times a hand-written jitted scan, not the served path: nothing of
``pilosa_tpu`` but the compile-cache placement is on it. The served
path's proof of life is ``chip_smoke.py``.

- One process, which owns the chip. It exits non-zero when JAX finds no
  accelerator: a CPU timing is not a device number.
- Query data is generated on the device (``jax.random.bits``), so
  host-to-device transfer never enters the measurement.
- Timing is by marginal cost: K queries batched in one jitted scan and
  fetched once, per-query time = (t(R2) - t(R1)) / ((R2 - R1) * K),
  which cancels the fixed dispatch and fetch cost of a call.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device": {"platform", "kind", "count"}}.
"""
import json
import sys
import time
from functools import partial

import numpy as np

from pilosa_tpu.utils import compilecache

S = 64          # slices (config #5: 64-slice sharded Count(Intersect))
W = 32768       # uint32 words per slice row
K = 64          # distinct query pairs resident on device
R1, R2 = 4, 68  # repetition counts: the marginal gap is (R2-R1)*K queries


def main():
    compilecache.enable()
    import jax
    import jax.numpy as jnp
    from jax import lax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("bench: JAX found no accelerator (platform cpu); "
              "nothing measured", file=sys.stderr)
        return 1

    def device_data(k, seed):
        ka, kb = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.bits(ka, (k, S, W), dtype=jnp.uint32)
        b = jax.random.bits(kb, (k, S, W), dtype=jnp.uint32)
        return a, b

    @jax.jit
    def batch_counts(a, b):
        def step(c, ab):
            x, y = ab
            return c, jnp.sum(
                lax.population_count(lax.bitwise_and(x, y)).astype(jnp.int32))
        _, counts = lax.scan(step, 0, (a, b))
        return counts

    @partial(jax.jit, static_argnames=("reps",))
    def repeated_counts(a, b, reps):
        """R passes over the K query pairs; each pass XORs the rep index
        into the stream so XLA cannot collapse the repetitions."""
        def rep(acc, r):
            def step(c, ab):
                x, y = ab
                x = lax.bitwise_xor(x, r)
                return c, jnp.sum(
                    lax.population_count(lax.bitwise_and(x, y))
                    .astype(jnp.int32))
            _, counts = lax.scan(step, 0, (a, b))
            return acc + counts, None
        out, _ = lax.scan(rep, jnp.zeros(a.shape[0], jnp.int32),
                          jnp.arange(reps, dtype=jnp.uint32))
        return out

    # Correctness: one pair fetched to host and recomputed with NumPy.
    a, b = device_data(2, 0)
    counts = np.asarray(batch_counts(a, b))
    a0 = np.asarray(a[0])
    b0 = np.asarray(b[0])
    expect = int(np.bitwise_count(a0 & b0).sum())
    assert int(counts[0]) == expect, (int(counts[0]), expect)

    # CPU baseline: identical single-query computation, single thread.
    n_cpu = 5
    t0 = time.perf_counter()
    for _ in range(n_cpu):
        int(np.bitwise_count(a0 & b0).sum())
    cpu_qps = n_cpu / (time.perf_counter() - t0)

    # Device: marginal per-query time between two repetition counts over
    # the same resident data; median of trials.
    a, b = device_data(K, 1)
    np.asarray(jnp.sum(a[0, 0]) + jnp.sum(b[0, 0]))  # force materialize

    def timed(reps):
        t0 = time.perf_counter()
        np.asarray(repeated_counts(a, b, reps))
        return time.perf_counter() - t0

    timed(R1), timed(R2)  # compile both shapes outside timing
    marginals = []
    for _ in range(3):
        t_small = timed(R1)
        t_big = timed(R2)
        marginals.append((t_big - t_small) / ((R2 - R1) * K))
    per_query = max(sorted(marginals)[1], 1e-7)  # median
    qps = 1.0 / per_query

    print(json.dumps({
        "metric": "count_intersect_64slice_qps",
        "value": round(qps, 1),
        "unit": "queries/sec (64-slice 67.1M-col Count(Intersect) kernel)",
        "vs_baseline": round(qps / cpu_qps, 1),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
