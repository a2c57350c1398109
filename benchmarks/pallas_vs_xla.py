"""Micro-bench: Pallas hand-blocked kernels vs the production XLA paths
(pilosa_tpu.ops.bitops) on the count-only hot paths, on the chip (the
Pallas kernels compile under Mosaic; there is no interpreter arm).
Marginal-cost timing (see bench.py docstring: it cancels the fixed
cost of a call).

Run: python benchmarks/pallas_vs_xla.py
"""
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()


def marginal_seconds(run, r1, r2, trials=3):
    """Median marginal cost between r1 and r2 in-jit repetitions of
    ``run(reps)``; guards against timer noise making the gap <= 0."""
    run(r1), run(r2)  # compile both shapes outside timing

    def timed(reps):
        t0 = time.perf_counter()
        run(reps)
        return time.perf_counter() - t0

    marg = []
    for _ in range(trials):
        t1, t2 = timed(r1), timed(r2)
        marg.append((t2 - t1) / (r2 - r1))
    return max(sorted(marg)[trials // 2], 1e-7)


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops import bitops, pallas_kernels as pk

    S, W = 64, 32768
    K = 32

    key = jax.random.PRNGKey(0)
    ka, kb = jax.random.split(key)
    a = jax.random.bits(ka, (K, S, W), dtype=jnp.uint32)
    b = jax.random.bits(kb, (K, S, W), dtype=jnp.uint32)

    # "xla" is the PRODUCTION path (pilosa_tpu.ops.bitops), not a copy.
    variants = {"xla": bitops.count_and, "pallas": pk.count_and}

    va = np.asarray(a[0]); vb = np.asarray(b[0])
    want = int(np.bitwise_count(va & vb).sum())
    for name, fn in variants.items():
        got = int(jax.jit(fn)(a[0], b[0]))
        assert got == want, (name, got, want)
    print("correctness ok:", want)

    for name, fn in variants.items():
        @partial(jax.jit, static_argnames=("reps",))
        def repeated(a, b, reps, fn=fn):
            def rep(acc, r):
                def step(c, ab):
                    x, y = ab
                    return c, fn(lax.bitwise_xor(x, r), y)
                _, counts = lax.scan(step, 0, (a, b))
                return acc + counts, None
            out, _ = lax.scan(rep, jnp.zeros(a.shape[0], jnp.int32),
                              jnp.arange(reps, dtype=jnp.uint32))
            return out

        per_q = marginal_seconds(
            lambda reps: np.asarray(repeated(a, b, reps)), 4, 36) / K
        gbps = 2 * S * W * 4 / per_q / 1e9
        print(f"{name:8s} {per_q*1e6:9.1f} us/query  {gbps:7.1f} GB/s effective")

    # per-row matrix counts (TopN path): [R_rows, W] & [W]
    R_rows = 512
    m = jax.random.bits(ka, (R_rows, W), dtype=jnp.uint32)
    filt = jax.random.bits(kb, (W,), dtype=jnp.uint32)

    want = np.bitwise_count(np.asarray(m) & np.asarray(filt)).sum(axis=1)
    for name, fn in {"xla": bitops.count_and_rows,
                     "pallas": pk.count_and_rows}.items():
        got = np.asarray(jax.jit(fn)(m, filt))
        assert (got == want).all(), name

        @partial(jax.jit, static_argnames=("reps",))
        def repeated(m, f, reps, fn=fn):
            def rep(acc, r):
                return acc + fn(lax.bitwise_xor(m, r), f), None
            out, _ = lax.scan(rep, jnp.zeros(m.shape[0], jnp.int32),
                              jnp.arange(reps, dtype=jnp.uint32))
            return out

        per_q = marginal_seconds(
            lambda reps: np.asarray(repeated(m, filt, reps)), 8, 72)
        gbps = R_rows * W * 4 / per_q / 1e9
        print(f"rows/{name:8s} {per_q*1e6:9.1f} us/call  {gbps:7.1f} GB/s effective")


if __name__ == "__main__":
    main()
