"""BASELINE.json config suite on the chip, with single-thread CPU
NumPy baselines of the identical computation.

Configs (BASELINE.json "configs"):
  1. single-fragment Count(Bitmap) on a 1M-column slice
  2. Intersect/Union/Difference fold over 1K rows, one slice
  3. TopN(frame, n=100) over a ranked row matrix
  4. BSI Sum/Min-plane pass over an integer field (10 planes + filter)
  5. 64-slice sharded Count(Intersect)  (bench.py's north star)

Timing uses the marginal-cost method (see bench.py): K in-jit
repetitions, per-op time from the repetition delta, so the fixed
dispatch and host-fetch cost of a call cancels out.

Run: python benchmarks/suite.py   (prints a markdown table)
"""
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.pallas_vs_xla import marginal_seconds  # noqa: E402


# SUITE_SCALE=16 shrinks every dimension ~16x for CPU smoke runs;
# default 1 = the real TPU-sized configs.
_SCALE = max(1, int(os.environ.get("SUITE_SCALE", "1")))
W = max(16, 32768 // _SCALE)  # uint32 words per slice
S = max(2, 64 // _SCALE)    # slices for config 5
R = max(8, 1024 // _SCALE)  # rows for configs 2/3
D = 10             # BSI bit planes for config 4
TOPN_K = min(100, R)  # TopN k clamps to the scaled row count


def bench_cpu(fn, reps=5):
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    rows = []

    def dev(shape, i):
        return jax.random.bits(ks[i], shape, dtype=jnp.uint32)

    def rep_harness(body, n_state):
        """Salted in-jit repetition: body(x) must be a fn of the salted
        input; state is a running int32 sum so XLA can't dead-code it."""
        @partial(jax.jit, static_argnames=("reps",))
        def repeated(x, reps):
            def rep(acc, r):
                return acc + body(lax.bitwise_xor(x, r)), None
            out, _ = lax.scan(rep, jnp.zeros(n_state, jnp.int32),
                              jnp.arange(reps, dtype=jnp.uint32))
            return out
        return repeated

    # ---- config 1: Count(Bitmap), one 1M-column slice -------------------
    a = dev((W,), 0)
    a_h = np.asarray(a)
    rep = rep_harness(lambda x: jnp.sum(
        lax.population_count(x).astype(jnp.int32)), ())
    t_tpu = marginal_seconds(lambda r: np.asarray(rep(a, r)),
                             max(10, 10_000 // _SCALE),
                             max(20, 810_000 // _SCALE))
    t_cpu = bench_cpu(lambda: int(np.bitwise_count(a_h).sum()), 50)
    rows.append((f"1. Count(Bitmap) {W * 32:,} cols", t_cpu, t_tpu))

    # ---- config 2: Intersect/Union/Difference fold over 1K rows ---------
    m = dev((R, W), 1)
    m_h = np.asarray(m)

    def fold_count(x):
        inter = lax.reduce(x, jnp.uint32(0xFFFFFFFF), lax.bitwise_and, (0,))
        union = lax.reduce(x, jnp.uint32(0), lax.bitwise_or, (0,))
        diff = lax.bitwise_and(x[0], lax.bitwise_not(union))
        return (jnp.sum(lax.population_count(inter).astype(jnp.int32))
                + jnp.sum(lax.population_count(union).astype(jnp.int32))
                + jnp.sum(lax.population_count(diff).astype(jnp.int32)))

    rep = rep_harness(fold_count, ())
    t_tpu = marginal_seconds(lambda r: np.asarray(rep(m, r)),
                             max(2, 50 // _SCALE), max(4, 1650 // _SCALE))

    def cpu_fold():
        inter = np.bitwise_and.reduce(m_h, axis=0)
        union = np.bitwise_or.reduce(m_h, axis=0)
        diff = m_h[0] & ~union
        return (int(np.bitwise_count(inter).sum())
                + int(np.bitwise_count(union).sum())
                + int(np.bitwise_count(diff).sum()))

    t_cpu = bench_cpu(cpu_fold, 3)
    rows.append((f"2. Int/Uni/Diff fold, {R} rows", t_cpu, t_tpu))

    # ---- config 3: TopN n=100 over 1K-row matrix ------------------------
    def topn_body(x):
        counts = jnp.sum(lax.population_count(x).astype(jnp.int32), axis=1)
        top, idx = lax.top_k(counts, TOPN_K)
        return jnp.sum(top) + jnp.sum(idx.astype(jnp.int32))

    rep = rep_harness(topn_body, ())
    t_tpu = marginal_seconds(lambda r: np.asarray(rep(m, r)),
                             max(2, 50 // _SCALE), max(4, 1650 // _SCALE))

    def cpu_topn():
        counts = np.bitwise_count(m_h).sum(axis=1)
        top = np.argpartition(counts, -TOPN_K)[-TOPN_K:]
        return int(counts[top].sum())

    t_cpu = bench_cpu(cpu_topn, 3)
    rows.append((f"3. TopN n={TOPN_K}, {R} rows", t_cpu, t_tpu))

    # ---- config 4: BSI Sum over 10 planes + filter ----------------------
    planes = dev((D, W), 2)
    filt = dev((W,), 3)
    planes_h, filt_h = np.asarray(planes), np.asarray(filt)

    def bsi_body(x):
        pc = jnp.sum(lax.population_count(
            lax.bitwise_and(x, filt[None, :])).astype(jnp.int32), axis=1)
        return jnp.sum(pc)

    rep = rep_harness(bsi_body, ())
    t_tpu = marginal_seconds(lambda r: np.asarray(rep(planes, r)),
                             max(4, 2_000 // _SCALE),
                             max(8, 152_000 // _SCALE))

    def cpu_bsi():
        pc = np.bitwise_count(planes_h & filt_h).sum(axis=1)
        return int((pc.astype(np.int64) << np.arange(D)).sum())

    t_cpu = bench_cpu(cpu_bsi, 10)
    rows.append(("4. BSI Sum 10 planes", t_cpu, t_tpu))

    # ---- config 5: 64-slice Count(Intersect) ----------------------------
    a5, b5 = dev((S, W), 4), dev((S, W), 5)
    a5_h, b5_h = np.asarray(a5), np.asarray(b5)

    def c5(x):
        return jnp.sum(lax.population_count(
            lax.bitwise_and(x, b5)).astype(jnp.int32))

    rep = rep_harness(c5, ())
    t_tpu = marginal_seconds(lambda r: np.asarray(rep(a5, r)),
                             max(2, 500 // _SCALE),
                             max(4, 13_500 // _SCALE))
    t_cpu = bench_cpu(lambda: int(np.bitwise_count(a5_h & b5_h).sum()), 3)
    rows.append((f"5. {S}-slice Count(Intersect)", t_cpu, t_tpu))

    if _SCALE > 1:
        print(f"(SUITE_SCALE={_SCALE}: dimensions shrunk — smoke run, "
              "not comparable to BASELINE numbers)")
    print("| config | CPU (numpy 1-thread) | TPU (v5e-1) | speedup |")
    print("|---|---|---|---|")
    for name, cpu, tpu in rows:
        print(f"| {name} | {cpu*1e6:,.0f} us | {tpu*1e6:,.1f} us "
              f"| {cpu/tpu:,.1f}x |")


if __name__ == "__main__":
    main()
