"""Chemical-similarity showcase: the reference's ONLY published
benchmark anecdote, end-to-end through the real serving stack.

The reference documents a chemical-similarity deployment — 500,000
molecules with 4096-bit fingerprints ranked by Tanimoto similarity via
``TopN(..., tanimotoThreshold=N)`` — and compares it qualitatively
against a MongoDB aggregation on a 2-core laptop
(/root/reference/docs/examples.md:338-347; the Tanimoto threshold gate
is fragment.go:421-431). This script builds that exact shape (molecules
as rows, fingerprint bit positions as columns — a row-heavy /
column-narrow fragment that narrow-width rows keep at ~268 MB instead
of a 64 GB full-width dense layout) and measures the similarity query
through PQL parse → executor → ranked-cache candidates → exact
on-device Tanimoto re-query, on whatever backend is active.

Run: python benchmarks/chem_showcase.py [n_molecules]
Env: CHEM_MOLS / CHEM_FP_BITS / CHEM_BITS_PER_MOL / CHEM_THRESHOLD
     override the workload shape (defaults 500000 / 4096 / 64 / 70).
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()
# This benchmark's metric is EXECUTION latency of the fused Tanimoto
# TopN (its repeated identical queries would otherwise be served by
# the whole-result memos as dict lookups — the r3 chip comparison
# numbers predate those memos).
os.environ.setdefault("PILOSA_TPU_RESULT_MEMO", "0")


def _env_i(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


MOLS = _env_i("CHEM_MOLS", 500_000)
FP_BITS = _env_i("CHEM_FP_BITS", 4096)
BITS_PER_MOL = _env_i("CHEM_BITS_PER_MOL", 64)
THRESHOLD = _env_i("CHEM_THRESHOLD", 70)
# 10k rows/batch keeps the random-matrix + argpartition transient
# around 0.5 GB peak; import throughput is O(rows) so batch size only
# bounds memory, not speed.
IMPORT_BATCH = 10_000


def _build(holder, rng):
    """Import MOLS random fingerprints (molecule = row, fingerprint bit
    = column) through the bulk import path, in row batches."""
    import numpy as np

    from pilosa_tpu.storage.index import FrameOptions

    idx = holder.create_index("mol")
    frame = idx.create_frame("fingerprint", FrameOptions(
        cache_type="ranked", cache_size=MOLS))
    t0 = time.perf_counter()
    for lo in range(0, MOLS, IMPORT_BATCH):
        n = min(IMPORT_BATCH, MOLS - lo)
        # n rows x BITS_PER_MOL distinct columns each. argpartition of
        # a random matrix gives per-row distinct samples without a
        # Python loop, at O(n) per row and no full-sort transient.
        cols = np.argpartition(
            rng.random((n, FP_BITS), dtype=np.float32),
            BITS_PER_MOL, axis=1)[:, :BITS_PER_MOL].astype(np.uint64)
        rows = np.repeat(np.arange(lo, lo + n, dtype=np.uint64),
                         BITS_PER_MOL)
        frame.import_bits(rows, cols.reshape(-1))
    return idx, frame, time.perf_counter() - t0


def _timed(e, q, reps=15, warm=5):
    for _ in range(warm):
        e.execute("mol", q)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = e.execute("mol", q)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2] * 1000, r[0]


def main():
    import jax
    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.testing import TestHolder

    rng = np.random.default_rng(42)
    with TestHolder() as holder:
        idx, frame, load_s = _build(holder, rng)
        e = Executor(holder)
        backend = jax.default_backend()
        print(f"molecules={MOLS:,}  fp_bits={FP_BITS}  "
              f"bits/mol={BITS_PER_MOL}  backend={backend}")
        print(f"load (bulk import path): {load_s:.1f} s "
              f"({MOLS * BITS_PER_MOL / max(load_s, 1e-9) / 1e6:.2f} "
              "M bits/s)")
        probes = rng.choice(MOLS, size=min(3, MOLS), replace=False)
        print("| query | median ms | result rows |")
        print("|---|---|---|")
        for p in probes:
            q = (f'TopN(Bitmap(frame="fingerprint", rowID={p}), '
                 f'frame="fingerprint", n=100, '
                 f'tanimotoThreshold={THRESHOLD})')
            ms, r = _timed(e, q)
            print(f"| Tanimoto>={THRESHOLD} probe={p} "
                  f"| {ms:.1f} | {len(r)} |")
        # The reference anecdote's headline: similarity search over the
        # full collection. One summary line for BASELINE.md.
        q = (f'TopN(Bitmap(frame="fingerprint", rowID={probes[0]}), '
             f'frame="fingerprint", n=100, tanimotoThreshold=1)')
        ms, r = _timed(e, q)
        print(f"| Tanimoto>=1 (rank all {MOLS:,}) | {ms:.1f} "
              f"| {len(r)} |")


if __name__ == "__main__":
    if len(sys.argv) > 1:
        MOLS = int(sys.argv[1])
    main()
