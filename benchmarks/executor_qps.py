"""End-to-end executor benchmark: the full serving path (PQL parse →
executor → batched mesh kernels) rather than raw kernels.

Measures Count / compound-Bitmap / Sum / TopN over a multi-slice index,
batched fast path vs forced-serial per-slice path, on whatever backend
JAX reports (run it through the chip tool for a device number).

Run: python benchmarks/executor_qps.py [n_slices]
"""
import os
import sys
import time
from datetime import datetime

T_STAMP = datetime(2017, 6, 1)  # all time-quantum bits share one day

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.utils import compilecache  # noqa: E402

compilecache.enable()
# This benchmark compares EXECUTION paths (batched vs serial); the
# whole-result memos would otherwise serve every repeated rep from a
# host value and measure nothing.
os.environ.setdefault("PILOSA_TPU_RESULT_MEMO", "0")


def main(n_slices=64):
    from pilosa_tpu.testing import TestHolder

    with TestHolder() as holder:
        _run(holder, n_slices)


def _run(holder, n_slices):
    import jax
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    idx = holder.create_index("i")
    fr = idx.create_frame("f")
    bsi = idx.create_frame("g", FrameOptions(range_enabled=True))
    bsi.create_field(Field("v", min=0, max=1000))
    tq = idx.create_frame("t", FrameOptions(time_quantum="YMD"))
    rng = np.random.default_rng(0)
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        for r in (1, 2, 3):
            cols = rng.choice(SLICE_WIDTH, 5000, replace=False) + base
            fr.import_bits([r] * len(cols), cols.tolist())
        vcols = rng.choice(SLICE_WIDTH, 1000, replace=False) + base
        bsi.import_value("v", vcols.tolist(),
                         rng.integers(0, 1001, size=1000).tolist())
        tcols = (rng.choice(SLICE_WIDTH, 500, replace=False) + base).tolist()
        tq.import_bits([1] * len(tcols), tcols,
                       timestamps=[T_STAMP] * len(tcols))
    e = Executor(holder)

    queries = {
        "count_intersect": ('Count(Intersect(Bitmap(frame="f", rowID=1), '
                            'Bitmap(frame="f", rowID=2)))'),
        "union_materialize": ('Union(Bitmap(frame="f", rowID=1), '
                              'Bitmap(frame="f", rowID=2), '
                              'Bitmap(frame="f", rowID=3))'),
        "sum": 'Sum(frame="g", field="v")',
        "topn": 'TopN(frame="f", n=3)',
        "topn_src": ('TopN(Bitmap(frame="f", rowID=1), frame="f", n=3)'),
        "topn_tanimoto": ('TopN(Bitmap(frame="f", rowID=1), frame="f", '
                          'n=3, tanimotoThreshold=1)'),
        "min": 'Min(frame="g", field="v")',
        "max": 'Max(frame="g", field="v")',
        "range_time": ('Count(Range(frame="t", rowID=1, '
                       'start="2017-05-30T00:00", end="2017-06-03T00:00"))'),
        "range_bsi": 'Count(Range(frame="g", v >< [200, 700]))',
    }

    try:
        default_reps = max(1, int(os.environ.get("PILOSA_QPS_REPS", "20")))
    except ValueError:
        default_reps = 20

    def timed(q, reps=default_reps):
        """Median per-query ms for (auto, forced-serial), reps
        INTERLEAVED so machine-load drift hits both columns equally.
        _force_path='serial' bypasses the cost model entirely, so the
        serial reps never pollute its statistics."""
        for _ in range(14):  # warm compile + caches + path cost model
            e.execute("i", q)
        e._force_path = "serial"
        for _ in range(2):   # warm serial-side host caches
            e.execute("i", q)
        auto, serial = [], []
        for _ in range(reps):
            e._force_path = None
            t0 = time.perf_counter()
            e.execute("i", q)
            auto.append(time.perf_counter() - t0)
            e._force_path = "serial"
            t0 = time.perf_counter()
            e.execute("i", q)
            serial.append(time.perf_counter() - t0)
        e._force_path = None
        auto.sort()
        serial.sort()
        return (auto[len(auto) // 2] * 1000,
                serial[len(serial) // 2] * 1000)

    print(f"n_slices={n_slices}  devices={len(jax.devices())} "
          f"({jax.devices()[0].platform})")
    print(f"{'query':20s} {'auto ms':>11s} {'serial ms':>10s} {'x':>6s}")
    for name, q in queries.items():
        fast, slow = timed(q)
        print(f"{name:20s} {fast:11.2f} {slow:10.2f} {slow / fast:6.1f}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 64)
