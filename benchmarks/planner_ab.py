"""Planner A/B: worst-case-ordered queries, planner ON vs OFF.

The adaptive planner (planner.py) exists for queries WRITTEN badly:
the most selective operand last, deep Intersect chains whose running
intermediate could have gone empty three operands ago, statically
impossible BSI predicates that still launch kernels. This harness
builds the count100b sparse shape (spread-sparse compressed ARRAY
rows over many slices, snapshotted + evicted) and measures exactly
those shapes planner-on vs planner-off on the same engine:

  worstcase_qps_{on,off} / speedup   deep Intersect chain with an
                                     EMPTY operand written LAST — the
                                     short-circuit suite headline
                                     (acceptance >= 5x)
  selective_last_speedup             most-selective (tiny, non-empty)
                                     operand written last
  static_empty_speedup               out-of-range BSI predicate in an
                                     Intersect (plan-time zero, no
                                     kernel)
  optimal_overhead_pct               already-optimally-written query:
                                     planning cost on the warm memo
                                     path (gate <= 2%, plannercheck
                                     enforces it; recorded here for
                                     the perfwatch trend)

Every pair is checked bit-exact before timing; rows land in
benchmarks/ledger.jsonl via benchmarks/_ledger.py so tools/perfwatch.py
gates the trend.

Env knobs:
  PLANNER_AB_SLICES   slice count (default 32; the shape matters
                      more than the scale)
  PLANNER_AB_SECONDS  per-arm measure window (default 2)
Run: python benchmarks/planner_ab.py
"""
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

try:
    from benchmarks import _ledger
except ImportError:  # pragma: no cover — ledger is best-effort
    _ledger = None

SLICE_WIDTH = 1 << 20

SLICES = int(os.environ.get("PLANNER_AB_SLICES", "32"))
SECONDS = float(os.environ.get("PLANNER_AB_SECONDS", "2"))

# Deep Intersect chain, worst-case written order: five spread-sparse
# rows, then the EMPTY row (9) last — the planner sorts it first and
# the running intermediate kills the whole chain per slice.
Q_WORST = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
           'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3), '
           'Bitmap(frame="f", rowID=4), Bitmap(frame="f", rowID=5), '
           'Bitmap(frame="f", rowID=9)))')
# Most-selective NON-empty operand last (row 8: a handful of bits).
Q_SELECTIVE = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
               'Bitmap(frame="f", rowID=2), '
               'Bitmap(frame="f", rowID=3), '
               'Bitmap(frame="f", rowID=8)))')
# Statically impossible BSI predicate inside the chain.
Q_STATIC = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
            'Range(frame="b", v > 100000)))')
# Already optimally written: the planner has nothing to improve, so
# its warm cost is pure overhead.
Q_OPTIMAL = ('Count(Intersect(Bitmap(frame="f", rowID=8), '
             'Bitmap(frame="f", rowID=1)))')


def emit(metric, value, unit):
    print(json.dumps({"metric": metric, "value": value, "unit": unit}))
    if _ledger is not None:
        _ledger.record("planner_ab", metric, value, unit,
                       knobs={"slices": SLICES})


def build(holder, n_slices):
    """count100b sparse shape: spread-sparse ARRAY rows over the full
    slice, snapshotted + evicted so serving runs compressed. Rows 1-5
    moderately sparse, row 8 tiny, row 9 never set; a BSI frame for
    the static-empty shape."""
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    rng = np.random.default_rng(7)
    idx = holder.create_index("pa")
    idx.create_frame("f")
    idx.create_frame("b", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=1000)]))
    frame = idx.frame("f")
    t0 = time.perf_counter()
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        rows, cols = [], []
        for rid in (1, 2, 3, 4, 5):
            c = rng.choice(SLICE_WIDTH, size=500, replace=False)
            rows.extend([rid] * len(c))
            cols.extend((base + c).tolist())
        c = rng.choice(SLICE_WIDTH, size=8, replace=False)
        rows.extend([8] * len(c))
        cols.extend((base + c).tolist())
        frame.import_bits(rows, cols)
        frag = holder.fragment("pa", "f", "standard", s)
        frag.snapshot()
        frag.unload()
    idx.frame("b").set_field_value(1, "v", 10)
    emit("planner_ab_build_s", round(time.perf_counter() - t0, 1),
         f"s ({n_slices} slices)")


def qps(ex, pql, seconds):
    ex.execute("pa", pql)  # compile/plan priming
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ex.execute("pa", pql)
        n += 1
    return n / (time.perf_counter() - t0)


def ab(ex, pql, seconds):
    """(on_qps, off_qps) interleaved rounds, bit-exactness checked
    first — a speedup from a wrong answer is not a speedup."""
    pl = ex.planner
    on_res = ex.execute("pa", pql)[0]
    pl.set_config(enabled=False)
    try:
        off_res = ex.execute("pa", pql)[0]
    finally:
        pl.set_config(enabled=True)
    assert on_res == off_res, (pql, on_res, off_res)
    on = off = 0.0
    rounds = 3
    for i in range(rounds):
        if i % 2:
            a = qps(ex, pql, seconds / rounds)
            pl.set_config(enabled=False)
            b = qps(ex, pql, seconds / rounds)
            pl.set_config(enabled=True)
        else:
            pl.set_config(enabled=False)
            b = qps(ex, pql, seconds / rounds)
            pl.set_config(enabled=True)
            a = qps(ex, pql, seconds / rounds)
        on += a / rounds
        off += b / rounds
    return on, off


def main():
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder

    d = tempfile.mkdtemp(prefix="planner_ab_")
    holder = Holder(os.path.join(d, "data")).open()
    try:
        build(holder, SLICES)
        ex = Executor(holder)
        ex._result_memo_off = True  # measure the engine, not replay

        on, off = ab(ex, Q_WORST, SECONDS)
        emit("planner_ab_worstcase_qps_on", round(on, 1),
             f"q/s deep Intersect, empty operand last ({SLICES} "
             f"slices)")
        emit("planner_ab_worstcase_qps_off", round(off, 1),
             "q/s same query, planner off (written order)")
        emit("planner_ab_worstcase_speedup", round(on / off, 2),
             "planner-on / planner-off (acceptance >= 5x)")

        on, off = ab(ex, Q_SELECTIVE, SECONDS)
        emit("planner_ab_selective_last_speedup", round(on / off, 2),
             "most-selective non-empty operand written last")

        on, off = ab(ex, Q_STATIC, SECONDS)
        emit("planner_ab_static_empty_speedup", round(on / off, 2),
             "out-of-range BSI predicate: plan-time zero vs kernels")

        on, off = ab(ex, Q_OPTIMAL, SECONDS)
        emit("planner_ab_optimal_overhead_pct",
             round(max(0.0, (1 - on / off)) * 100, 2),
             "planning overhead on an already-optimal query "
             "(gate <= 2%, plannercheck)")
    finally:
        holder.close()


if __name__ == "__main__":
    main()
