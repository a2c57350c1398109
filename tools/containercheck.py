"""Compressed-container smoke: the full PQL surface must be BIT-EXACT
with ``[storage] container-formats`` on vs off (ops/containers.py —
array/run/dense classification, format-polymorphic dispatch, densify
fallback), across the block shapes that exercise every classification
branch:

- random sparse (ARRAY), run-structured (RUN), genuinely dense,
- all-empty and all-FULL rows (full collapses to one run),
- threshold-straddling rows (exactly 4096 and 4097 set bits — the
  roaring ARRAY_MAX_BITS boundary),

in both residency states (hot matrices and snapshotted+evicted, where
containers classify from the lazy decode), for Count, Intersect,
Union, Difference, Xor, TopN, and a BSI Sum. Plus the conversion path:
a mid-serve write that pushes an ARRAY row over the threshold must
flip its next served container to DENSE, count a conversion, and stay
bit-exact.

Wired into ``make test`` as ``make containercheck`` (the plancheck /
warmcheck pattern). Small and CPU-only by design.
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


SLICE_WIDTH = 1 << 20


def build(data_dir):
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.holder import Holder
    from pilosa_tpu.storage.index import FrameOptions

    holder = Holder(data_dir)
    holder.create_index("i")
    idx = holder.index("i")
    idx.create_frame("f")
    frame = idx.frame("f")
    rng = np.random.default_rng(11)

    rows = {
        1: rng.choice(SLICE_WIDTH, 800, replace=False),          # array
        2: np.concatenate([np.arange(5_000, 12_000),             # run
                           np.arange(400_000, 401_000)]),
        3: rng.choice(SLICE_WIDTH, 30_000, replace=False),       # dense
        4: np.arange(SLICE_WIDTH),                               # all-full
        5: rng.choice(SLICE_WIDTH, 4096, replace=False),         # at edge
        6: rng.choice(SLICE_WIDTH, 4097, replace=False),         # over edge
        # row 7 stays all-empty (never imported)
    }
    for rid, bits in rows.items():
        frame.import_bits([rid] * len(bits), bits.tolist())

    idx.create_frame("g", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=1000)]))
    from pilosa_tpu.executor import Executor

    ex = Executor(holder)
    cols = rng.choice(SLICE_WIDTH, 500, replace=False)
    vals = rng.integers(0, 1000, size=500)
    for c, v in zip(cols.tolist(), vals.tolist()):
        ex.execute("i", f'SetFieldValue(frame="g", columnID={c}, v={v})')
    return holder


QUERIES = [
    'Count(Bitmap(frame="f", rowID=%d))' % r for r in range(1, 8)
] + [
    'Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=3)))',
    'Count(Intersect(Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=4)))',
    'Count(Intersect(Bitmap(frame="f", rowID=5), Bitmap(frame="f", rowID=6)))',
    'Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=7)))',
    'Count(Union(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))',
    'Count(Union(Bitmap(frame="f", rowID=4), Bitmap(frame="f", rowID=7)))',
    'Count(Difference(Bitmap(frame="f", rowID=4), Bitmap(frame="f", rowID=2)))',
    'Count(Difference(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=4)))',
    'Count(Xor(Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3)))',
    'Count(Xor(Bitmap(frame="f", rowID=5), Bitmap(frame="f", rowID=6)))',
    ('Count(Intersect(Union(Bitmap(frame="f", rowID=1), '
     'Bitmap(frame="f", rowID=2)), Bitmap(frame="f", rowID=3)))'),
    'Intersect(Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=4))',
    'Union(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=6))',
    'TopN(frame="f", n=4)',
    'Sum(frame="g", field="v")',
    'Sum(Bitmap(frame="f", rowID=4), frame="g", field="v")',
]


def run_surface(ex):
    out = []
    for q in QUERIES:
        r = ex.execute("i", q)
        r = r[0] if isinstance(r, list) else r
        if hasattr(r, "columns"):
            r = tuple(r.columns().tolist())
        out.append(r)
    return out


def evict_all(holder):
    for frame_name, view in (("f", "standard"), ("g", "field_v")):
        frag = holder.fragment("i", frame_name, view, 0)
        if frag is not None:
            frag.snapshot()
            frag.unload()


def main():
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import containers

    fails = []
    d = tempfile.mkdtemp(prefix="containercheck_")
    holder = build(os.path.join(d, "data"))
    ex = Executor(holder)

    def check(label, got, want):
        for q, g, w in zip(QUERIES, got, want):
            if g != w:
                fails.append(f"{label}: {q}: formats-on {g} != off {w}")

    # Baseline: formats OFF (today's dense behavior), resident.
    containers.set_enabled(False)
    want = run_surface(ex)

    containers.set_enabled(True)
    check("resident", run_surface(ex), want)

    # Evicted: containers classify from the lazy decode; the batched
    # path declines all-compressed plans so the registered compressed
    # kernels actually serve.
    evict_all(holder)
    check("evicted", run_surface(ex), want)
    frag = holder.fragment("i", "f", "standard", 0)
    stats = frag.container_stats()
    blocks = {f: v["blocks"] for f, v in stats["formats"].items()}
    if blocks["array"] == 0 or blocks["run"] == 0:
        fails.append(f"evicted serve built no compressed blocks: {blocks}")

    # Formats off again on the evicted state (lazy dense path).
    containers.set_enabled(False)
    check("evicted-off", run_surface(ex), want)

    # Mid-serve ARRAY -> DENSE conversion: a resident row at 4090 bits
    # serves as array; a write burst pushing it past ARRAY_MAX_BITS
    # must convert its next container to dense, count the conversion,
    # and stay bit-exact.
    containers.set_enabled(True)
    rng = np.random.default_rng(23)
    bits = rng.choice(SLICE_WIDTH, 4090, replace=False)
    hf = holder.index("i").frame("f")
    hf.import_bits([50] * len(bits), bits.tolist())
    frag = holder.fragment("i", "f", "standard", 0)
    c0 = frag.row_container(50)
    if c0.fmt != "array":
        fails.append(f"pre-conversion format {c0.fmt} != array")
    before = containers.conversions_total()
    extra = np.setdiff1d(np.arange(SLICE_WIDTH), bits)[:200]
    hf.import_bits([50] * len(extra), extra.tolist())
    c1 = frag.row_container(50)
    if c1.fmt != "dense":
        fails.append(f"post-conversion format {c1.fmt} != dense")
    if containers.conversions_total() <= before:
        fails.append("conversion was not counted")
    if frag.container_stats()["conversions"] < 1:
        fails.append("fragment conversion counter did not move")
    got = ex.execute("i", 'Count(Bitmap(frame="f", rowID=50))')[0]
    containers.set_enabled(False)
    want50 = ex.execute("i", 'Count(Bitmap(frame="f", rowID=50))')[0]
    containers.set_enabled(True)
    if got != want50 or got != 4090 + len(extra):
        fails.append(f"post-conversion count {got} != {want50}")

    if fails:
        print("containercheck FAILED:")
        for f in fails:
            print("  -", f)
        return 1
    print(f"containercheck OK: {len(QUERIES)} queries x "
          f"{{resident, evicted}} x {{on, off}} bit-exact; "
          f"array->dense conversion counted "
          f"(blocks at evicted serve: {blocks})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
