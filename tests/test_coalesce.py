"""Cross-query count coalescing (executor group commit).

Concurrent count-shaped queries fuse into ONE vmapped device program
per dispatch round (the single-device answer to the reference's
goroutine-per-connection concurrency, server.go:205-217). Enabled by
default only on accelerator backends — on CPU the fused program
competes with serving threads for the same cores — so tests pin it on
via the executor's memo.
"""
import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.storage.holder import Holder


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_frame("general")
    e = Executor(holder)
    e._force_path = "batched"
    e._co_enabled_memo = True  # pin on (CPU default is off)
    # Pin tick-everything routing: these tests exercise the fused
    # tiers' correctness under accelerator dispatch economics; the
    # CPU-backend compressed-only routing has its own test.
    e._co_route_all = True
    yield holder, idx, e
    holder.close()


def _fill(frame, n_slices=6):
    rng = np.random.default_rng(9)
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        for rid, n in ((1, 120), (2, 90), (3, 60), (4, 30)):
            c = rng.choice(3000, size=n, replace=False)
            frame.import_bits([rid] * n, (base + c).tolist())


def test_concurrent_same_structure_counts_fuse(env):
    holder, idx, e = env
    frame = idx.frame("general")
    _fill(frame)

    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        (f'Count(Intersect(Bitmap(frame="general", rowID={a}), '
         f'Bitmap(frame="general", rowID={b})))')
        for a, b in [(1, 2), (1, 3), (2, 3), (1, 4), (2, 4), (3, 4)]
    ] * 4
    want = {q: serial.execute("i", q)[0] for q in set(queries)}

    results = {}
    errors = []
    barrier = threading.Barrier(len(queries))

    def run(q, i):
        try:
            barrier.wait(timeout=30)
            results[i] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q, i))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    for i, q in enumerate(queries):
        assert results[i] == want[q], (q, results[i], want[q])
    # At least one round actually fused multiple queries.
    assert e._co_stats["fused_queries"] >= 2, e._co_stats
    assert e._co_stats["max_group"] >= 2


def test_concurrent_bsi_range_counts_fuse(env):
    """Count(Range(field op value)) coalescing: the 'bits' predicate
    args are [K, depth] with NO slice axis — they must not be sharded
    like row stacks (depth is not divisible by the 8-device mesh)."""
    holder, idx, e = env
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    idx.create_frame("bsif", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=0, max=7)]))
    frame = idx.frame("bsif")
    for s in range(3):
        base = s * SLICE_WIDTH
        for i in range(50):
            frame.set_field_value(base + i, "v", (i * 3) % 8)

    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [f'Count(Range(frame="bsif", v > {x}))' for x in range(6)]
    want = {q: serial.execute("i", q)[0] for q in queries}

    results = {}
    errors = []
    barrier = threading.Barrier(len(queries))

    def run(q):
        try:
            barrier.wait(timeout=30)
            results[q] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    assert results == want


def test_concurrent_filtered_sums_fuse(env):
    """Sum(filter, frame, field) coalescing: the plane stack is shared
    across the group; per-query filter leaves gain the query axis."""
    holder, idx, e = env
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    frame = idx.frame("general")
    _fill(frame, n_slices=3)
    idx.create_frame("sums", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=0, max=300)]))
    bsi = idx.frame("sums")
    for s in range(3):
        base = s * SLICE_WIDTH
        for i in range(400):
            bsi.set_field_value(base + i, "v", (i * 7) % 300)

    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        (f'Sum(Bitmap(frame="general", rowID={r}), '
         f'frame="sums", field="v")')
        for r in (1, 2, 3, 4)
    ] * 3 + ['Sum(frame="sums", field="v")'] * 4
    want = {q: serial.execute("i", q)[0] for q in set(queries)}

    results = {}
    errors = []
    barrier = threading.Barrier(len(queries))

    def run(q, i):
        try:
            barrier.wait(timeout=30)
            results[i] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q, i))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    for i, q in enumerate(queries):
        assert results[i] == want[q], (q, results[i], want[q])
    assert e._co_stats["fused_queries"] >= 2


def test_concurrent_sums_under_bsi_ranges_fuse_with_host_bits(env):
    """Sums of one shape with different bounds fuse (PR 32): each
    "bits" slot reaches the fused program as ONE host array
    [k_pad, depth] (stacked and padded with NumPy, uploaded by the
    call), row slots stay device stacks, and every answer equals the
    single batched path's and the serial path's."""
    import jax

    holder, idx, e = env
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    frame = idx.frame("general")
    _fill(frame, n_slices=3)
    idx.create_frame("sums", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=0, max=300)]))
    bsi = idx.frame("sums")
    for s in range(3):
        base = s * SLICE_WIDTH
        for i in range(400):
            bsi.set_field_value(base + i, "v", (i * 7) % 300)
    depth = bsi.field("v").bit_depth()

    single = Executor(holder)
    single._force_path = "batched"
    single._co_enabled_memo = False
    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        (f'Sum(Intersect(Bitmap(frame="general", rowID=1), '
         f'Range(frame="sums", v >< [{lo}, {lo + 90}])), '
         f'frame="sums", field="v")')
        for lo in (5, 40, 75, 110, 145, 180)
    ]
    want = {q: single.execute("i", q)[0] for q in queries}
    assert want == {q: serial.execute("i", q)[0] for q in queries}
    assert len(set(map(tuple, want.values()))) > 1

    stacked = []
    real = e._co_stack_args

    def spy(per_query, leaves0, k_pad, n_dev):
        args = real(per_query, leaves0, k_pad, n_dev)
        stacked.append((len(per_query), k_pad, leaves0, args))
        return args

    e._co_stack_args = spy
    results = {}
    errors = []
    barrier = threading.Barrier(len(queries))

    def run(q):
        try:
            barrier.wait(timeout=30)
            results[q] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    assert results == want
    assert e._co_stats["fused_queries"] >= 2, e._co_stats
    assert max(k for k, _, _, _ in stacked) >= 2
    for k, k_pad, leaves0, args in stacked:
        kinds = [sp[0] for sp in leaves0]
        assert sorted(kinds) == ["bits", "bits", "planes", "row"]
        for kind, arg in zip(kinds, args):
            if kind == "bits":
                assert type(arg) is np.ndarray and arg.dtype == np.int32
                assert arg.shape == (k_pad, depth)
                assert not arg[k:].any()        # the bucket's filler
            else:
                assert isinstance(arg, jax.Array)
                assert arg.shape[0] == k_pad
        # Each member's own bounds, row by row.
        lows = [a for sp, a in zip(leaves0, args) if sp[0] == "bits"][0]
        assert len({tuple(r) for r in lows[:k].tolist()}) == k


def test_concurrent_filtered_minmax_fuse(env):
    """Min/Max coalescing: shared plane stack, per-query filters, the
    global bit-descent vmapped over the query axis — results equal the
    serial path, including the empty-filter (None) case."""
    holder, idx, e = env
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    frame = idx.frame("general")
    _fill(frame, n_slices=2)
    idx.create_frame("mm", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=-10, max=400)]))
    bsi = idx.frame("mm")
    for s in range(2):
        base = s * SLICE_WIDTH
        for i in range(300):
            bsi.set_field_value(base + i, "v", (i * 13) % 400 - 10)

    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        (f'{op}(Bitmap(frame="general", rowID={r}), '
         f'frame="mm", field="v")')
        for op in ("Min", "Max") for r in (1, 2, 3)
    ] * 2 + ['Min(frame="mm", field="v")', 'Max(frame="mm", field="v")']
    want = {q: serial.execute("i", q)[0] for q in set(queries)}

    results = {}
    errors = []
    barrier = threading.Barrier(len(queries))

    def run(q, i):
        try:
            barrier.wait(timeout=30)
            results[i] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q, i))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    for i, q in enumerate(queries):
        assert results[i] == want[q], (q, results[i], want[q])
    # The fused path really ran (not a silent serial fallback).
    assert e._co_stats["fused_queries"] >= 2, e._co_stats


def test_coalescer_single_query_passthrough(env):
    holder, idx, e = env
    frame = idx.frame("general")
    _fill(frame, n_slices=2)
    q = ('Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=2)))')
    first = e.execute("i", q)[0]
    assert e.execute("i", q)[0] == first
    # Lone queries never waited on a timed window; rounds ran size-1.
    assert e._co_stats["max_group"] in (0, 1) or first >= 0


def test_coalescer_stress_all_shapes_with_eviction(env):
    """All fused shapes (Count/Sum/Min/Max) under concurrent readers,
    a writer, and a fragment evictor — every read double-checked
    against the serial path (re-checked once to tolerate racing
    writes). COALESCE_STRESS_SECONDS env extends for burn-ins."""
    import os
    import random
    import time as _t

    holder, idx, e = env
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    frame = idx.frame("general")
    _fill(frame, n_slices=3)
    idx.create_frame("sb", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=0, max=400)]))
    bsi = idx.frame("sb")
    rng = np.random.default_rng(2)
    for s in range(3):
        base = s * SLICE_WIDTH
        vcols = np.unique(rng.integers(0, 5000, 200)) + base
        bsi.import_value("v", vcols.tolist(),
                         rng.integers(0, 401, len(vcols)).tolist())

    serial = Executor(holder)
    serial._force_path = "serial"
    shapes = (
        ['Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=2)))'] +
        [f'Sum(Bitmap(frame="general", rowID={r}), frame="sb", '
         f'field="v")' for r in (1, 2)] +
        ['Min(frame="sb", field="v")', 'Max(frame="sb", field="v")',
         'Count(Range(frame="sb", v > 200))'])
    seconds = float(os.environ.get("COALESCE_STRESS_SECONDS", "6"))
    stop = _t.time() + seconds
    errors = []
    # Writers and mismatch re-checks share this lock, so a re-check's
    # fused/serial pair can never straddle a racing write.
    wlock = threading.Lock()

    def reader(tid):
        prng = random.Random(tid)
        try:
            while _t.time() < stop:
                q = prng.choice(shapes)
                a = e.execute("i", q)[0]
                b = serial.execute("i", q)[0]
                if a != b:  # racing write: re-check write-free
                    with wlock:
                        a = e.execute("i", q)[0]
                        b = serial.execute("i", q)[0]
                    assert a == b, (q, a, b)
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc)[:300])

    def writer():
        prng = random.Random(99)
        try:
            while _t.time() < stop:
                col = prng.randrange(3 * SLICE_WIDTH)
                with wlock:
                    e.execute("i", f'SetBit(frame="general", '
                                   f'rowID={prng.randrange(1, 5)}, '
                                   f'columnID={col})')
                _t.sleep(0.01)
        except Exception as exc:  # noqa: BLE001
            errors.append("writer:" + repr(exc)[:300])

    def evictor():
        prng = random.Random(7)
        try:
            while _t.time() < stop:
                for fr2 in idx.frames.values():
                    for v in fr2.views.values():
                        for frag in list(v.fragments.values()):
                            if prng.random() < 0.3:
                                frag.unload()
                _t.sleep(0.15)
        except Exception as exc:  # noqa: BLE001
            errors.append("evictor:" + repr(exc)[:300])

    threads = ([threading.Thread(target=reader, args=(t,))
                for t in range(6)]
               + [threading.Thread(target=writer),
                  threading.Thread(target=evictor)])
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 120)
    assert not any(t.is_alive() for t in threads), "stress hung"
    assert not errors, errors[:5]


# ------------------------------------------------------------- PR 12
# Format-aware micro-batching: compressed container lanes, tick-based
# admission, deadline-bounded batch wait.

def _evict(frame):
    """Snapshot + unload every fragment: the 100B serving shape
    (matrices cold, rows served from the compressed container tier)."""
    for v in frame.views.values():
        for frag in list(v.fragments.values()):
            frag.snapshot()
            frag.unload()


def _count_req(e, index, pql_text, slices):
    """A _coalesced_count-shaped request dict for direct
    _co_run_fused calls — deterministic group composition, no thread
    timing."""
    from pilosa_tpu.plancache import slice_key
    from pilosa_tpu.pql import parse

    child = parse(pql_text).calls[0].children[0]
    plan, leaves = e._plan_memoized(index, child)
    assert plan is not None, pql_text
    return {"key": ("count", index, slice_key(slices), str(plan)),
            "index": index, "slices": slices, "plan": plan,
            "leaves": leaves, "out": e._CO_PENDING,
            "single": lambda: e._batched_count(index, child, slices),
            "fuse": e._co_run_fused}


def _fill_formats(frame, n_slices=2):
    """Rows covering the container-format matrix per slice: the
    4096/4097 roaring thresholds, all-empty, all-full, a RUN row, and
    sparse ARRAY rows."""
    rng = np.random.default_rng(31)
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        # row 1: exactly ARRAY_MAX_BITS scattered bits (array edge)
        c = rng.choice(SLICE_WIDTH, size=4096, replace=False)
        frame.import_bits([1] * 4096, (base + c).tolist())
        # row 2: 4097 scattered bits (dense-count edge — the probe
        # keeps it on the dense path, so the group MIXES tiers)
        c = rng.choice(SLICE_WIDTH, size=4097, replace=False)
        frame.import_bits([2] * 4097, (base + c).tolist())
        # row 3: all-full slice (one run spanning every column)
        cols = np.arange(SLICE_WIDTH, dtype=np.int64) + base
        frame.import_bits([3] * SLICE_WIDTH, cols.tolist())
        # row 4: all-empty (never written)
        # row 5: run-structured (2,000-bit run)
        start = 1000 + s * 37
        c = np.arange(start, start + 2000)
        frame.import_bits([5] * 2000, (base + c).tolist())
        # rows 6, 7: spread-sparse arrays
        for rid, n in ((6, 300), (7, 150)):
            c = rng.choice(SLICE_WIDTH, size=n, replace=False)
            frame.import_bits([rid] * n, (base + c).tolist())


def test_compressed_lane_fusion_bit_exact_all_ops(env):
    """The headline PR-12 behavior: an all-compressed group no longer
    declines — it fuses as format-bucketed container lanes, one
    launch per (op, fmt, fmt) cell, bit-exact against the serial
    compressed kernels for every count op incl. the threshold and
    empty/full rows, with zero densifications."""
    from pilosa_tpu.ops import containers

    holder, idx, e = env
    frame = idx.frame("general")
    _fill_formats(frame)
    _evict(frame)
    slices = list(range(2))
    serial = Executor(holder)
    serial._force_path = "serial"

    pairs = [(1, 5), (1, 6), (5, 6), (4, 6), (1, 4), (6, 7), (5, 7),
             (4, 5)]
    conv0 = containers.conversions_total()
    for op in ("Intersect", "Union", "Difference", "Xor"):
        queries = [
            (f'Count({op}(Bitmap(frame="general", rowID={a}), '
             f'Bitmap(frame="general", rowID={b})))')
            for a, b in pairs]
        reqs = [_count_req(e, "i", q, slices) for q in queries]
        assert e._co_run_fused(reqs) is True
        for q, req in zip(queries, reqs):
            want = serial.execute("i", q)[0]
            assert req["out"] == want, (q, req["out"], want)
    # Single-leaf group: counts come straight from the host-known
    # cardinalities — no device work at all.
    launches0 = e._co_stats["lane_launches"]
    queries = [f'Count(Bitmap(frame="general", rowID={r}))'
               for r in (1, 4, 5, 6)]
    reqs = [_count_req(e, "i", q, slices) for q in queries]
    assert e._co_run_fused(reqs) is True
    assert e._co_stats["lane_launches"] == launches0
    for q, req in zip(queries, reqs):
        assert req["out"] == serial.execute("i", q)[0], q
    assert e._co_stats["compressed_fused"] >= 4 * len(pairs) + 4
    assert e._co_stats["lane_launches"] > 0
    # The lane tier NEVER densifies — conversions stay flat.
    assert containers.conversions_total() == conv0


def test_mixed_tier_group_splits_and_stays_exact(env):
    """A group mixing dense-served plans (the 4097-count row keeps
    its dense stacks) and all-compressed plans splits across the two
    fused tiers in one round — both halves bit-exact."""
    holder, idx, e = env
    frame = idx.frame("general")
    _fill_formats(frame)
    _evict(frame)
    slices = list(range(2))
    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        'Count(Intersect(Bitmap(frame="general", rowID=2), '
        'Bitmap(frame="general", rowID=3)))',   # dense tier (4097/full)
        'Count(Intersect(Bitmap(frame="general", rowID=1), '
        'Bitmap(frame="general", rowID=6)))',   # compressed lanes
        'Count(Intersect(Bitmap(frame="general", rowID=5), '
        'Bitmap(frame="general", rowID=7)))',   # compressed lanes
    ]
    reqs = [_count_req(e, "i", q, slices) for q in queries]
    assert e._co_run_fused(reqs) is True
    for q, req in zip(queries, reqs):
        assert req["out"] == serial.execute("i", q)[0], q
    assert e._co_stats["compressed_fused"] >= 2


def test_deep_compressed_tree_densifies_within_budget(env):
    """A deep all-compressed tree (no 2-operand count identity) stages
    densely only under the per-group densify budget — each staged
    block ticks container_conversions_total; over budget it declines
    to the serial path. Bit-exact either way."""
    from pilosa_tpu.ops import containers

    holder, idx, e = env
    frame = idx.frame("general")
    _fill_formats(frame)
    _evict(frame)
    slices = list(range(2))
    serial = Executor(holder)
    serial._force_path = "serial"
    q = ('Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Union(Bitmap(frame="general", rowID=5), '
         'Bitmap(frame="general", rowID=6))))')
    want = serial.execute("i", q)[0]

    conv0 = containers.conversions_total()
    reqs = [_count_req(e, "i", q, slices) for _ in range(3)]
    assert e._co_run_fused(reqs) is True
    assert all(r["out"] == want for r in reqs)
    assert containers.conversions_total() > conv0  # churn is visible
    assert e._co_stats["densified_blocks"] > 0

    e.set_coalesce_config(densify_bytes=0)
    conv1 = containers.conversions_total()
    reqs = [_count_req(e, "i", q, slices) for _ in range(3)]
    assert e._co_run_fused(reqs) is False  # → callers serve singly
    assert containers.conversions_total() == conv1
    assert e._co_stats["declined"].get("densify_budget", 0) >= 1
    assert serial.execute("i", q)[0] == want


def test_coalesce_compressed_off_restores_decline(env):
    """[executor] coalesce-compressed=false is the pre-lane behavior:
    all-compressed groups decline wholesale (counted by reason) and
    serve singly through the serial compressed kernels."""
    holder, idx, e = env
    frame = idx.frame("general")
    _fill_formats(frame, n_slices=1)
    _evict(frame)
    e.set_coalesce_config(compressed=False)
    slices = [0]
    q = ('Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=6)))')
    reqs = [_count_req(e, "i", q, slices) for _ in range(2)]
    assert e._co_run_fused(reqs) is False
    assert all(r["out"] is e._CO_PENDING for r in reqs)
    assert e._co_stats["declined"].get("compressed_off", 0) >= 1
    assert e._co_stats["compressed_fused"] == 0


def test_fused_lane_kernels_match_numpy_reference():
    """Every (op, fmt, fmt) lane cell against a numpy popcount oracle
    over the format matrix (empty / threshold-4096 array / run /
    dense), incl. the distinct-sentinel padding rule."""
    from pilosa_tpu.ops import bitops, containers

    rng = np.random.default_rng(17)
    width32 = 1024  # 32,768-bit blocks: random picks stay scattered,
    nbits = width32 * 32  # so threshold counts classify array/dense

    def from_positions(pos):
        words = np.zeros(nbits // 64, dtype=np.uint64)
        p = np.asarray(pos, dtype=np.int64)
        if len(p):
            np.bitwise_or.at(words, p // 64,
                             np.uint64(1) << (p % 64).astype(np.uint64))
        return containers.build_container(words, width32)

    arrays = [from_positions([]),
              from_positions(rng.choice(nbits, 10, replace=False)),
              from_positions(rng.choice(nbits, 4096, replace=False))]
    runs = [from_positions(np.arange(100, 2100)),
            from_positions(np.r_[np.arange(0, 500),
                                 np.arange(4000, 6000)])]
    denses = [from_positions(np.arange(0, nbits, 2)[:4097]),
              from_positions(rng.choice(nbits, 6000, replace=False))]
    assert {c.fmt for c in arrays} == {"array"}
    assert {c.fmt for c in runs} == {"run"}
    assert {c.fmt for c in denses} == {"dense"}

    def words(c):
        return np.asarray(c.host_words64(), dtype=np.uint64)

    oracle = {
        "and": lambda a, b: a & b, "or": lambda a, b: a | b,
        "xor": lambda a, b: a ^ b, "andnot": lambda a, b: a & ~b}
    groups = {"array": arrays, "run": runs, "dense": denses}
    for fa, ca in groups.items():
        for fb, cb in groups.items():
            n = max(len(ca), len(cb))
            lane_a = [ca[i % len(ca)] for i in range(n)]
            lane_b = [cb[i % len(cb)] for i in range(n)]
            for op, fn in oracle.items():
                cell = bitops.fused_count_kernel(op, fa, fb)
                assert cell is not None, (op, fa, fb)
                got = cell(lane_a, lane_b)
                want = [int(np.bitwise_count(
                    fn(words(a), words(b))).sum())
                        for a, b in zip(lane_a, lane_b)]
                assert list(got) == want, (op, fa, fb, list(got), want)


def test_device_lane_member_cells_bit_exact(env, monkeypatch):
    """The accelerator lane path (per-(q, slice) members bucketed by
    format cell, stack_positions/stack_runs/stack_dense lanes through
    the vmapped device kernels) — forced on the CPU backend by
    disabling host-lane mode — stays bit-exact vs serial. Keeps the
    device cells covered where CI has no accelerator."""
    from pilosa_tpu.ops import containers

    monkeypatch.setattr(containers, "_LANE_HOST", False)
    holder, idx, e = env
    frame = idx.frame("general")
    _fill_formats(frame, n_slices=2)
    _evict(frame)
    slices = list(range(2))
    serial = Executor(holder)
    serial._force_path = "serial"
    queries = [
        (f'Count({op}(Bitmap(frame="general", rowID={a}), '
         f'Bitmap(frame="general", rowID={b})))')
        for op in ("Intersect", "Union", "Difference", "Xor")
        for a, b in ((1, 5), (5, 6), (4, 6), (1, 6))]
    for op_queries in (queries[:4], queries[4:8], queries[8:12],
                       queries[12:]):
        reqs = [_count_req(e, "i", q, slices) for q in op_queries]
        assert e._co_run_fused(reqs) is True
        for q, req in zip(op_queries, reqs):
            assert req["out"] == serial.execute("i", q)[0], q
    assert e._co_stats["lane_launches"] > 0


def test_device_lane_kernels_direct():
    """The jitted vmapped lane kernels themselves (what accelerators
    run) against the same numpy oracle — executed on the CPU backend
    explicitly, since _fused_and_counts would route around them
    there."""
    from pilosa_tpu.ops import containers

    rng = np.random.default_rng(4)
    width32 = 512  # 16,384 bits: room for a 4,097-alternating dense row
    nbits = width32 * 32

    def build(pos):
        words = np.zeros(nbits // 64, dtype=np.uint64)
        p = np.asarray(pos, dtype=np.int64)
        if len(p):
            np.bitwise_or.at(words, p // 64,
                             np.uint64(1) << (p % 64).astype(np.uint64))
        return containers.build_container(words, width32)

    arrays = [build(rng.choice(nbits, n, replace=False))
              for n in (0, 7, 300)]
    runs = [build(np.arange(50, 1550)), build(np.arange(3000, 3800))]
    denses = [build(np.arange(0, nbits, 2)[:4097])]
    assert all(c.fmt == "run" for c in runs)
    assert denses[0].fmt == "dense"

    def inter(a, b):
        wa = np.asarray(a.host_words64(), dtype=np.uint64)
        wb = np.asarray(b.host_words64(), dtype=np.uint64)
        return int(np.bitwise_count(wa & wb).sum())

    la = [arrays[i % 3] for i in range(4)]
    lb = [arrays[(i + 1) % 3] for i in range(4)]
    got = containers.fused_count_array_array(
        containers.stack_positions(la),
        containers.stack_positions(lb, sentinel_off=1))
    assert [int(v) for v in got] == [inter(a, b)
                                     for a, b in zip(la, lb)]
    lr = [runs[i % 2] for i in range(4)]
    s, ends = containers.stack_runs(lr)
    got = containers.fused_count_array_run(
        containers.stack_positions(la), s, ends)
    assert [int(v) for v in got] == [inter(a, b)
                                     for a, b in zip(la, lr)]
    ld = [denses[0]] * 4
    got = containers.fused_count_array_dense(
        containers.stack_positions(la), containers.stack_dense(ld))
    assert [int(v) for v in got] == [inter(a, b)
                                     for a, b in zip(la, ld)]
    got = containers.fused_count_run_dense(
        s, ends, containers.stack_dense(ld))
    assert [int(v) for v in got] == [inter(a, b)
                                     for a, b in zip(lr, ld)]
    got = containers.fused_count_dense_dense(
        containers.stack_dense(ld), containers.stack_dense(ld))
    assert [int(v) for v in got] == [inter(a, b)
                                     for a, b in zip(ld, ld)]


def test_minmax_kpad_filler_lanes_inert(env):
    """k_pad zero-filled filler lanes must not perturb Min/Max: a
    3-query group pads to k_pad=4, and the zeroed 4th lane would
    read value 0 — outside [field.min, max] here — if it leaked into
    any real query's descent."""
    holder, idx, e = env
    from pilosa_tpu.pql import parse
    from pilosa_tpu.storage.frame import Field
    from pilosa_tpu.storage.index import FrameOptions

    frame = idx.frame("general")
    _fill(frame, n_slices=2)
    idx.create_frame("mmk", FrameOptions(
        range_enabled=True,
        fields=[Field(name="v", type="int", min=50, max=400)]))
    bsi = idx.frame("mmk")
    for s in range(2):
        base = s * SLICE_WIDTH
        for i in range(200):
            bsi.set_field_value(base + i, "v", 50 + (i * 7) % 350)

    serial = Executor(holder)
    serial._force_path = "serial"
    slices = list(range(2))
    for op, find_max in (("Min", False), ("Max", True)):
        queries = [
            (f'{op}(Bitmap(frame="general", rowID={r}), '
             f'frame="mmk", field="v")') for r in (1, 2, 3)]
        reqs = []
        for q in queries:
            call = parse(q).calls[0]
            resolved = e._co_bsi_resolve("i", call)
            assert resolved is not None
            fname, field_name, field, depth, plan, leaves = resolved
            reqs.append({
                "index": "i", "slices": slices, "plan": plan,
                "leaves": leaves, "field": field, "depth": depth,
                "frame_name": fname, "field_name": field_name,
                "find_max": find_max, "out": e._CO_PENDING,
                "single": lambda c=call: e._batched_min_max(
                    "i", c, slices, find_max),
                "fuse": e._co_run_fused_minmax})
        assert e._co_run_fused_minmax(reqs) is True
        for q, req in zip(queries, reqs):
            want = serial.execute("i", q)[0]
            assert req["out"] == want, (q, req["out"], want)
            # Filler leakage would surface as value 0 (< field.min).
            assert req["out"].sum >= 50, req["out"]


def test_tick_admission_priority_order(env):
    """Admission order when the tick truncates: interactive
    coalescees admit ahead of batch/ingest ones (FIFO within a
    class), the leader's own request always admits, leftovers stay
    queued for the next tick."""
    from pilosa_tpu import qos

    holder, idx, e = env
    e._co_config_memo = (0.0, 3, True, 0)  # max_group=3, no wait
    mk = (lambda prio, tag: {
        "key": ("k", tag), "prio": prio, "deadline": None,
        "out": e._CO_PENDING, "single": lambda: tag,
        "fuse": lambda reqs: False})
    waiters = [mk(qos.PRIO_BATCH, "b0"), mk(qos.PRIO_INTERACTIVE, "i0"),
               mk(qos.PRIO_INGEST, "g0"), mk(qos.PRIO_INTERACTIVE, "i1"),
               mk(qos.PRIO_BATCH, "b1")]
    own = mk(qos.PRIO_BATCH, "own")
    with e._co_mu:
        e._co_leader = True
        e._co_pending = waiters + [own]
        batch = e._co_admit_locked(own)
        leftovers = list(e._co_pending)
        e._co_pending = []
        e._co_leader = False
    tags = [r["key"][1] for r in batch]
    # Both interactive waiters admitted (never parked behind batch),
    # sorted ahead of the batch-priority leader; FIFO within class.
    assert tags == ["i0", "i1", "own"], tags
    assert [r["key"][1] for r in leftovers] == ["b0", "g0", "b1"]


def test_tick_window_accumulates_one_round(env):
    """coalesce-max-wait-us holds the window open so aligned arrivals
    land in ONE tick (the 1-core CPU shape: without the window each
    query finishes inside its GIL slice and batches never form)."""
    holder, idx, e = env
    frame = idx.frame("general")
    _fill(frame, n_slices=2)
    e.set_coalesce_config(max_wait_us=60_000)
    queries = [
        (f'Count(Intersect(Bitmap(frame="general", rowID={a}), '
         f'Bitmap(frame="general", rowID={b})))')
        for a, b in [(1, 2), (1, 3), (2, 3), (1, 4)]]
    serial = Executor(holder)
    serial._force_path = "serial"
    want = {q: serial.execute("i", q)[0] for q in queries}
    results, errors = {}, []
    barrier = threading.Barrier(len(queries))

    def run(q, i):
        try:
            barrier.wait(timeout=30)
            results[i] = e.execute("i", q)[0]
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=run, args=(q, i))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    for i, q in enumerate(queries):
        assert results[i] == want[q], (q, results[i], want[q])
    assert e._co_stats["max_group"] >= 2, e._co_stats


@pytest.mark.faults
def test_deadline_expiry_during_batch_wait(env):
    """An expired coalescee must fail fast (the handler maps
    qos.DeadlineExceeded to 504) WITHOUT poisoning or stalling the
    rest of the group — the leader is pinned slow via the real
    executor.slice.delay failpoint, the parked follower's bounded
    wait wakes at ITS deadline (not the leader's completion), and
    the tick machinery keeps serving afterward."""
    import time as _t

    from pilosa_tpu import faults, qos

    holder, idx, e = env
    reg = faults.enable()
    try:
        reg.configure("executor.slice.delay=delay(0.15)")
        started = threading.Event()

        def leader_single():
            started.set()
            # The REAL injection point: the serial per-slice loop.
            return e._serial_exec(list(range(4)), lambda s: 1,
                                  lambda p, v: (p or 0) + v)

        results, follow = {}, {}

        def lead():
            results["lead"] = e._co_submit({
                "key": ("lead",), "prio": qos.PRIO_INTERACTIVE,
                "deadline": None, "out": e._CO_PENDING,
                "single": leader_single, "fuse": lambda reqs: False})

        t1 = threading.Thread(target=lead)
        t1.start()
        assert started.wait(10)
        _t.sleep(0.03)  # the leader is now inside its slow serve

        def follower():
            t0 = _t.monotonic()
            try:
                follow["out"] = e._co_submit({
                    "key": ("follow",), "prio": qos.PRIO_INTERACTIVE,
                    "deadline": _t.monotonic() + 0.1,
                    "out": e._CO_PENDING, "single": lambda: 7,
                    "fuse": lambda reqs: False})
            except qos.DeadlineExceeded:
                follow["expired_after"] = _t.monotonic() - t0

        t2 = threading.Thread(target=follower)
        t2.start()
        t2.join(timeout=10)
        assert not t2.is_alive(), "follower stalled past its deadline"
        # Expired at its own deadline, NOT after the leader's ~0.6 s.
        assert follow.get("expired_after") is not None, follow
        assert follow["expired_after"] < 0.45, follow
        t1.join(timeout=10)
        assert results["lead"] == 4  # the group was not poisoned
        # And the machinery still serves the next tick.
        assert e._co_submit({
            "key": ("after",), "prio": qos.PRIO_INTERACTIVE,
            "deadline": None, "out": e._CO_PENDING,
            "single": lambda: 9, "fuse": lambda reqs: False}) == 9
        assert e._co_expired >= 1
        assert e.coalesce_metrics()["expired_waits_total"] >= 1
    finally:
        faults.disable()


def test_cpu_routing_dense_bypasses_tick(env):
    """CPU-backend routing: dense-plan counts keep their direct
    single-dispatch path (parking them behind a tick on shared cores
    only adds latency — measured 3.4x slower), compressed-tier plans
    enter the tick. Both bit-exact; BSI plans always tick."""
    holder, idx, e = env
    e._co_route_all = False  # the real CPU routing under test
    frame = idx.frame("general")
    _fill_formats(frame, n_slices=2)

    serial = Executor(holder)
    serial._force_path = "serial"
    q = ('Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=6)))')
    want = serial.execute("i", q)[0]
    # Resident fragments → dense probe → direct path, no tick state.
    assert e.execute("i", q)[0] == want
    assert e._co_stats["rounds"] == 0
    # Evicted → compressed probe → the tick (and the lane tier).
    _evict(frame)
    assert e.execute("i", q)[0] == want
    assert e._co_stats["rounds"] >= 1
    assert e._co_stats["compressed_fused"] >= 0  # group of 1 → single
    # coalesce-compressed=false restores tick-everything (pre-PR).
    e.set_coalesce_config(compressed=False)
    rounds = e._co_stats["rounds"]
    assert e.execute("i", q)[0] == want
    assert e._co_stats["rounds"] == rounds + 1


def test_coalesce_config_surface(tmp_path):
    """[executor] coalesce knobs: env overrides, validation, TOML
    round trip, and the executor-side resolution order (explicit
    set_coalesce_config wins over env/defaults)."""
    from pilosa_tpu.config import Config

    cfg = Config.load(env={
        "PILOSA_COALESCE_MAX_WAIT_US": "250",
        "PILOSA_COALESCE_MAX_GROUP": "8",
        "PILOSA_COALESCE_COMPRESSED": "no",
        "PILOSA_COALESCE_DENSIFY_BYTES": "1024",
    })
    assert cfg.executor["coalesce-max-wait-us"] == 250
    assert cfg.executor["coalesce-max-group"] == 8
    assert cfg.executor["coalesce-compressed"] is False
    assert cfg.executor["coalesce-densify-bytes"] == 1024
    # Malformed env keeps defaults instead of crashing boot.
    cfg2 = Config.load(env={"PILOSA_COALESCE_MAX_WAIT_US": "bogus"})
    assert cfg2.executor["coalesce-max-wait-us"] == 0
    # TOML round trip.
    p = tmp_path / "c.toml"
    p.write_text(cfg.to_toml())
    cfg3 = Config.load(path=str(p), env={})
    assert cfg3.executor["coalesce-max-wait-us"] == 250
    assert cfg3.executor["coalesce-compressed"] is False
    for bad in ({"coalesce-max-wait-us": -1},
                {"coalesce-max-group": 0},
                {"coalesce-compressed": "yes"},
                {"coalesce-densify-bytes": -5}):
        with pytest.raises(ValueError):
            Config.load(env={}, overrides={"executor": bad})


def test_executor_coalesce_config_resolution(env, monkeypatch):
    holder, _, _ = env
    monkeypatch.setenv("PILOSA_COALESCE_MAX_WAIT_US", "500")
    monkeypatch.setenv("PILOSA_COALESCE_MAX_GROUP", "5")
    monkeypatch.setenv("PILOSA_COALESCE_COMPRESSED", "off")
    e2 = Executor(holder)
    wait_s, group, comp, _ = e2._co_config()
    assert (wait_s, group, comp) == (0.0005, 5, False)
    e2.set_coalesce_config(max_group=9, compressed=True)
    wait_s, group, comp, _ = e2._co_config()
    assert (wait_s, group, comp) == (0.0005, 9, True)


def test_coalesce_metrics_and_debug_surfaces(env):
    """pilosa_coalesce_* renders as a first-class group (declines
    tagged by reason) and the group-size histogram family records
    real fused-group sizes; coalesce_snapshot carries the knobs."""
    from pilosa_tpu import stats as stats_mod

    holder, idx, e = env
    hset = stats_mod.HistogramSet()
    e.set_histograms(hset)
    frame = idx.frame("general")
    _fill_formats(frame, n_slices=1)
    _evict(frame)
    e.set_coalesce_config(compressed=False)
    q = ('Count(Intersect(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=6)))')
    reqs = [_count_req(e, "i", q, [0]) for _ in range(2)]
    assert e._co_run_fused(reqs) is False  # → declined_total{reason=}
    e._co_run([_count_req(e, "i", q, [0]) for _ in range(2)])

    text = stats_mod.prometheus_exposition(
        {}, [("coalesce", e.coalesce_metrics())], histograms=hset)
    assert "pilosa_coalesce_rounds_total" in text
    assert "pilosa_coalesce_fused_queries_total" in text
    assert "pilosa_coalesce_lane_launches_total" in text
    assert ('pilosa_coalesce_declined_total{reason="compressed_off"}'
            in text)
    assert "pilosa_coalesce_group_size_bucket" in text
    snap = e.coalesce_snapshot()
    assert snap["maxGroup"] >= 1 and "declined" in snap
    assert snap["compressed"] is False


def test_coalescer_mixed_with_writes(env):
    """Writes interleaved with fused counts stay correct (stack
    version tokens invalidate mid-stream)."""
    holder, idx, e = env
    frame = idx.frame("general")
    _fill(frame, n_slices=3)
    q = ('Count(Union(Bitmap(frame="general", rowID=1), '
         'Bitmap(frame="general", rowID=2)))')
    base = e.execute("i", q)[0]
    errors = []
    done = threading.Event()

    def reader():
        try:
            while not done.is_set():
                v = e.execute("i", q)[0]
                assert v >= base
        except Exception as exc:  # noqa: BLE001
            errors.append(repr(exc))

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for k in range(40):
        e.execute("i", f'SetBit(frame="general", rowID=1, '
                       f'columnID={3100 + k})')
    done.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert e.execute("i", q)[0] == base + 40
