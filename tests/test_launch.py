"""Every launch site of the served path goes through ``bitops.launch``:
untraced it makes no ``Span``; traced it emits the names, tags and
nesting each site has always had; and the first launch of a new program
counts one compile in ``/debug/kernels`` (the observatory's table),
the second none."""
from types import SimpleNamespace

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, tracing
from pilosa_tpu.executor import Executor
from pilosa_tpu.observe import kerneltime as kt
from pilosa_tpu.ops import bitops, containers, ingest
from pilosa_tpu.ops import topn as topn_ops
from pilosa_tpu.parallel.mesh import MeshQueryEngine, make_mesh
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions

# Widths no other test compiles, so a module-level program is new here.
W = 2999


@pytest.fixture(scope="module")
def holder(tmp_path_factory):
    """Frame ``f``: rows 1 and 2 dense and rows 3 and 4 sparse over two
    slices; frame ``g``: an int field ``v``."""
    h = Holder(str(tmp_path_factory.mktemp("d"))).open()
    idx = h.create_index("i")
    idx.create_frame("f")
    idx.create_frame("g", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=100)]))
    rng = np.random.default_rng(5)
    for s in range(2):
        for rid, n in ((1, 9000), (2, 9000), (3, 40), (4, 40)):
            cols = s * SLICE_WIDTH + rng.choice(20000, n, replace=False)
            idx.frame("f").import_bits([rid] * n, cols.tolist())
        for c in range(8):
            idx.frame("g").set_field_value(s * SLICE_WIDTH + c, "v", c)
    yield h
    h.close()


@pytest.fixture
def obs():
    prev = kt.ACTIVE
    yield kt.enable()
    kt.ACTIVE = prev


def _co_requests(ex, pql, n=2):
    child = parse(pql).calls[0].children[0]
    plan, leaves = ex._plan_memoized("i", child)
    return [{"index": "i", "slices": [0, 1], "plan": plan,
             "leaves": leaves, "out": ex._CO_PENDING} for _ in range(n)]


def _dense(seed, n=1):
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 1 << 32, size=(n, W), dtype=np.uint64)
    return words.astype(np.uint32)


def _lane(env):
    a, b = (containers.dense_container(w, W, int(np.bitwise_count(w).sum()))
            for w in _dense(1, 2))
    cell = bitops.fused_count_kernel("and", "dense", "dense")
    assert cell([a], [b]).tolist() == [int(np.bitwise_count(
        a.words & b.words).sum())]


def _serial_cell(env):
    words = _dense(2)[0]
    arr = containers.Container("array", W, 3,
                               positions=np.array([1, 5, 9], np.int32))
    want = sum(int(words[p >> 5] >> (p & 31) & 1) for p in (1, 5, 9))
    assert bitops.dispatch_count(
        "and", arr, containers.dense_container(words, W, 0)) == want


def _mesh(env):
    engine = env.engine
    rows = _dense(3, 8)
    got = int(engine.tree_count(
        ("leaf", 0), (engine.shard_rows(rows),), ("slice",), 5))
    assert got == int(np.bitwise_count(rows[:5]).sum())


def _ingest(env):
    words, counts, _ = ingest.pack_classify(
        np.array([0, 0, 1], np.int32), np.array([3, 4, 70], np.int32),
        2, W)
    assert counts.tolist() == [2, 1] and words.shape == (2, W)


def _fetch(fn, op, *args):
    def drive(env):
        m = _dense(4, 4)
        assert topn_ops.fetch_counts(fn, m, *args, op=op).shape == (4,)
    return drive


def _served(pql):
    def drive(env):
        assert env.ex.execute("i", pql)[0] is not None
    return drive


def _co_dense(env):
    reqs = _co_requests(env.ex, 'Count(Intersect(Bitmap(frame="f", '
                                'rowID=1), Bitmap(frame="f", rowID=2)))')
    assert env.ex._co_run_fused(reqs)
    assert reqs[0]["out"] == reqs[1]["out"] > 0


def _co_host_lanes(env):
    """On the CPU backend a compressed group's lane is a host pass: it
    has no program, so it never compiles."""
    reqs = _co_requests(env.ex, 'Count(Intersect(Bitmap(frame="f", '
                                'rowID=3), Bitmap(frame="f", rowID=4)))')
    assert env.ex._co_run_fused(reqs)
    assert reqs[0]["out"] == reqs[1]["out"]


DISPATCH = ("kernel.dispatch", ("devices", "program"), None)
WAIT, FETCH = ("kernel.wait", (), None), ("kernel.fetch", (), None)


def _under(parent, *spans):
    return tuple((name, tags, parent) for name, tags, _ in spans)


# id: (what drives the site, the op it notes, compiles of its first launch,
# the launch spans traced: (name, tag keys, parent launch span))
SITES = {
    "bitops": (lambda env: bitops.count_and(*_dense(6, 2)), "count_and", 1,
               (("kernel:count_and", ("first_compile",), None),)),
    "count_batched": (
        _served('Count(Intersect(Bitmap(frame="f", rowID=1), '
                'Bitmap(frame="f", rowID=3)))'),
        "count_batched", 1,
        (("kernel:count_batched",
          ("first_compile", "slices", "warm_compiled", "width32"), None),
         ("kernel.fn", ("compile",), "kernel:count_batched"))
        + _under("kernel:count_batched", DISPATCH, WAIT, FETCH)),
    "sum_batched": (
        _served('Sum(Bitmap(frame="f", rowID=1), frame="g", field="v")'),
        "sum_batched", 1,
        (("kernel:sum_batched", ("slices", "width32"), None),
         ("kernel.fn", ("compile",), "kernel:sum_batched"))
        + _under("kernel:sum_batched", DISPATCH, WAIT, FETCH)),
    "topn_candidates": (
        _served('TopN(frame="f", ids=[1, 2, 3])'),
        "topn_rows", 1, (DISPATCH, WAIT, FETCH)),
    "fetch_counts": (
        _fetch(topn_ops.tanimoto_masked_counts_at, "topn_tanimoto_frag",
               np.int32(2), np.full(4, 9, np.int32), np.int32(50)),
        "topn_tanimoto_frag", 1,
        (("top.kernel", ("program", "scanned"), None),
         ("top.wait", (), None), ("top.fetch", (), None))),
    "fetch_counts_bitops": (
        _fetch(bitops.count_and_rows_at, None, np.int32(2)),
        "count_and_rows_at", 1,
        (("top.kernel", ("program", "scanned"), None),
         ("top.wait", (), None), ("top.fetch", (), None))),
    "bitmap_batched": (
        _served('Intersect(Bitmap(frame="f", rowID=1), '
                'Bitmap(frame="f", rowID=2))'), "bitmap_batched", 1, ()),
    "minmax_batched": (
        _served('Max(Bitmap(frame="f", rowID=1), frame="g", field="v")'),
        "minmax_batched", 1, ()),
    "coalesce_count_fused": (_co_dense, "coalesce_count_fused", 1, ()),
    "coalesce_lanes_host": (_co_host_lanes, "fused_count_and", 0, ()),
    "lane": (_lane, "fused_count_and", 1, ()),
    "serial_cell": (_serial_cell, "count_and", 1, ()),
    "mesh": (_mesh, "mesh_count", 1, ()),
    "ingest": (_ingest, "ingest.pack_classify", 1, ()),
}


def _compiles(obs, op):
    return sum(r["compileCalls"] for r in obs.snapshot()["cells"]
               if r["op"] == op)


def _launch_spans(trace):
    """(name, tag keys, parent's name where that is a launch span too)
    of the spans a launch makes, sorted."""
    spans = [s for s in trace["spans"]
             if s["name"].startswith(("kernel", "top."))]
    names = {s["spanId"]: s["name"] for s in spans}
    return sorted((s["name"], tuple(sorted(s["tags"])),
                   names.get(s["parentId"])) for s in spans)


@pytest.mark.parametrize("site", sorted(SITES))
def test_a_launch_site(holder, obs, monkeypatch, site):
    drive, op, first, want = SITES[site]
    env = SimpleNamespace(ex=Executor(holder),
                          engine=MeshQueryEngine(make_mesh()))
    env.ex._force_path = "batched"
    made = []
    real_init = tracing.Span.__init__

    def spy(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    with monkeypatch.context() as patch:
        patch.setattr(tracing.Span, "__init__", spy)
        drive(env)
    assert made == []
    assert _compiles(obs, op) == first
    tracer = tracing.Tracer(ring_size=2)
    with tracer.start("test"):
        drive(env)
    assert _compiles(obs, op) == first
    assert _launch_spans(tracer.recent(1)[0]) == sorted(want)
