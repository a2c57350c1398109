"""Collective data plane (cluster/meshplane.py) on the 8-device
virtual CPU mesh: multi-node queries served as ONE shard_map + psum
program must be bit-exact against the serial executor oracle —
including device-count padding, all-empty rows, and every fallback
rule (resize transition, membership, budget, unsupported shapes).

These are the load-bearing graduates of the parallel/ suite: the
in-process two-node cluster shares one JAX runtime and one device
set, which is exactly the pod topology the plane models."""
import tempfile

import jax
import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.cluster.cluster import Cluster, ModHasher, Node
from pilosa_tpu.cluster.meshplane import DECLINED, MeshPlane
from pilosa_tpu.executor import Executor
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions


class BoomClient:
    """Any HTTP use fails the test: the collective path must serve."""

    breakers = None

    def __getattr__(self, name):
        raise AssertionError(f"HTTP client used: {name}")


class LoopbackClient:
    """In-process 'HTTP': remote subqueries run on the peer's executor
    directly, counted — tests assert the collective path kept the
    count at zero (or that the fallback actually engaged)."""

    breakers = None

    def __init__(self):
        self.executors = {}
        self.calls = 0

    def execute_query(self, node, index, query, slices=None,
                      remote=False, **kw):
        from pilosa_tpu.executor import ExecOptions

        self.calls += 1
        return self.executors[node.host].execute(
            index, query, slices=slices, opt=ExecOptions(remote=True))


class MeshRig:
    """Two-node in-process 'pod': per-host holders holding only their
    owned slices, registered mesh planes, a coordinator executor with
    a counting loopback client, and a single-holder serial oracle."""

    def __init__(self, tmp, group, n_slices=13, seed=7, bsi=True):
        self.n_slices = n_slices
        self.cluster = Cluster(nodes=[Node("a"), Node("b")],
                               hasher=ModHasher())
        self.holders = {"a": Holder(f"{tmp}/a").open(),
                        "b": Holder(f"{tmp}/b").open()}
        self.oracle_holder = Holder(f"{tmp}/o").open()
        for h in self._all_holders():
            idx = h.create_index("i")
            idx.create_frame("f")
            if bsi:
                idx.create_frame("g", FrameOptions(
                    range_enabled=True,
                    fields=[Field("v", min=-5, max=200)]))
        rng = np.random.default_rng(seed)
        shared = rng.choice(SLICE_WIDTH, 400, replace=False)
        for s in range(n_slices):
            owner = self.cluster.fragment_nodes("i", s)[0].host
            base = s * SLICE_WIDTH
            # Overlapping row sets so Intersect/Difference/Xor are
            # non-trivial; row 4 stays all-empty everywhere.
            for r, take in ((1, 300), (2, 250), (3, 120)):
                cols = (np.concatenate([
                    shared[:take // 2],
                    rng.choice(SLICE_WIDTH, take, replace=False),
                ]) + base).tolist()
                self._import(owner, "f", r, cols)
            if bsi:
                vcols = (rng.choice(SLICE_WIDTH, 60, replace=False)
                         + base).tolist()
                vals = rng.integers(-5, 201, size=60).tolist()
                self.holders[owner].index("i").frame("g").import_value(
                    "v", vcols, vals)
                self.oracle_holder.index("i").frame("g").import_value(
                    "v", vcols, vals)
        for h in self._all_holders():
            h.index("i").set_remote_max_slice(n_slices - 1)
        self.client = LoopbackClient()
        self.ex = Executor(self.holders["a"], cluster=self.cluster,
                           host="a", client=self.client)
        ex_b = Executor(self.holders["b"], cluster=self.cluster,
                        host="b", client=self.client)
        self.client.executors = {"a": self.ex, "b": ex_b}
        self.plane_a = MeshPlane(self.holders["a"], self.cluster, "a",
                                 group=group).register()
        self.plane_b = MeshPlane(self.holders["b"], self.cluster, "b",
                                 group=group).register()
        self.ex.meshplane = self.plane_a
        self.oracle = Executor(self.oracle_holder)
        # The ORACLE is the serial per-slice path — the batched arms
        # are disabled so the comparison target is the reference fold,
        # not another fused program.
        for attr in ("_batched_count", "_batched_sum",
                     "_batched_min_max", "_batched_topn_ids",
                     "_batched_topn_phase1", "_batched_bitmap"):
            setattr(self.oracle, attr, lambda *a, **k: None)

    def _all_holders(self):
        return list(self.holders.values()) + [self.oracle_holder]

    def _import(self, owner, frame, row, cols):
        self.holders[owner].index("i").frame(frame).import_bits(
            [row] * len(cols), cols)
        self.oracle_holder.index("i").frame(frame).import_bits(
            [row] * len(cols), cols)

    def check(self, query):
        got = self.ex.execute("i", query)
        want = self.oracle.execute("i", query)
        assert got == want, (query, got, want)
        return got[0]

    def close(self):
        self.plane_a.close()
        self.plane_b.close()
        for h in self._all_holders():
            h.close()


@pytest.fixture
def rig(tmp_path, request):
    r = MeshRig(str(tmp_path), group=f"t-{request.node.name}")
    yield r
    r.close()


def _count_call(query):
    from pilosa_tpu.pql import parse

    return parse(query).calls[0]


def test_collective_count_trees_match_serial_oracle(rig):
    """Every boolean-tree Count shape over a padded slice set (13
    slices / 8 devices) serves collectively, bit-exact vs the serial
    oracle — and the loopback counter proves no HTTP round trip ran."""
    queries = [
        'Count(Bitmap(frame="f", rowID=1))',
        'Count(Bitmap(frame="f", rowID=4))',          # all-empty row
        'Count(Intersect(Bitmap(frame="f", rowID=1), '
        'Bitmap(frame="f", rowID=2)))',
        'Count(Union(Bitmap(frame="f", rowID=1), '
        'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3)))',
        'Count(Difference(Bitmap(frame="f", rowID=1), '
        'Bitmap(frame="f", rowID=2)))',
        'Count(Xor(Bitmap(frame="f", rowID=2), '
        'Bitmap(frame="f", rowID=3)))',
        'Count(Union(Intersect(Bitmap(frame="f", rowID=1), '
        'Bitmap(frame="f", rowID=2)), Difference('
        'Bitmap(frame="f", rowID=3), Bitmap(frame="f", rowID=4))))',
    ]
    nonzero = 0
    for q in queries:
        nonzero += 1 if rig.check(q) else 0
    assert nonzero >= 4  # the data actually exercised the kernels
    assert rig.plane_a._stats["launches"]["count"] == len(queries)
    assert not any(rig.plane_a._stats["fallbacks"].values())
    assert rig.client.calls == 0  # not one socket-path round trip


def test_collective_bsi_range_counts_match_serial_oracle(rig):
    """Count(Range(cond)) — the BSI-Range reduction cell vmapped
    inside the collective program — for every comparison operator."""
    for q in ('Count(Range(frame="g", v > 50))',
              'Count(Range(frame="g", v < 0))',
              'Count(Range(frame="g", v >= 200))',
              'Count(Range(frame="g", v <= -5))',
              'Count(Range(frame="g", v == 7))',
              'Count(Range(frame="g", v != 7))',
              'Count(Range(frame="g", v >< [0, 100]))',
              'Count(Range(frame="g", v > 9999))',   # out-of-range ->
              # statically-empty plan: serves 0 with NO program launch
              # and, regression, no reason=error fallback
              'Count(Union(Range(frame="g", v > 150), '
              'Bitmap(frame="f", rowID=1)))'):
        rig.check(q)
    assert not any(rig.plane_a._stats["fallbacks"].values())


def test_collective_topn_and_sum_match_serial_oracle(rig):
    """TopN exact recounts (explicit ids, with/without src tree) and
    BSI Sum (with/without filter) reduce on the mesh bit-exact."""
    for q in ('TopN(frame="f", n=2, ids=[1, 2, 3, 4])',
              'TopN(Bitmap(frame="f", rowID=1), frame="f", n=3, '
              'ids=[1, 2, 3])',
              'Sum(frame="g", field="v")',
              'Sum(Bitmap(frame="f", rowID=1), frame="g", field="v")'):
        rig.check(q)
    st = rig.plane_a._stats
    assert st["launches"]["topn"] == 2
    assert st["launches"]["sum"] == 2


def test_full_topn_two_phase_rides_collective_recount(rig):
    """A full TopN(frame, n) — discovery walks host cache metadata
    (counted as an 'unsupported' fallback), the exact phase-2 recount
    serves collectively — and the end result matches the oracle."""
    before = rig.plane_a._stats["launches"]["topn"]
    rig.check('TopN(frame="f", n=3)')
    assert rig.plane_a._stats["launches"]["topn"] > before


def test_write_invalidates_staged_stacks(rig):
    """A write on the REMOTE member (shared in-process mutation epoch)
    must drop the coordinator's staged stacks: counts stay bit-exact
    across interleaved writes, and the stack cache re-misses."""
    q = ('Count(Union(Bitmap(frame="f", rowID=1), '
         'Bitmap(frame="f", rowID=2)))')
    base = rig.check(q)
    misses0 = rig.plane_a._stats["stack_misses"]
    rig.check(q)  # warm: served from staged stacks
    assert rig.plane_a._stats["stack_misses"] == misses0

    # Write to a slice owned by b, through b's own holder — the path
    # a relayed write lands on. ModHasher: slice 1 -> node b.
    owner = rig.cluster.fragment_nodes("i", 1)[0].host
    col = 1 * SLICE_WIDTH + 999_983
    rig.holders[owner].index("i").frame("f").set_bit("standard", 1, col)
    rig.oracle_holder.index("i").frame("f").set_bit("standard", 1, col)
    assert rig.check(q) == base + 1
    assert rig.plane_a._stats["stack_misses"] > misses0


def test_transition_falls_back_and_resumes_at_commit(rig):
    """Placement mid-TRANSITION declines (reason=transition); the
    COMMITTED phase — every moved fragment verified — serves
    collectively again."""
    call = _count_call('Count(Bitmap(frame="f", rowID=1))')
    slices = list(range(rig.n_slices))
    assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
        is not DECLINED

    pl = rig.cluster.placement
    pl.pin(["a", "b"])
    state = pl.begin(["a", "b", "c"], ["a", "b"], pl.generation + 1)
    assert state["phase"] == "transition"
    assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
        is DECLINED
    assert rig.plane_a._stats["fallbacks"]["transition"] == 1

    pl.commit()
    # Post-commit the new generation routes; hosts still cover a+b
    # under ModHasher for this slice range only if 'c' owns nothing
    # queried — re-derive coverage instead of asserting blindly.
    out = rig.plane_a.try_collective(rig.ex, "i", call, slices)
    assert out is not DECLINED or \
        rig.plane_a._stats["fallbacks"]["not_resident"] >= 1


def test_member_leaving_declines_not_resident(rig):
    """Unregistering a member (its server draining) rotates the
    registry version: the cover memo re-derives and declines instead
    of staging against a gone holder."""
    call = _count_call('Count(Bitmap(frame="f", rowID=2))')
    slices = list(range(rig.n_slices))
    assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
        is not DECLINED
    rig.plane_b.close()
    assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
        is DECLINED
    reasons = rig.plane_a._stats["fallbacks"]
    assert reasons["not_resident"] + reasons["no_group"] >= 1
    # Re-registration restores the collective path.
    rig.plane_b.register()
    assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
        is not DECLINED


def test_stack_budget_declines(rig):
    rig.plane_a.stack_bytes = 1024  # smaller than one slice row
    call = _count_call('Count(Bitmap(frame="f", rowID=1))')
    assert rig.plane_a.try_collective(
        rig.ex, "i", call, list(range(rig.n_slices))) is DECLINED
    assert rig.plane_a._stats["fallbacks"]["budget"] >= 1

    # Per-QUERY aggregate: each stack fits, but a 3-leaf plan's
    # working set exceeds the budget (in-flight args pin their
    # arrays, so LRU eviction can't save the query — it must decline
    # like the batched path's BATCH_OVER_BUDGET).
    slices = list(range(rig.n_slices))
    one = _count_call('Count(Bitmap(frame="f", rowID=1))')
    rig.plane_a.stack_bytes = 1 << 40
    out = rig.plane_a.try_collective(rig.ex, "i", one, slices)
    assert out is not DECLINED
    per_stack = rig.plane_a._stack_bytes  # one staged row stack
    rig.plane_a.stack_bytes = per_stack * 2  # fits 2 stacks, not 3
    union3 = _count_call(
        'Count(Union(Bitmap(frame="f", rowID=1), '
        'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3)))')
    before = rig.plane_a._stats["fallbacks"]["budget"]
    assert rig.plane_a.try_collective(rig.ex, "i", union3, slices) \
        is DECLINED
    assert rig.plane_a._stats["fallbacks"]["budget"] == before + 1


def test_unsupported_shapes_decline(rig):
    from pilosa_tpu.pql import parse

    slices = list(range(rig.n_slices))
    for q in ('TopN(frame="f", n=3)',                    # discovery
              'TopN(frame="f", n=3, threshold=50, ids=[1, 2])',
              'Min(frame="g", field="v")',
              'Bitmap(frame="f", rowID=1)'):
        call = parse(q).calls[0]
        assert rig.plane_a.try_collective(rig.ex, "i", call, slices) \
            is DECLINED, q
    assert rig.plane_a._stats["fallbacks"]["unsupported"] == 4


def test_int32_width_guard_declines():
    """Slice sets wider than the int32 psum contract decline before
    any staging (the guard is O(1))."""
    from pilosa_tpu.parallel.mesh import INT32_SAFE_SLICES

    cl = Cluster(nodes=[Node("a"), Node("b")], hasher=ModHasher())
    holder = Holder(tempfile.mkdtemp()).open()
    ex = Executor(holder, cluster=cl, host="a", client=BoomClient())
    mp = MeshPlane(holder, cl, "a", group="t-int32").register()
    try:
        ex.meshplane = mp
        call = _count_call('Count(Bitmap(frame="f", rowID=1))')
        wide = list(range(INT32_SAFE_SLICES + 1))
        assert mp.try_collective(ex, "i", call, wide) is DECLINED
        assert mp._stats["fallbacks"]["int32"] == 1  # before staging
    finally:
        mp.close()
        holder.close()


def test_masked_padding_is_bit_exact_under_garbage(rng):
    """The collective cells mask padded lanes by GLOBAL slice index —
    a pad lane holding garbage (a reused stack, a staging bug) must
    not perturb any reduce, sum or non-sum alike."""
    from pilosa_tpu.parallel.mesh import MeshQueryEngine, make_mesh

    engine = MeshQueryEngine(make_mesh())
    W = 64
    S, PAD = 5, 8
    rows = (rng.integers(0, 1 << 32, size=(PAD, W), dtype=np.uint64)
            .astype(np.uint32))
    rows2 = (rng.integers(0, 1 << 32, size=(PAD, W), dtype=np.uint64)
             .astype(np.uint32))
    # Rows beyond S are GARBAGE, deliberately nonzero.
    a = engine.shard_rows(rows)
    b = engine.shard_rows(rows2)
    plan = ("Intersect", [("leaf", 0), ("leaf", 1)])
    got = int(np.asarray(engine.tree_count(
        plan, (a, b), ("slice", "slice"), S)))
    want = int(np.bitwise_count(rows[:S] & rows2[:S]).sum())
    assert got == want

    # TopN counts: [S, R, W] with poisoned padding.
    R = 3
    m = (rng.integers(0, 1 << 32, size=(PAD, R, W), dtype=np.uint64)
         .astype(np.uint32))
    counts = np.asarray(engine.topn_tree_counts(
        engine.shard_rows(m), None, (), (), S))
    assert counts.tolist() == [
        int(np.bitwise_count(m[:S, r]).sum()) for r in range(R)]

    # BSI sum counts: planes with poisoned padding.
    D = 4
    planes = (rng.integers(0, 1 << 32, size=(PAD, D + 1, W),
                           dtype=np.uint64).astype(np.uint32))
    out = np.asarray(engine.bsi_sum_counts(
        engine.shard_rows(planes), None, (), (), S))
    exists = planes[:S, D]
    want_counts = [int(np.bitwise_count(planes[:S, i] & exists).sum())
                   for i in range(D)]
    assert out[:D].tolist() == want_counts
    assert int(out[D]) == int(np.bitwise_count(exists).sum())


def test_bsi_range_count_cell(rng):
    """The standalone BSI-Range reduction cell vs a host oracle."""
    from pilosa_tpu.ops import bsi as bsi_ops
    from pilosa_tpu.parallel.mesh import MeshQueryEngine, make_mesh

    engine = MeshQueryEngine(make_mesh())
    W, S, D = 32, 8, 5
    vals = rng.integers(0, 1 << D, size=(S, W * 32))
    exists_bits = rng.random((S, W * 32)) < 0.5
    planes = np.zeros((S, D + 1, W), np.uint32)
    for s in range(S):
        for i in range(D):
            bits = ((vals[s] >> i) & 1).astype(np.uint8) \
                & exists_bits[s]
            planes[s, i] = np.packbits(
                bits, bitorder="little").view(np.uint32)
        planes[s, D] = np.packbits(
            exists_bits[s].astype(np.uint8),
            bitorder="little").view(np.uint32)
    sharded = engine.shard_rows(planes)
    masked_vals = np.where(exists_bits, vals, -1)
    for op, want in (
            (">", int(((masked_vals > 9) & exists_bits).sum())),
            ("<=", int(((masked_vals <= 9) & exists_bits
                        & (masked_vals >= 0)).sum())),
            ("==", int((masked_vals == 9).sum()))):
        got = int(np.asarray(engine.bsi_range_count(
            sharded, op, bsi_ops.value_to_bits(9, D), S)))
        assert got == want, op


def test_local_mesh_rebuilds_on_device_topology_change(monkeypatch):
    """executor.py regression: the memoized local mesh must version on
    the device fingerprint — a topology change between calls used to
    serve a stale mesh naming the old device set forever."""
    ex = Executor(Holder(tempfile.mkdtemp()))
    m8 = ex._local_mesh()
    assert m8.devices.size == len(jax.devices())
    assert ex._local_mesh() is m8  # memoized while topology holds

    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real[:4])
    m4 = ex._local_mesh()
    assert m4 is not m8
    assert m4.devices.size == 4
    monkeypatch.undo()
    assert ex._local_mesh().devices.size == len(real)


def test_shard_map_unchecked_kernel_compiles():
    """A kernel whose output replication the checker can't prove (an
    all_gather) must compile with ``check_vma=False``, the form
    parallel/distributed.py uses."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()

    def kernel(x):
        return lax.all_gather(jnp.sum(x), "slice")

    out = jax.shard_map(kernel, mesh=mesh, in_specs=(P("slice"),),
                        out_specs=P(), check_vma=False)(
        jnp.arange(len(jax.devices()), dtype=jnp.int32))
    assert int(np.asarray(out).sum()) >= 0


def test_placement_mesh_coords():
    """placement.py mesh awareness: coordinates come from the pinned
    generation order and survive (only) committed generation flips."""
    from pilosa_tpu.cluster.placement import PlacementMap

    pl = PlacementMap(hosts=["a", "b"])
    pl.pin(["a", "b"])
    gen, phase, hosts = pl.mesh_view()
    assert (phase, hosts) == ("stable", ("a", "b"))
    assert pl.mesh_coords() == {"a": 0, "b": 1}
    assert pl.mesh_coords(["b", "zz"]) == {"b": 1, "zz": None}

    pl.begin(["b", "c"], ["a", "b"], gen + 1)
    _, phase, _ = pl.mesh_view()
    assert phase == "transition"
    pl.commit()
    pl.cleanup()
    assert pl.mesh_coords() == {"b": 0, "c": 1}


def test_mesh_server_cluster_end_to_end(tmp_path):
    """Real-socket in-process 2-node cluster with [mesh] enabled:
    queries over HTTP serve via the collective plane bit-exact vs the
    same cluster with the plane detached, and the ops surfaces
    (/debug/mesh, pilosa_mesh_* on /metrics) are live."""
    import json
    import urllib.request

    from pilosa_tpu.testing import ServerCluster

    def req(host, method, path, body=None):
        r = urllib.request.Request(
            f"http://{host}{path}",
            data=body.encode() if isinstance(body, str) else body,
            method=method)
        with urllib.request.urlopen(r, timeout=30) as resp:
            return resp.read()

    cluster = ServerCluster(2, base_path=str(tmp_path),
                            mesh={"enabled": True})
    try:
        h = cluster.hosts[0]
        req(h, "POST", "/index/i", "{}")
        req(h, "POST", "/index/i/frame/f", "{}")
        rng = np.random.default_rng(3)
        for s in range(5):
            for r in (1, 2):
                cols = rng.choice(1000, 60, replace=False) \
                    + s * SLICE_WIDTH
                for c in cols.tolist()[:20]:
                    req(h, "POST", "/index/i/query",
                        f'SetBit(frame="f", rowID={r}, columnID={c})')
        queries = [
            'Count(Intersect(Bitmap(frame="f", rowID=1), '
            'Bitmap(frame="f", rowID=2)))',
            'Count(Union(Bitmap(frame="f", rowID=1), '
            'Bitmap(frame="f", rowID=2)))',
            'TopN(frame="f", n=2)',
        ]
        mesh_out = [json.loads(req(h, "POST", "/index/i/query", q))
                    for q in queries]
        snap = json.loads(req(h, "GET", "/debug/mesh"))
        assert snap["enabled"] and len(snap["members"]) == 2
        assert snap["launches"]["count"] >= 2
        metrics = req(h, "GET", "/metrics").decode()
        assert "pilosa_mesh_collective_launches_total" in metrics
        assert 'pilosa_mesh_fallback_total{reason="transition"}' \
            in metrics

        # Same cluster, plane detached -> pure HTTP fan-out: results
        # must be bit-identical. (Result memos/response caches would
        # replay the mesh answers — that equality is exactly what the
        # epoch tokens guarantee, so replays are fine to compare.)
        for srv in cluster:
            srv.executor.meshplane = None
            srv.executor._result_memo_off = True
            srv.handler._resp_cache = None
        http_out = [json.loads(req(h, "POST", "/index/i/query", q))
                    for q in queries]
        assert mesh_out == http_out
    finally:
        cluster.close()
