"""Workload observatory (PR 13): kernel-cost attribution cells with
compile-vs-steady separation, decayed slice/row heatmaps with top-K
bounding, SLO burn-rate math, NOP-path guarantees, coalescer
query-stats attribution, and the 2-node /cluster/metrics heatmap
merge."""
import json
import threading
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, qos, querystats
from pilosa_tpu import stats as stats_mod
from pilosa_tpu.executor import Executor
from pilosa_tpu.observe import heatmap as heatmap_mod
from pilosa_tpu.observe import kerneltime as kt
from pilosa_tpu.observe import slo as slo_mod
from pilosa_tpu.server.server import Server
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.testing import ServerCluster


@pytest.fixture(autouse=True)
def _restore_observe():
    """Process-global tiers restored after every test — a test that
    enables/disables the observatory must not leak into its
    neighbors."""
    prev_kt, prev_hm = kt.ACTIVE, heatmap_mod.ACTIVE
    yield
    kt.ACTIVE, heatmap_mod.ACTIVE = prev_kt, prev_hm


def http_get(url):
    with urllib.request.urlopen(url, timeout=15) as resp:
        return resp.status, resp.read()


def http_post(url, body):
    req = urllib.request.Request(url, data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=15) as resp:
        return resp.status, resp.read()


# ------------------------------------------------ kerneltime units


def test_kernel_cell_accumulation_and_snapshot():
    obs = kt.KernelObservatory()
    obs.note("count_and", "array*dense", "<=4KB", 0.002)
    obs.note("count_and", "array*dense", "<=4KB", 0.004,
             compiled=True)
    obs.note("count_and", "array*dense", "<=4KB", 0.001, device=True)
    obs.note("count_or", "run*run", "<=1KB", 0.005)
    snap = obs.snapshot()
    rows = {(r["op"], r["cell"], r["bucket"]): r for r in snap["cells"]}
    r = rows[("count_and", "array*dense", "<=4KB")]
    assert r["calls"] == 3
    assert r["compileCalls"] == 1
    assert r["steadyCalls"] == 2
    # steady mean excludes the compile-laden sample: (2 + 1) ms / 2.
    assert r["steadyMeanUs"] == pytest.approx(1500.0)
    assert r["deviceSampledCalls"] == 1
    assert r["deviceMeanUs"] == pytest.approx(1000.0)
    # Most expensive first (the planner reads the top of the table).
    assert snap["cells"][0]["totalMs"] >= snap["cells"][-1]["totalMs"]
    m = obs.metrics()
    assert m["calls_total;op:count_and,cell:array*dense,"
             "bucket:<=4KB"] == 3
    assert m["compile_total;op:count_and,cell:array*dense,"
             "bucket:<=4KB"] == 1


def test_kernel_transfer_rollup_and_jit_cache():
    obs = kt.KernelObservatory()
    obs.note_transfer(1024, 0.001)
    obs.note_transfer(2048, 0.002)
    assert obs.snapshot()["transfers"] == {
        "count": 2, "bytes": 3072, "seconds": 0.003}
    # The cache-size gauge: the last size a compiling launch stamped.
    obs.note_jit_cache("k", 1)
    assert obs.snapshot()["jitCacheSizes"] == {"k": 1}
    obs.note_jit_cache("k", 2)
    assert obs.metrics()["jit_cache_size;kernel:k"] == 2


def test_kernel_shape_buckets():
    assert kt.shape_bucket(0) == "0B"
    assert kt.shape_bucket(1) == "<=1B"
    assert kt.shape_bucket(4096) == "<=4KB"
    assert kt.shape_bucket(4097) == "<=8KB"
    assert kt.shape_bucket(1 << 20) == "<=1MB"
    assert kt.lane_bucket(1) == "k<=1"
    assert kt.lane_bucket(5) == "k<=8"


def test_kernel_sampling_rate():
    obs = kt.KernelObservatory(sample_rate=4)
    hits = sum(1 for _ in range(100) if obs.should_sample())
    assert hits == 25
    assert not kt.KernelObservatory(sample_rate=0).should_sample()


def test_kernel_cell_cap_overflow(monkeypatch):
    monkeypatch.setattr(kt, "MAX_CELLS", 2)
    obs = kt.KernelObservatory()
    obs.note("a", "x", "b1", 0.001)
    obs.note("b", "x", "b1", 0.001)
    obs.note("c", "x", "b1", 0.001)  # over cap: dropped, counted
    assert len(obs.snapshot()["cells"]) == 2
    assert obs.snapshot()["cellOverflow"] == 1


def test_compile_vs_steady_separation_on_fresh_jit_cache():
    """A dispatch on a shape this process never compiled records as
    COMPILE; the repeat on the same shape records as steady state —
    the tracing-only first_compile probe, now always-on."""
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitops

    obs = kt.enable()
    try:
        a = jnp.zeros(7013, jnp.uint32)  # width unique to this test
        # Steady-state notes are stride-sampled (compiles always
        # record), so drive enough repeats to guarantee a steady
        # sample lands.
        for _ in range(1 + 2 * kt.STRIDE):
            assert int(bitops.count(a)) == 0
        bucket = kt.shape_bucket(7013 * 4)
        row = next(r for r in obs.snapshot()["cells"]
                   if r["op"] == "count" and r["bucket"] == bucket)
        assert row["compileCalls"] == 1, row
        assert row["calls"] >= 2 and row["steadyCalls"] >= 1, row
        assert obs.snapshot()["jitCacheSizes"].get("count", 0) >= 1
    finally:
        kt.disable()


def test_serial_compressed_cell_attribution():
    """The registered (op, fmt, fmt) serial cells record into their
    format-pair cost cell — stride-sampled (1-in-STRIDE with weight
    STRIDE, the one stride of kerneltime), so N dispatches land ~N
    scaled calls."""
    from pilosa_tpu.ops import bitops, containers

    obs = kt.enable()
    try:
        arr = containers.Container(
            bitops.FMT_ARRAY, 1024, 3,
            positions=np.array([1, 5, 9], np.int32))
        run = containers.Container(
            bitops.FMT_RUN, 1024, 8,
            runs=np.array([[4, 12]], np.int32))
        n = 2 * kt.STRIDE
        for _ in range(n):
            assert bitops.dispatch_count("and", arr, run) == 2  # {5, 9}
        rows = [r for r in obs.snapshot()["cells"]
                if r["op"] == "count_and" and r["cell"] == "array*run"]
        # The deterministic stride guarantees >= floor(n / stride)
        # samples, each standing for STRIDE calls.
        assert rows, obs.snapshot()["cells"]
        assert rows[0]["calls"] >= n - kt.STRIDE, rows
    finally:
        kt.disable()


# --------------------------------------------------- heatmap units


def test_heatmap_decay_with_fake_clock():
    now = [0.0]
    hm = heatmap_mod.Heatmap(half_life=10.0, top_k=5,
                             _clock=lambda: now[0])
    hm.touch_slice("i", 3, weight=100)
    hm.touch_slice("i", 3, weight=100)
    top, _ = hm._slices.top(5)
    assert top[0][1] == pytest.approx(2.0)
    now[0] = 10.0  # one half-life
    top, _ = hm._slices.top(5)
    assert top[0][1] == pytest.approx(1.0)
    assert top[0][2] == pytest.approx(100.0)  # bytes decay too
    # A touch after decay folds the decayed score in.
    hm.touch_slice("i", 3)
    top, _ = hm._slices.top(5)
    assert top[0][1] == pytest.approx(2.0)


def test_heatmap_topk_bounding_and_prune(monkeypatch):
    monkeypatch.setattr(heatmap_mod, "MAX_ENTRIES", 8)
    now = [0.0]
    hm = heatmap_mod.Heatmap(half_life=1e9, top_k=3,
                             _clock=lambda: now[0])
    for row in range(12):
        for _ in range(row + 1):  # row N touched N+1 times
            hm.touch_row("i", "f", row)
    snap = hm.snapshot()
    # Exposition is top-K only; the table itself stays bounded.
    assert len(snap["rows"]) == 3
    assert snap["rowEntries"] <= 8
    assert len(hm.row_metrics()) <= 2 * 3
    # The hottest rows survive the prune.
    assert snap["rows"][0]["row"] == 11


def test_heatmap_metrics_shape():
    hm = heatmap_mod.Heatmap(top_k=2)
    hm.touch_slice("idx", 7, weight=64)
    hm.note_query("idx", 100)
    hm.note_conversion("idx", "f")
    assert hm.slice_metrics()["heat;index:idx,slice:7"] == 1.0
    om = hm.observe_metrics()
    assert om["heatmap_queries_total;index:idx"] == 1
    assert om["heatmap_conversions_total;index:idx,frame:f"] == 1


# ------------------------------------------------------- SLO units


def test_windowed_counts_ring():
    now = [0.0]
    wc = stats_mod.WindowedCounts(_clock=lambda: now[0])
    wc.add({"total": 5})
    now[0] = 200.0
    wc.add({"total": 3})
    assert wc.window(300)["total"] == 8
    now[0] = 400.0  # the first bucket ages out of the 5m window
    assert wc.window(300)["total"] == 3
    assert wc.window(3600)["total"] == 8
    now[0] = 3500.0  # both buckets still inside the hour
    assert wc.window(3600)["total"] == 8
    now[0] = 4000.0  # and out the far side
    assert wc.window(3600).get("total", 0) == 0


def test_slo_burn_rate_hand_computed():
    now = [0.0]
    tr = slo_mod.SLOTracker(
        {"interactive": {"latency": 0.1, "target": 0.999,
                         "availability": 0.99}},
        _clock=lambda: now[0])
    # 100 requests: 10 slow, 2 errors.
    for i in range(100):
        tr.record("interactive", 0.5 if i < 10 else 0.01,
                  error=i < 2)
    per = tr.burn_rates()["interactive"]
    # latency: bad_frac 0.1 over budget (1 - 0.999) = 100x.
    assert per["5m"]["latency"] == pytest.approx(100.0)
    # availability: 0.02 over budget 0.01 = 2x.
    assert per["5m"]["availability"] == pytest.approx(2.0)
    assert per["5m"]["total"] == 100
    # Multi-window: both windows see the same young data → page-level
    # latency burn, ticket-level nothing on availability.
    snap = tr.snapshot()
    assert snap["advisories"]["interactive"] == "page"
    # Untracked priorities are ignored, not crashed on.
    tr.record("batch", 9.9, error=True)
    assert "batch" not in tr.burn_rates()


def test_slo_multi_window_divergence():
    """A burst that ages out of the 5m window keeps burning the 1h
    window — the slow-leak (ticket) shape."""
    now = [0.0]
    tr = slo_mod.SLOTracker(
        {"batch": {"latency": 1.0, "target": 0.99,
                   "availability": 0.99}},
        _clock=lambda: now[0])
    for _ in range(100):
        tr.record("batch", 5.0)  # all slow
    now[0] = 1200.0  # 20 minutes later: 5m empty, 1h still burning
    for _ in range(10):
        tr.record("batch", 0.01)
    per = tr.burn_rates()["batch"]
    assert per["5m"]["latency"] == pytest.approx(0.0)
    assert per["1h"]["latency"] == pytest.approx(
        (100 / 110) / 0.01, rel=1e-3)
    assert tr.snapshot()["advisories"]["batch"] == "ticket"


def test_slo_objective_parsing_and_validation():
    objs = slo_mod.parse_objectives("interactive=250ms@99.9,batch=2s@99")
    assert objs["interactive"]["latency"] == pytest.approx(0.25)
    assert objs["batch"]["latency"] == pytest.approx(2.0)
    assert objs["batch"]["target"] == pytest.approx(0.99)
    with pytest.raises(ValueError):
        slo_mod.parse_objectives("bogus=1ms@99")  # unknown class
    with pytest.raises(ValueError):
        slo_mod.parse_objectives("interactive=fast@99")
    norm = slo_mod.normalize_objectives(
        {"ingest": {"latency-ms": 500, "target": 99.0}})
    assert norm["ingest"]["availability"] == pytest.approx(0.99)
    with pytest.raises(ValueError):
        slo_mod.normalize_objectives(
            {"interactive": {"latency-ms": -1}})
    with pytest.raises(ValueError):
        slo_mod.normalize_objectives(
            {"interactive": {"latency-ms": 10, "target": 150}})


# ----------------------------------------------- disabled path is nop


def test_nop_path_single_attribute_read():
    """The disabled tiers are the shared NOP objects whose hot
    methods do nothing — pilint's Nop-purity analyzer holds them to
    one attribute read mechanically; this pins the wiring."""
    kt.disable()
    heatmap_mod.disable()
    assert kt.ACTIVE is kt.NOP and kt.NOP.enabled is False
    assert heatmap_mod.ACTIVE is heatmap_mod.NOP
    assert heatmap_mod.NOP.enabled is False
    assert slo_mod.NOP.enabled is False
    # Every hot hook is inert and every surface still answers.
    assert kt.NOP.note("a", "b", "c", 1.0) is None
    assert kt.NOP.should_sample() is False
    assert kt.NOP.note_jit_cache("k", 1) is None
    assert kt.NOP.steady_weight() == 0
    assert heatmap_mod.NOP.touch_row("i", "f", 1) is None
    assert slo_mod.NOP.record("interactive", 1.0) is None
    assert kt.NOP.snapshot() == {"enabled": False}
    assert heatmap_mod.NOP.metrics() == {} \
        if hasattr(heatmap_mod.NOP, "metrics") \
        else heatmap_mod.NOP.slice_metrics() == {}
    assert slo_mod.NOP.metrics() == {}


def test_observe_disabled_server_keeps_nop(tmp_path):
    kt.disable()
    heatmap_mod.disable()
    s = Server(str(tmp_path / "d"), bind="127.0.0.1:0",
               observe={"enabled": False}).open()
    try:
        assert kt.ACTIVE is kt.NOP
        assert heatmap_mod.ACTIVE is heatmap_mod.NOP
        _, body = http_get(f"http://{s.host}/debug/kernels")
        assert json.loads(body) == {"enabled": False}
        _, body = http_get(f"http://{s.host}/debug/heatmap")
        assert json.loads(body) == {"enabled": False}
        _, body = http_get(f"http://{s.host}/debug/slo")
        assert json.loads(body) == {"enabled": False}
        _, body = http_get(f"http://{s.host}/metrics")
        assert b"pilosa_kernel_calls_total" not in body
        assert b"pilosa_slice_heat" not in body
    finally:
        s.close()


# ------------------------------------- coalescer stats attribution


@pytest.fixture
def co_env(tmp_path):
    holder = Holder(str(tmp_path / "data")).open()
    idx = holder.create_index("i")
    idx.create_frame("general")
    e = Executor(holder)
    e._force_path = "batched"
    e._co_enabled_memo = True
    e._co_route_all = True
    yield holder, idx, e
    holder.close()


def test_co_run_single_serve_charges_member_not_leader(co_env):
    """A member served singly on the leader's thread must land its
    resource counts in ITS accumulator — and a member with none gets
    nothing (the leader's active accumulator must not absorb it)."""
    holder, idx, e = co_env
    member_qs = querystats.QueryStats()
    leader_qs = querystats.QueryStats()

    def member_single():
        querystats.add("blocks", 7)
        return 1

    reqs = [
        {"key": ("a",), "prio": qos.PRIO_INTERACTIVE, "deadline": None,
         "out": e._CO_PENDING, "qs": member_qs,
         "single": member_single, "fuse": lambda r: False},
        {"key": ("b",), "prio": qos.PRIO_INTERACTIVE, "deadline": None,
         "out": e._CO_PENDING, "qs": None,
         "single": member_single, "fuse": lambda r: False},
    ]
    with querystats.scope(leader_qs):
        e._co_run(reqs)
    assert reqs[0]["out"] == 1 and reqs[1]["out"] == 1
    assert member_qs.to_dict()["blocks"] == 7
    # The qs-less member's work charged NOBODY — especially not the
    # leader's thread-local accumulator.
    assert leader_qs.to_dict()["blocks"] == 0


def test_parked_coalescee_profile_reflects_own_share(co_env):
    """Regression (PR 12 satellite): a parked coalescee's
    ?profile=true resources used to read ~zero while the tick leader
    was billed the whole fused batch. Each fused member must see its
    own slices/blocks/bytesPopcounted."""
    holder, idx, e = co_env
    frame = idx.frame("general")
    rng = np.random.default_rng(5)
    n_slices = 3
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        for rid in range(1, 9):
            cols = rng.choice(3000, size=40, replace=False)
            frame.import_bits([rid] * 40, (base + cols).tolist())
    e.set_coalesce_config(max_wait_us=60_000)
    # Four DISTINCT row pairs: each member's stacks are its own, so
    # per-member attribution is unambiguous.
    pairs = [(1, 2), (3, 4), (5, 6), (7, 8)]
    queries = [
        (f'Count(Intersect(Bitmap(frame="general", rowID={a}), '
         f'Bitmap(frame="general", rowID={b})))')
        for a, b in pairs]
    serial = Executor(holder)
    serial._force_path = "serial"
    want = [serial.execute("i", q)[0] for q in queries]

    stats_by_i = {}
    results, errors = {}, []
    barrier = threading.Barrier(len(queries))

    def run(q, i):
        qs = querystats.QueryStats()
        stats_by_i[i] = qs
        try:
            barrier.wait(timeout=30)
            with querystats.scope(qs):
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
    assert [results[i] for i in range(len(queries))] == want
    assert e._co_stats["fused_queries"] >= 2, e._co_stats
    counts = {i: qs.to_dict() for i, qs in stats_by_i.items()}
    for i, c in counts.items():
        # Every member — parked or leader — saw its own share.
        assert c["slices"] == n_slices, (i, c)
        assert c["bytesPopcounted"] > 0, (i, c)
        assert c["blocks"] > 0, (i, c)
    # No member was billed the whole batch's blocks: distinct rows
    # mean roughly equal shares, so the max is bounded well below
    # the group total.
    blocks = [c["blocks"] for c in counts.values()]
    assert max(blocks) < sum(blocks), counts


# --------------------------------------------- server acceptance


def test_server_observatory_end_to_end(tmp_path):
    s = Server(str(tmp_path / "d"), bind="127.0.0.1:0",
               observe={"kernel-sample-rate": 2},
               slo={"enabled": True,
                    "objectives": {"interactive":
                                   {"latency-ms": 250,
                                    "target": 99.9}}}).open()
    try:
        base = f"http://{s.host}"
        http_post(f"{base}/index/i", "{}")
        http_post(f"{base}/index/i/frame/general", "{}")
        for c in range(64):
            http_post(f"{base}/index/i/query",
                      f'SetBit(frame="general", rowID={c % 4 + 1}, '
                      f'columnID={c})')
        for a, b in [(1, 2), (1, 3), (2, 3), (1, 2)]:
            http_post(
                f"{base}/index/i/query",
                f'Count(Intersect(Bitmap(frame="general", rowID={a}), '
                f'Bitmap(frame="general", rowID={b})))')
        _, body = http_get(f"{base}/debug/kernels")
        k = json.loads(body)
        assert k["enabled"] and k["cells"], k
        assert any(r["compileCalls"] for r in k["cells"]), k["cells"]
        _, body = http_get(f"{base}/debug/heatmap")
        h = json.loads(body)
        assert h["slices"] and h["rows"], h
        _, body = http_get(f"{base}/debug/slo")
        slo = json.loads(body)
        assert slo["enabled"]
        assert slo["objectives"]["interactive"]["latencyMs"] == 250.0
        assert slo["burnRates"]["interactive"]["5m"]["total"] >= 68
        _, body = http_get(f"{base}/metrics")
        text = body.decode()
        assert "pilosa_kernel_calls_total{" in text
        assert "pilosa_slice_heat{" in text
        assert "pilosa_slo_burn_rate{" in text
        # /debug/vars carries the always-present observe/slo groups.
        _, body = http_get(f"{base}/debug/vars")
        v = json.loads(body)
        assert v["observe"]["kernels"] is True
        assert v["slo"]["enabled"] is True
    finally:
        s.close()


def test_cluster_metrics_merges_heatmap_with_node_labels(tmp_path):
    """2-node acceptance: the existing /cluster/metrics fan-out
    merges each node's top-K heat series under node= labels — one
    scrape shows cluster-wide hot spots."""
    with ServerCluster(2, observe={"enabled": True}) as servers:
        base0 = f"http://{servers[0].host}"
        http_post(f"{base0}/index/i", "{}")
        http_post(f"{base0}/index/i/frame/general", "{}")
        # Columns across enough slices that both nodes own fragments.
        for sl in range(6):
            http_post(f"{base0}/index/i/query",
                      f'SetBit(frame="general", rowID=1, '
                      f'columnID={sl * SLICE_WIDTH + 5})')
        for _ in range(3):
            http_post(f"{base0}/index/i/query",
                      'Count(Bitmap(frame="general", rowID=1))')
        _, body = http_get(f"{base0}/cluster/metrics")
        text = body.decode()
        heat = [ln for ln in text.splitlines()
                if ln.startswith("pilosa_slice_heat{")]
        assert heat, text[:2000]
        nodes = {ln.split('node="', 1)[1].split('"', 1)[0]
                 for ln in heat}
        assert nodes == {servers[0].host, servers[1].host}, nodes