"""Placement by shard on the suite's eight CPU devices (ISSUE 37): a
stack sharded over the slice axis is built shard by shard, each row on
the device whose shard holds its slice, and a row's memoized device copy
lives there too: nothing is staged on device 0 for a slice that lives
elsewhere. When the index grows the shard boundaries move, and the rows
whose only copy lies on another device are copied over and counted
(``crossDeviceRows``). The answers stay exact: the segmentation cell's
five forms through the HTTP handler against the benchmark's own
reference, and a BSI Sum under a Range, a time Range and a Tanimoto
TopN against a brute force."""
import json
import os
from datetime import datetime, timedelta

import jax
import numpy as np
import pytest

from perfbench.datagen import segmentation
from perfbench.lib.serverproc import Client
from perfbench.reference import bitmap_count
from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import TIME_FORMAT, Executor
from pilosa_tpu.server.server import Server
from pilosa_tpu.storage import fragment as fragment_mod
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_711
SLICES, ROWS = 16, 6


def _config():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "segmentation-1b.json")) as f:
        config = json.load(f)
    config["shape"].update(slices=SLICES, rows=ROWS)
    return config


def _forms():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "count-mixed-c1.json")) as f:
        return [form["pql"] for form in json.load(f)["forms"]]


def _owner(pos, padded_n):
    """The device ``PartitionSpec("slice")`` lays position ``pos`` of a
    padded slice list on."""
    devs = jax.devices()
    return devs[pos // (padded_n // len(devs))]


def _padded(n):
    return -(-n // len(jax.devices())) * len(jax.devices())


def _serve(tmp_path):
    config = _config()
    server = Server(str(tmp_path / "d"), bind="localhost:0").open()
    server.executor._force_path = "batched"
    client = Client(int(server.host.rsplit(":", 1)[1]))
    data = segmentation.load(client, config, SEED, lambda *a, **k: None)
    for q in segmentation.stage_queries(config):
        client.request("POST", "/index/users/query", q)
    return config, server, client, data


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    config, server, client, data = _serve(tmp_path_factory.mktemp("seg"))
    yield config, server, client, bitmap_count.Reference(config, data)
    client.close()
    server.close()


def _ask(client, query):
    return client.json("POST", "/index/users/query?profile=true", query)


def _fill(form, config, rows):
    names = iter(segmentation.bitmap(config, r) for r in rows)
    out = form
    for slot in ("{a}", "{b}", "{c}"):
        if slot in out:
            out = out.replace(slot, next(names))
    return out


@pytest.mark.parametrize("form", range(5))
def test_each_form_of_the_mix_equals_the_reference(served, form):
    config, _, client, reference = served
    for rows in ((0, 4, 2), (5, 1, 3), (3, 0, 5)):
        q = _fill(_forms()[form], config, rows)
        doc = _ask(client, q)
        assert doc["results"] == reference.answers([q]), q
        assert doc["profile"]["resources"]["servedBy"] == {"batched": 1}


def test_every_shard_and_every_memoized_row_lies_where_its_slice_lives(
        served):
    _, server, client, _ = served
    ex = server.executor
    stacks = [entry[1] for entry in ex._stack_cache.values()]
    assert len(stacks) >= ROWS
    padded = _padded(SLICES)
    for stack in stacks:
        assert stack.shape[0] == padded
        assert len(stack.addressable_shards) == len(jax.devices())
        for shard in stack.addressable_shards:
            lo = shard.index[0].start or 0
            assert shard.device == _owner(lo, padded)
            assert shard.data.devices() == {shard.device}
    holder = ex.holder
    seen = 0
    for frame in _config()["shape"]["frames"]:
        for s in range(SLICES):
            frag = holder.fragment("users", frame, "standard", s)
            for key, (_, row) in frag._row_dev.items():
                # Nothing for a slice that lives elsewhere on device 0.
                assert key[-1] == _owner(s, padded), (frame, s)
                assert row.devices() == {key[-1]}, (frame, s)
                seen += 1
    assert seen == ROWS * SLICES


def test_staged_rows_are_never_copied_across_devices(served):
    config, _, client, reference = served
    before = fragment_mod.cross_device_rows()
    q = _fill(_forms()[4], config, (1, 2, 0))
    doc = _ask(client, q)
    assert doc["results"] == reference.answers([q])
    assert doc["profile"]["resources"]["crossDeviceRows"] == 0
    assert fragment_mod.cross_device_rows() == before
    got = client.json("GET", "/debug/vars")["crossDeviceRows"]
    assert got == fragment_mod.cross_device_rows()


def test_a_stack_build_is_one_span_a_shard_and_the_launch_spans_them_all(
        tmp_path):
    config, server, client, _ = _serve(tmp_path)
    try:
        server.executor._stack_cache.clear()    # a never-seen Xor
        doc = _ask(client, _fill(_forms()[3], config, (2, 5)))
        spans = doc["profile"]["spans"]
        shards = [s for s in spans if s["name"] == "stack.shard"]
        assert len(shards) == 2 * len(jax.devices())
        assert sorted({s["tags"]["device"] for s in shards}) == sorted(
            d.id for d in jax.devices())
        assert {s["tags"]["rows"] for s in shards} == {
            _padded(SLICES) // len(jax.devices())}
        assert {s["tags"]["moved"] for s in shards} == {0}
        dispatch = next(s for s in spans if s["name"] == "kernel.dispatch")
        assert dispatch["tags"]["devices"] == len(jax.devices())
    finally:
        client.close()
        server.close()


def test_a_shard_stages_its_rows_in_one_transfer(tmp_path, monkeypatch):
    """A never-seen row goes to each shard's device in one device_put of
    the shard's rows, not one a row; the memo and the answer as before."""
    config, server, client, data = _serve(tmp_path)
    try:
        lists, one_by_one = [], []
        put = jax.device_put
        monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: (
            lists.append(len(x)) if isinstance(x, list) else None,
            put(x, *a, **k))[1])
        rebased = fragment_mod.Fragment._rebased_row
        monkeypatch.setattr(fragment_mod.Fragment, "_rebased_row",
                            lambda *a: (one_by_one.append(1),
                                        rebased(*a))[1])
        holder = server.executor.holder
        for frame in config["shape"]["frames"]:
            for s in range(SLICES):
                holder.fragment("users", frame, "standard", s)._row_dev = {}
        server.executor._stack_cache.clear()
        q = _fill(_forms()[0], config, (1, 4))
        doc = _ask(client, q)
        assert doc["results"] == bitmap_count.Reference(
            config, data).answers([q])
        per_shard = _padded(SLICES) // len(jax.devices())
        assert lists == [per_shard] * (2 * len(jax.devices()))
        assert one_by_one == []
        frag = holder.fragment("users", "device", "standard", SLICES - 1)
        assert {key[-1] for key in frag._row_dev} == {
            _owner(SLICES - 1, _padded(SLICES))}
    finally:
        client.close()
        server.close()


def test_growth_moves_the_rows_whose_shard_moved_and_counts_exactly_them(
        tmp_path):
    config, server, client, data = _serve(tmp_path)
    try:
        reference = bitmap_count.Reference(config, data)
        b0, d0, g0, b1 = (segmentation.bitmap(config, r) for r in (0, 1, 2, 3))
        # One bit in slice 16, row 0 of ``behavior``: seventeen slices.
        client.request("POST", "/index/users/query",
                       f'SetBit(frame="behavior", rowID=0, '
                       f'columnID={SLICES * SLICE_WIDTH + 5})')
        old, new = _padded(SLICES), _padded(SLICES + 1)
        assert old != new
        moved_a_row = sum(_owner(s, old) != _owner(s, new)
                          for s in range(SLICES))
        assert moved_a_row > 0
        cases = [(f"Count(Union({b0}, {d0}))", 1, 2),
                 (f"Count({b0})", 1, 0),          # the stack is cached
                 (f"Count(Intersect({d0}, Difference({g0}, {b1})))", 0, 2)]
        for q, extra, rebuilt in cases:
            before = fragment_mod.cross_device_rows()
            doc = _ask(client, q)
            want = reference.answers([q])[0] + extra
            assert doc["results"] == [want], q
            moved = doc["profile"]["resources"]["crossDeviceRows"]
            assert moved == rebuilt * moved_a_row, q
            assert fragment_mod.cross_device_rows() - before == moved
            # Each shard's span says what its own build moved.
            assert sum(s["tags"]["moved"] for s in doc["profile"]["spans"]
                       if s["name"] == "stack.shard") == moved, q
        # Moved once: the memo keeps the row where each layout asked.
        doc = _ask(client, f"Count(Xor({g0}, {b1}))")
        assert doc["profile"]["resources"]["crossDeviceRows"] == 0
        for s in range(SLICES):
            frag = server.executor.holder.fragment("users", "behavior",
                                                   "standard", s)
            memo = frag._row_dev
            assert {key[-1] for key in memo} == {_owner(s, old),
                                                 _owner(s, new)}
            for key, (_, row) in memo.items():
                assert row.devices() == {key[-1]}
    finally:
        client.close()
        server.close()


def test_after_growth_a_path_that_names_no_device_mixes_no_two_chips(
        tmp_path):
    """b0's memo moved with the rebuilt stacks while g0's did not: the
    per-slice path, which names no device, still combines them."""
    config, server, client, data = _serve(tmp_path)
    try:
        reference = bitmap_count.Reference(config, data)
        b0, g0 = (segmentation.bitmap(config, r) for r in (0, 2))
        client.request("POST", "/index/users/query",
                       f'SetBit(frame="behavior", rowID=0, '
                       f'columnID={SLICES * SLICE_WIDTH + 5})')
        assert _ask(client, f"Count({b0})")["results"] == [
            reference.answers([f"Count({b0})"])[0] + 1]
        ex = server.executor
        ex._force_path = "serial"
        for q in (f"Count(Intersect({b0}, {g0}))",
                  _fill(_forms()[4], config, (0, 2, 3))):
            doc = _ask(client, q)
            assert doc["results"] == reference.answers([q]), q
            assert doc["profile"]["resources"]["servedBy"] == {"serial": 1}
        for frame in config["shape"]["frames"]:
            for s in range(SLICES):
                frag = ex.holder.fragment("users", frame, "standard", s)
                for key, (_, row) in frag._row_dev.items():
                    # What a caller that names no device gets is on the
                    # default device, committed to no chip.
                    assert key[-1] is not None or not row.committed
        # The path model's own serial turns, among batched ones (a turn
        # past its deadline is finished batched: exact either way).
        ex._force_path = None
        probes = []
        for a, b, c in ((1, 2, 3), (2, 3, 4), (3, 4, 5), (4, 5, 1),
                        (5, 1, 2), (1, 3, 5)):
            q = _fill(_forms()[4], config, (a, b, c))
            doc = _ask(client, q)
            assert doc["results"] == reference.answers([q]), q
            probes += [sp["tags"]["path"] for sp in doc["profile"]["spans"]
                       if sp["name"] == "path.probe"]
        assert "serial" in probes
    finally:
        client.close()
        server.close()


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """An index over five slices for the other batched shapes."""
    holder = Holder(str(tmp_path_factory.mktemp("small") / "d")).open()
    idx = holder.create_index("i")
    rng = np.random.default_rng(37)
    cols = rng.choice(5 * SLICE_WIDTH, 4000, replace=False)
    bsi = idx.create_frame("b", FrameOptions(range_enabled=True))
    bsi.create_field(Field("v", min=0, max=1000))
    values = rng.integers(0, 1001, len(cols))
    bsi.import_value("v", cols.tolist(), values.tolist())
    ymd = idx.create_frame("t", FrameOptions(time_quantum="YMD"))
    first = datetime(2017, 1, 1)
    days = rng.integers(0, 90, len(cols))
    for c, d in zip(cols.tolist(), days.tolist()):
        ymd.set_bit("standard", 0, c, t=first + timedelta(days=d, hours=3))
    f = idx.create_frame("f")
    # Row d holds the first d columns of every slice.
    rows, at = np.nonzero(np.tril(np.ones((64, 64), dtype=np.uint8)))
    for s in range(5):
        f.import_bits((rows + 1).tolist(), (at + s * SLICE_WIDTH).tolist())
    e = Executor(holder)
    e._force_path = "batched"
    yield e, dict(zip(cols.tolist(), values.tolist())), \
        dict(zip(cols.tolist(), days.tolist())), first
    holder.close()


def test_a_bsi_sum_under_a_range_is_exact(small):
    e, values, _, _ = small
    got = e.execute("i", 'Sum(Range(frame="b", v > 300), frame="b", '
                         'field="v")')[0]
    kept = [v for v in values.values() if v > 300]
    assert (got.sum, got.count) == (sum(kept), len(kept))
    padded = _padded(5)
    for key, entry in e._stack_cache.items():
        if key[0] == "planes":
            for shard in entry[1].addressable_shards:
                assert shard.device == _owner(shard.index[0].start or 0,
                                              padded)


def test_a_sum_under_two_ranges_reads_bits_replicated_over_the_mesh(small):
    """Over a planes stack sharded on every device, the predicate bits
    of two ``><`` ranges are resident arrays replicated over the same
    devices, one upload a distinct vector: the answer equals the serial
    path's and a brute force, and the same bounds in another order
    upload nothing."""
    e, values, _, _ = small
    q = ('Sum(Intersect(Range(frame="b", v >< [{}, {}]), '
         'Range(frame="b", v >< [{}, {}])), frame="b", field="v")')
    one, other = q.format(150, 650, 350, 950), q.format(350, 950, 150, 650)
    kept = [v for v in values.values() if 350 <= v <= 650]
    serial = Executor(e.holder)
    serial._force_path = "serial"
    assert serial.execute("i", one)[0] == (sum(kept), len(kept))
    had, misses = set(e._bsi_bits), e.bsi_bits["bsiBitsMisses"]
    assert e.execute("i", one)[0] == (sum(kept), len(kept))
    new = [e._bsi_bits[k] for k in e._bsi_bits if k not in had]
    assert len(new) == 4 == e.bsi_bits["bsiBitsMisses"] - misses
    (planes,) = [v[1] for k, v in e._stack_cache.items()
                 if k[0] == "planes"]
    for arr in new:
        assert arr.sharding.device_set == planes.sharding.device_set \
            == set(jax.devices())
        assert arr.sharding.is_fully_replicated
    hits = e.bsi_bits["bsiBitsHits"]
    assert e.execute("i", other)[0] == (sum(kept), len(kept))
    assert e.bsi_bits["bsiBitsMisses"] - misses == 4
    assert e.bsi_bits["bsiBitsHits"] - hits == 4


def test_a_time_range_count_is_exact(small):
    e, _, days, first = small
    for a, b in ((0, 7), (10, 41), (3, 88)):
        q = (f'Count(Range(frame="t", rowID=0, '
             f'start="{(first + timedelta(days=a)).strftime(TIME_FORMAT)}",'
             f' end="{(first + timedelta(days=b)).strftime(TIME_FORMAT)}"))')
        assert e.execute("i", q)[0] == sum(a <= d < b
                                           for d in days.values()), q


def test_a_tanimoto_topn_is_exact(small):
    e = small[0]
    q = ('TopN(Bitmap(frame="f", rowID=40), frame="f", {ids}'
         'tanimotoThreshold=50)')
    passing = [r for r in range(1, 65)
               if 100 * min(r, 40) > 50 * max(r, 40)]
    want = sorted(((r, 5 * min(r, 40)) for r in passing),
                  key=lambda p: (-p[1], p[0]))
    ids = list(range(10, 60))
    assert e.execute("i", q.format(ids=f"ids={ids}, "))[0] == [
        p for p in want if p[0] in ids]
    assert e.execute("i", q.format(ids="n=5, "))[0] == want[:5]


def test_a_write_rescatters_only_its_own_shard_on_its_own_device(tmp_path):
    holder = Holder(str(tmp_path / "d")).open()
    try:
        f = holder.create_index("i").create_frame("f")
        for s in range(5):
            f.import_bits([1] * 3, [s * SLICE_WIDTH + c for c in (1, 2, 3)])
        e = Executor(holder)
        e._force_path = "batched"
        q = 'Count(Bitmap(frame="f", rowID=1))'
        assert e.execute("i", q)[0] == 15
        real, seen = e._scatter_rows_fn, []

        def spy():
            fn = real()
            return lambda stack, idx, rows: (
                seen.append((stack.devices(), rows.devices())),
                fn(stack, idx, rows))[1]

        e._scatter_rows_fn = spy
        e.execute("i", f'SetBit(frame="f", rowID=1, '
                       f'columnID={3 * SLICE_WIDTH + 9})')
        assert e.execute("i", 'Count(Union(Bitmap(frame="f", rowID=1)))'
                         )[0] == 16
        owner = _owner(3, _padded(5))
        assert seen == [({owner}, {owner})]
        (stack,) = [v[1] for k, v in e._stack_cache.items()
                    if k[0] == "row"]
        for shard in stack.addressable_shards:
            assert shard.device == _owner(shard.index[0].start or 0,
                                          _padded(5))
    finally:
        holder.close()
