"""Holder/Index/Frame/View tests — persistence, schema validation, BSI
offset encoding, time-quantum views (analog of index_test.go,
frame_test.go, view_test.go, holder_test.go)."""
from datetime import datetime

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu import errors as perr
from pilosa_tpu import time_quantum as tq
from pilosa_tpu.storage.attrs import AttrStore
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.storage.index import FrameOptions


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    yield h
    h.close()


def test_create_index_and_frame(holder):
    idx = holder.create_index("i")
    with pytest.raises(perr.ErrIndexExists):
        holder.create_index("i")
    f = idx.create_frame("f")
    with pytest.raises(perr.ErrFrameExists):
        idx.create_frame("f")
    assert f.cache_type == "ranked"
    with pytest.raises(perr.ErrName):
        holder.create_index("BAD NAME")


def test_frame_option_validation(holder):
    idx = holder.create_index("i")
    with pytest.raises(perr.ErrInverseRangeNotAllowed):
        idx.create_frame("a", FrameOptions(range_enabled=True,
                                           inverse_enabled=True))
    with pytest.raises(perr.ErrRangeCacheNotAllowed):
        idx.create_frame("b", FrameOptions(range_enabled=True,
                                           cache_type="ranked"))
    with pytest.raises(perr.ErrFrameFieldsNotAllowed):
        idx.create_frame("c", FrameOptions(fields=[Field("v", max=10)]))
    with pytest.raises(perr.ErrColumnRowLabelEqual):
        idx.create_frame("d", FrameOptions(row_label="columnID"))
    with pytest.raises(perr.ErrInvalidFieldRange):
        idx.create_frame("e", FrameOptions(range_enabled=True,
                                           fields=[Field("v", min=5, max=1)]))


def test_setbit_time_views(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f", FrameOptions(time_quantum="YMDH"))
    f.set_bit("standard", 1, 5, datetime(2017, 8, 12, 15))
    views = sorted(f.views)
    assert views == ["standard", "standard_2017", "standard_201708",
                     "standard_20170812", "standard_2017081215"]
    for v in views:
        assert f.views[v].fragment(0).row_count(1) == 1


def test_holder_reopen_persistence(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    idx = h.create_index("i", time_quantum="YM")
    f = idx.create_frame("f", FrameOptions(inverse_enabled=True))
    f.set_bit("standard", 3, 9)
    f.set_bit("inverse", 9, 3)
    local_id = h.local_id
    h.close()

    h2 = Holder(str(tmp_path / "data")).open()
    assert h2.local_id == local_id
    idx2 = h2.index("i")
    assert idx2.time_quantum == "YM"
    f2 = idx2.frame("f")
    assert f2.inverse_enabled is True
    assert f2.view("standard").fragment(0).row_count(3) == 1
    assert f2.view("inverse").fragment(0).row_count(9) == 1
    h2.close()


def test_max_slice(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    f.set_bit("standard", 0, 0)
    f.set_bit("standard", 0, 3 * SLICE_WIDTH + 1)
    assert idx.max_slice() == 3
    idx.set_remote_max_slice(7)
    assert idx.max_slice() == 7


def test_bsi_frame_offset_encoding(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=100, max=200)]))
    assert f.field("v").bit_depth() == 7  # 100 values fit in 7 bits

    f.set_field_value(1, "v", 150)
    f.set_field_value(2, "v", 100)
    f.set_field_value(3, "v", 200)
    with pytest.raises(perr.ErrFieldValueTooLow):
        f.set_field_value(4, "v", 99)
    with pytest.raises(perr.ErrFieldValueTooHigh):
        f.set_field_value(4, "v", 201)

    assert f.field_value(1, "v") == (150, True)
    assert f.field_value(2, "v") == (100, True)
    assert f.field_value(9, "v") == (0, False)
    assert f.field_sum(None, "v") == (450, 3)

    # base_value offsetting
    fd = f.field("v")
    assert fd.base_value(">", 150) == (50, False)
    assert fd.base_value(">", 250) == (0, True)
    assert fd.base_value("<", 50) == (0, True)
    assert fd.base_value("<", 250) == (100, False)
    assert fd.base_value("==", 127) == (27, False)
    assert fd.base_value_between(120, 180) == (20, 80, False)
    assert fd.base_value_between(300, 400) == (0, 0, True)


def test_import_value_overwrite(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f", FrameOptions(
        range_enabled=True, fields=[Field("v", min=0, max=255)]))
    f.import_value("v", [1, 2], [10, 20])
    assert f.field_value(1, "v") == (10, True)
    f.import_value("v", [1], [200])       # overwrite must clear old planes
    assert f.field_value(1, "v") == (200, True)
    assert f.field_sum(None, "v") == (220, 2)


def test_frame_import_groups_views(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f", FrameOptions(inverse_enabled=True,
                                           time_quantum="YM"))
    f.import_bits([1, 2], [5, SLICE_WIDTH + 6],
                  [datetime(2017, 1, 1), None])
    assert f.view("standard").fragment(0).row_count(1) == 1
    assert f.view("standard").fragment(1).row_count(2) == 1
    # inverse: orientation swapped, cols become rows
    assert f.view("inverse").fragment(0).row_count(5) == 1
    # time views only for the timestamped bit
    assert f.view("standard_2017").fragment(0).row_count(1) == 1
    assert f.view("standard_201701").fragment(0).row_count(1) == 1


def test_schema_and_apply(holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    f.set_bit("standard", 0, 0)
    schema = holder.schema()
    assert schema == [{"name": "i", "frames": [
        {"name": "f", "views": [{"name": "standard"}]}]}]


def test_apply_schema_merge(tmp_path):
    h = Holder(str(tmp_path / "a")).open()
    h.apply_schema([{"name": "i", "frames": [
        {"name": "f", "views": [{"name": "standard"}]}]}])
    assert h.index("i").frame("f").view("standard") is not None
    h.close()


# --------------------------- time quantum ----------------------------------

def test_views_by_time():
    t = datetime(2017, 8, 12, 15)
    assert tq.views_by_time("standard", t, "YMDH") == [
        "standard_2017", "standard_201708", "standard_20170812",
        "standard_2017081215"]


def test_views_by_time_range_minimal_cover():
    got = tq.views_by_time_range(
        "standard", datetime(2017, 8, 30, 22), datetime(2017, 9, 2, 2), "YMDH")
    assert got == [
        "standard_2017083022", "standard_2017083023",
        "standard_20170831",
        "standard_20170901",
        "standard_2017090200", "standard_2017090201"]


def test_views_by_time_range_year_span():
    got = tq.views_by_time_range(
        "standard", datetime(2016, 1, 1), datetime(2018, 1, 1), "YMDH")
    assert got == ["standard_2016", "standard_2017"]


def test_views_by_time_range_coarse_only():
    # quantum without hour: sub-day remainder is dropped (no finer unit)
    got = tq.views_by_time_range(
        "standard", datetime(2017, 1, 1), datetime(2017, 3, 1), "YM")
    assert got == ["standard_201701", "standard_201702"]


# ----------------------------- attrs ---------------------------------------

def test_attr_store(tmp_path):
    s = AttrStore(str(tmp_path / "attrs")).open()
    s.set_attrs(1, {"name": "foo", "n": 7})
    s.set_attrs(1, {"n": None, "x": True})   # delete n, add x
    assert s.attrs(1) == {"name": "foo", "x": True}
    s.set_bulk_attrs({2: {"a": 1}, 300: {"b": 2.5}})
    assert s.attrs(300) == {"b": 2.5}
    assert s.ids() == [1, 2, 300]

    blocks = s.blocks()
    assert [b for b, _ in blocks] == [0, 3]
    assert s.block_data(3) == {300: {"b": 2.5}}

    # diff: change one block, other stays identical
    s2 = AttrStore(str(tmp_path / "attrs2")).open()
    s2.set_bulk_attrs({2: {"a": 1}, 1: {"name": "foo", "x": True},
                       300: {"b": 99}})
    assert s2.blocks_diff(blocks) == [3]
    s.close()
    s2.close()


def test_attr_store_persistence(tmp_path):
    s = AttrStore(str(tmp_path / "attrs")).open()
    s.set_attrs(5, {"k": "v"})
    s.close()
    s2 = AttrStore(str(tmp_path / "attrs")).open()
    assert s2.attrs(5) == {"k": "v"}
    s2.close()


# -------------------------- input definitions ------------------------------

def test_input_definition(holder):
    idx = holder.create_index("i")
    idef = idx.create_input_definition(
        "def1",
        [{"name": "event", "options": {}}],
        [
            {"name": "columnID", "primaryKey": True},
            {"name": "color", "actions": [
                {"frame": "event", "valueDestination": "mapping",
                 "valueMap": {"red": 1, "blue": 2}}]},
            {"name": "active", "actions": [
                {"frame": "event", "valueDestination": "single-row-boolean",
                 "rowID": 10}]},
            {"name": "score", "actions": [
                {"frame": "event", "valueDestination": "value-to-row"}]},
        ])
    bits = idef.parse_records([
        {"columnID": 7, "color": "red", "active": True, "score": 42.0},
        {"columnID": 8, "color": "blue", "active": False},
    ])
    assert set(bits["event"]) == {(1, 7, None), (10, 7, None), (42, 7, None),
                                  (2, 8, None)}
    for row, col, t in bits["event"]:
        idx.input_bits("event", [(row, col, t)])
    assert idx.frame("event").view("standard").fragment(0).row_count(1) == 1

    with pytest.raises(perr.ErrInputDefinitionExists):
        idx.create_input_definition("def1", [{"name": "e2"}],
                                    [{"name": "columnID", "primaryKey": True}])
    with pytest.raises(perr.ErrInputDefinitionHasPrimaryKey):
        idx.create_input_definition("def2", [{"name": "e2"}],
                                    [{"name": "color", "actions": []}])


def test_import_bits_empty_and_mismatched(tmp_path):
    import pytest
    from pilosa_tpu.storage.holder import Holder

    h = Holder(str(tmp_path / "d"))
    h.open()
    f = h.create_index("i").create_frame("f")
    f.import_bits([], [])  # no-op, no view side effects
    assert f.view("standard") is None or not f.view("standard").fragments
    with pytest.raises(ValueError, match="length mismatch"):
        f.import_bits([1, 2], [3])
    with pytest.raises(ValueError, match="timestamp length"):
        f.import_bits([1, 2], [3, 4], timestamps=[None])


def test_holder_raises_file_limit(tmp_path):
    """Holder.open raises RLIMIT_NOFILE toward the hard limit
    (ref: setFileLimit holder.go:385-431)."""
    import resource

    from pilosa_tpu.storage.holder import Holder

    soft0, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft0 == resource.RLIM_INFINITY:
        import pytest as _pytest
        _pytest.skip("soft limit already unlimited")
    try:
        h = Holder(str(tmp_path / "d")).open()
        soft1, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
        # platform kernels may cap below the hard limit (darwin
        # fallback path) — the invariant is monotone non-decreasing
        assert soft1 >= soft0
        want = 262144 if hard == resource.RLIM_INFINITY \
            else min(262144, hard)
        assert soft1 in (max(soft0, want), max(soft0, 10240))
        h.close()
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft0, hard))


def test_cache_ids_arr_memo_tracks_membership():
    """ids_arr() is memoized (TopN reads it every query; np.fromiter
    over 500k entries cost ~25 ms/query) and must invalidate on every
    MEMBERSHIP change — insert, zero-count removal, threshold rebuild,
    LRU eviction, clear — while count-only overwrites keep the memo."""
    import numpy as np

    from pilosa_tpu.storage.cache import LRUCache, RankCache

    rc = RankCache(max_entries=100)
    rc.bulk_add(1, 5)
    rc.bulk_add(2, 7)
    a1 = rc.ids_arr()
    assert sorted(a1.tolist()) == [1, 2]
    assert rc.ids_arr() is a1          # memo hit
    rc.bulk_add(1, 9)                  # overwrite: same membership
    assert rc.ids_arr() is a1
    rc.bulk_add(3, 4)                  # insert
    assert sorted(rc.ids_arr().tolist()) == [1, 2, 3]
    rc.bulk_add(2, 0)                  # zero count removes
    assert sorted(rc.ids_arr().tolist()) == [1, 3]
    rc.clear()
    assert rc.ids_arr().size == 0

    # Threshold rebuild (invalidate) re-derives the array.
    rc2 = RankCache(max_entries=2)
    for rid in range(20):
        rc2.bulk_add(rid, rid + 1)
    rc2.ids_arr()
    rc2.invalidate()                   # trims to max_entries
    assert sorted(rc2.ids_arr().tolist()) == sorted(rc2.ids())

    lru = LRUCache(max_entries=2)
    lru.bulk_add(1, 1)
    lru.bulk_add(2, 2)
    b1 = lru.ids_arr()
    lru.get(1)                         # recency touch: no membership change
    assert lru.ids_arr() is b1
    lru.bulk_add(3, 3)                 # evicts id 2
    assert sorted(lru.ids_arr().tolist()) == [1, 3]
    assert np.issubdtype(lru.ids_arr().dtype, np.uint64)


def test_holder_dir_lock_replaces_per_fragment_flocks(tmp_path):
    """One directory-level flock guards the whole holder: fragments
    under it create NO per-file .lock fds (10B-scale fd exhaustion),
    a second holder on the same dir is refused, and a standalone
    Fragment outside any holder still takes its own flock."""
    import os
    import subprocess
    import sys

    from pilosa_tpu import errors as perr
    from pilosa_tpu.storage.fragment import Fragment
    from pilosa_tpu.storage.holder import Holder

    d = str(tmp_path / "h")
    holder = Holder(d)
    holder.open()
    try:
        idx = holder.create_index("i")
        idx.create_frame("f")
        idx.frame("f").import_bits([1], [5])
        frag = holder.fragment("i", "f", "standard", 0)
        assert frag is not None
        assert frag._lock_file is None, "fragment took a per-file flock"
        assert not os.path.exists(frag.path + ".lock")
        # A second holder on the same dir must be refused — from
        # ANOTHER PROCESS (flock is per open-file-description; an
        # in-process second open would need a second fd anyway).
        r = subprocess.run(
            [sys.executable, "-c", f"""
import sys; sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu import errors as perr
try:
    Holder({d!r}).open()
    print("OPENED")
except perr.ErrHolderLocked:
    print("LOCKED")
"""], capture_output=True, text=True, timeout=120)
        assert "LOCKED" in r.stdout, (r.stdout, r.stderr[-300:])
    finally:
        holder.close()

    # After close, the dir lock releases: reopen works.
    h2 = Holder(d)
    h2.open()
    h2.close()

    # Standalone fragment (no holder): per-file flock still guards.
    p = str(tmp_path / "frag")
    f1 = Fragment(p, "i", "f", "standard", 0).open()
    try:
        assert f1._lock_file is not None
    finally:
        f1.close()


def test_mixed_era_locks_still_mutually_exclude(tmp_path):
    """The dir-level lock must not weaken the old per-file guard in
    either direction: a standalone fragment opened in ANOTHER process
    must be refused while a holder owns the tree, and a holder's
    fragment must be refused while another process holds the
    fragment's legacy per-file lock."""
    import os
    import subprocess
    import sys

    from pilosa_tpu.storage.holder import Holder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = str(tmp_path / "h")
    holder = Holder(d)
    holder.open()
    try:
        idx = holder.create_index("i")
        idx.create_frame("f")
        idx.frame("f").import_bits([1], [5])
        frag_path = holder.fragment("i", "f", "standard", 0).path
        # Direction 1: standalone Fragment in another process walks up
        # to .holder.lock and is refused.
        r = subprocess.run([sys.executable, "-c", f"""
import sys; sys.path.insert(0, {root!r})
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu import errors as perr
try:
    Fragment({frag_path!r}, "i", "f", "standard", 0).open()
    print("OPENED")
except perr.ErrFragmentLocked:
    print("REFUSED")
"""], capture_output=True, text=True, timeout=120)
        assert "REFUSED" in r.stdout, (r.stdout, r.stderr[-300:])
    finally:
        holder.close()

    # Direction 2: another process holds the legacy per-file lock
    # (old-binary writer); a NEW holder in this process must refuse
    # that fragment at open.
    locker = subprocess.Popen([sys.executable, "-c", f"""
import sys; sys.path.insert(0, {root!r})
import fcntl, time
f = open({frag_path!r} + ".lock", "ab")
fcntl.flock(f.fileno(), fcntl.LOCK_EX)
print("HELD", flush=True)
time.sleep(30)
"""], stdout=subprocess.PIPE, text=True)
    try:
        assert locker.stdout.readline().strip() == "HELD"
        from pilosa_tpu import errors as perr

        try:
            Holder(d).open()
            raise AssertionError("holder opened over a held "
                                 "per-file lock")
        except perr.ErrFragmentLocked:
            pass
    finally:
        locker.kill()
        locker.wait(timeout=10)
