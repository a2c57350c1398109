"""Fragment tests — modeled on the reference's fragment_test.go suite:
set/clear, persistence (reopen), snapshot, import, BSI field ops, TopN,
blocks/checksums, merge, backup round-trip."""
import io
import os

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.storage import fragment as frag_mod
from pilosa_tpu.storage.fragment import WORDS64, Fragment, TopOptions


@pytest.fixture
def frag(tmp_path):
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    yield f
    f.close()


def test_set_clear_bit(frag):
    assert frag.set_bit(10, 3) is True
    assert frag.set_bit(10, 3) is False       # already set
    assert frag.row_count(10) == 1
    assert frag.clear_bit(10, 3) is True
    assert frag.clear_bit(10, 3) is False
    assert frag.row_count(10) == 0


def test_slice_bounds(tmp_path):
    f = Fragment(str(tmp_path / "s2"), "i", "f", "standard", 2).open()
    f.set_bit(0, 2 * SLICE_WIDTH + 5)
    assert f.row_count(0) == 1
    with pytest.raises(ValueError):
        f.set_bit(0, 5)  # column belongs to slice 0
    f.close()


def test_persistence_reopen(tmp_path):
    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0).open()
    bits = [(0, 1), (0, 2), (5, 100), (120, SLICE_WIDTH - 1)]
    for r, c in bits:
        f.set_bit(r, c)
    f.clear_bit(0, 2)
    f.close()

    f2 = Fragment(path, "i", "f", "standard", 0).open()
    assert f2.row_count(0) == 1
    assert f2.row_count(5) == 1
    assert f2.row_count(120) == 1
    assert f2.op_n == 5  # op log replayed, no snapshot yet
    f2.close()


def test_snapshot_resets_oplog(tmp_path):
    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0).open()
    for c in range(10):
        f.set_bit(1, c)
    f.snapshot()
    assert f.op_n == 0
    f.set_bit(1, 100)
    f.close()
    f2 = Fragment(path, "i", "f", "standard", 0).open()
    assert f2.row_count(1) == 11
    # The count above served lazily (no fault-in) — op_n still comes
    # from the lazy reader's op-log parse.
    assert f2.op_n == 1
    assert not f2._resident
    f2.close()


def test_auto_snapshot_at_max_opn(tmp_path, monkeypatch):
    monkeypatch.setattr(frag_mod, "MAX_OPN", 50)
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    for c in range(60):
        f.set_bit(0, c)
    assert f.op_n <= 50
    assert f.row_count(0) == 60
    f.close()


def test_import_bits(frag):
    rows = [0, 0, 0, 3, 3, 7]
    cols = [1, 5, 9, 2, 2, SLICE_WIDTH - 1]
    frag.import_bits(rows, cols)
    assert frag.row_count(0) == 3
    assert frag.row_count(3) == 1    # duplicate collapsed
    assert frag.row_count(7) == 1
    assert frag.op_n == 6            # small batch: op-log append path


def test_row_words_and_device(frag):
    frag.set_bit(2, 65)
    w = frag.row_words(2)
    assert w[1] == np.uint64(2)      # bit 65 = word 1, bit 1
    dev = np.asarray(frag.device_row(2))
    assert dev[2] == 2               # uint32 word 2, bit 1


def test_count(frag):
    frag.import_bits([0, 1, 2], [0, 0, 0])
    frag.set_bit(0, 9)
    assert frag.count() == 4


def test_bsi_field_ops(frag):
    depth = 8
    vals = {3: 17, 9: 200, 100: 0, 5000: 255}
    for col, v in vals.items():
        frag.set_field_value(col, depth, v)
    for col, v in vals.items():
        got, exists = frag.field_value(col, depth)
        assert exists and got == v
    assert frag.field_value(12345, depth) == (0, False)

    total, count = frag.field_sum(None, depth)
    assert total == sum(vals.values()) and count == len(vals)

    # filter to a subset of columns
    filt = np.zeros(frag_mod.WORDS64, dtype=np.uint64)
    for col in (3, 9):
        filt[col >> 6] |= np.uint64(1 << (col & 63))
    total, count = frag.field_sum(filt, depth)
    assert total == 217 and count == 2

    def cols_of(words):
        return set(np.flatnonzero(
            np.unpackbits(words.view(np.uint8), bitorder="little")).tolist())

    assert cols_of(frag.field_range("<", depth, 200)) == {3, 100}
    assert cols_of(frag.field_range("<=", depth, 200)) == {3, 9, 100}
    assert cols_of(frag.field_range("==", depth, 200)) == {9}
    assert cols_of(frag.field_range("!=", depth, 200)) == {3, 100, 5000}
    assert cols_of(frag.field_range(">", depth, 17)) == {9, 5000}
    assert cols_of(frag.field_range_between(depth, 17, 200)) == {3, 9}
    assert cols_of(frag.field_not_null(depth)) == set(vals)

    assert frag.field_min_max(None, depth, True) == (255, 1)
    assert frag.field_min_max(None, depth, False) == (0, 1)


def test_topn(frag):
    frag.import_bits(
        [0] * 5 + [1] * 10 + [2] * 3 + [3] * 10,
        list(range(5)) + list(range(10)) + list(range(3)) + list(range(100, 110)))
    top = frag.top(TopOptions(n=2))
    assert top == [(1, 10), (3, 10)]  # ties broken by ascending row id
    assert frag.top(TopOptions()) == [(1, 10), (3, 10), (0, 5), (2, 3)]

    # src-restricted counts
    src = np.zeros(frag_mod.WORDS64, dtype=np.uint64)
    src[0] = np.uint64(0b111)  # columns 0..2
    top = frag.top(TopOptions(n=2, src=src))
    assert top == [(0, 3), (1, 3)]

    # explicit candidate restriction
    assert frag.top(TopOptions(row_ids=[2, 3])) == [(3, 10), (2, 3)]


def test_topn_tanimoto(frag):
    frag.import_bits([0] * 4 + [1] * 4, [0, 1, 2, 3, 0, 1, 10, 11])
    src = np.zeros(frag_mod.WORDS64, dtype=np.uint64)
    src[0] = np.uint64(0b1111)  # cols 0-3; row0 tanimoto=100, row1=2/6=33
    top = frag.top(TopOptions(src=src, tanimoto_threshold=50))
    assert top == [(0, 4)]


def test_blocks_checksums(frag):
    assert frag.blocks() == []
    frag.set_bit(0, 1)
    b1 = frag.blocks()
    assert [b for b, _ in b1] == [0]
    frag.set_bit(250, 1)  # block 2
    b2 = frag.blocks()
    assert [b for b, _ in b2] == [0, 2]
    assert b2[0][1] == b1[0][1]  # block 0 unchanged
    frag.set_bit(0, 2)
    assert frag.blocks()[0][1] != b1[0][1]
    assert frag.block_data(2)[0].tolist() == [250]


def test_merge_block(frag):
    # local has (0,1); remote has (0,2). 2 participants, majority=1 -> union.
    frag.set_bit(0, 1)
    diffs = frag.merge_block(0, [([0], [2])])
    assert frag.row_count(0) == 2          # local gained (0,2)
    assert diffs == [([(0, 1)], [])]        # remote needs (0,1) set

    # 3 participants, majority=2: minority bits get cleared everywhere.
    # local={(0,1),(0,2)}, r1={(0,1)}, r2={(0,9)} -> consensus={(0,1)}.
    diffs = frag.merge_block(0, [([0], [1]), ([0], [9])])
    assert frag.row_count(0) == 1           # (0,2) lost its majority
    assert diffs[0] == ([], [])             # replica 1 already at consensus
    assert diffs[1][0] == [(0, 1)]          # replica 2 must set (0,1)
    assert diffs[1][1] == [(0, 9)]          # ... and clear (0,9)


def test_backup_roundtrip(tmp_path):
    f = Fragment(str(tmp_path / "a"), "i", "f", "standard", 0).open()
    f.import_bits([0, 1, 9], [5, 6, 7])
    buf = io.BytesIO()
    f.write_to(buf)
    f.close()

    g = Fragment(str(tmp_path / "b"), "i", "f", "standard", 0).open()
    buf.seek(0)
    g.read_from(buf)
    assert g.count() == 3
    assert g.row_count(9) == 1
    g.close()
    # restored file persists
    h = Fragment(str(tmp_path / "b"), "i", "f", "standard", 0).open()
    assert h.count() == 3
    h.close()


def test_torn_oplog_recovery(tmp_path):
    """A partial trailing op record (crash mid-append) must not brick the
    fragment: open recovers the valid prefix and rewrites the file."""
    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0).open()
    f.set_bit(0, 1)
    f.set_bit(0, 2)
    f.close()
    with open(path, "ab") as fh:
        fh.write(b"\x00\x07\x00")  # torn record
    f2 = Fragment(path, "i", "f", "standard", 0).open()
    # Lazy read: the valid op prefix applies, the torn tail is ignored
    # in place (every reader sees the same consistent prefix).
    assert f2.row_count(0) == 2
    assert f2.op_n == 2 and not f2._resident
    # The first WRITE faults in, which detects the torn tail and
    # rewrites the file via snapshot before appending the new op.
    f2.set_bit(0, 3)
    assert f2.op_n == 1  # clean rewrite + the one new op
    f2.close()
    f3 = Fragment(path, "i", "f", "standard", 0).open()
    assert f3.row_count(0) == 3
    f3.close()


def test_narrow_width_grows_and_persists(tmp_path):
    """Rows allocate words only up to the widest touched column
    (powers of two from 64): narrow shapes stay narrow across reopen,
    width grows transparently, and full-width APIs pad."""
    from pilosa_tpu.storage.fragment import WORDS64, Fragment

    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    f.import_bits([0] * 3 + [1] * 2, [1, 5, 4000, 7, 4095])
    assert f._w64 == 64  # 4096 columns
    assert f.count() == 5
    assert len(f.row_words(0)) == WORDS64  # padded API
    f.close()

    f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    assert f2._w64 == 64  # narrow file reopens narrow
    assert f2.count() == 5 and f2.row_count(0) == 3
    # touching a high column grows the width; bits survive
    f2.set_bit(0, 1048575)
    assert f2._w64 == WORDS64
    assert f2.row_count(0) == 4
    f2.close()

    f3 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    assert f3.count() == 6
    f3.close()


def test_narrow_matrix_top_with_wide_src(tmp_path):
    """TopN src bitmaps may be wider than a narrow fragment matrix:
    intersections trim to the matrix width, but the Tanimoto |src|
    denominator counts the FULL src."""
    import numpy as np

    from pilosa_tpu.storage.fragment import WORDS64, Fragment, TopOptions

    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    f.import_bits([0, 0, 1], [1, 2, 1])  # narrow rows
    src = np.zeros(WORDS64, dtype=np.uint64)
    src[0] = np.uint64(0b110)       # cols 1,2 (inside width)
    src[WORDS64 - 1] = np.uint64(1)  # one col far beyond width
    # plain src counts: |row ∩ src| ignores the out-of-width src bit
    top = f.top(TopOptions(src=src))
    assert top == [(0, 2), (1, 1)]
    # tanimoto: row0: inter=2, |A|=2, |B|=3 → 100·2/3 = 66.7 → ceil 67
    top = f.top(TopOptions(src=src, tanimoto_threshold=66))
    assert top == [(0, 2)]
    top = f.top(TopOptions(src=src, tanimoto_threshold=67))
    assert top == []
    f.close()


def test_import_value_duplicate_columns_last_wins(frag):
    """Duplicate columns in one batch apply sequentially — last value
    wins (ref: importValue fragment.go:1335 applies pairs in order);
    the vectorized clear-then-set must not OR the values together."""
    frag.import_value_bits([5, 5, 5], [3, 12, 9], 8)
    assert frag.field_value(5, 8) == (9, True)
    frag.import_value_bits([5], [1], 8)
    assert frag.field_value(5, 8) == (1, True)


def test_import_value_bits(frag):
    frag.import_value_bits([1, 2, 3], [10, 20, 30], 8)
    assert frag.field_value(1, 8) == (10, True)
    assert frag.field_value(2, 8) == (20, True)
    # Small FRESH-INSERT BSI imports ride the op log — (depth+2)
    # records per value (null sandwich + planes) — instead of
    # snapshotting per call.
    assert frag.op_n == 10 * 3
    # overwrite clears stale planes
    frag.import_value_bits([1], [255], 8)
    assert frag.field_value(1, 8) == (255, True)
    assert frag.field_sum(None, 8) == (305, 3)
    # Overwrites SNAPSHOT (op log reset): a torn op-log group replays
    # as null, which may only lose unacknowledged writes — column 1's
    # old value was acknowledged, so the old-or-new guarantee of the
    # reference's snapshot + atomic rename applies
    # (fragment.go:1335-1367).
    assert frag.op_n == 0


def test_import_value_overwrite_never_rides_oplog(tmp_path):
    """Any batch touching an existing (not-null) column snapshots, even
    when most of the batch is fresh inserts — the torn-group replay
    (null) may only erase unacknowledged writes, never an acknowledged
    value (ADVICE r3; ref ImportValue old-or-new via snapshot+rename,
    fragment.go:1335-1367)."""
    p = str(tmp_path / "frag")
    f = Fragment(p, "i", "f", "standard", 0).open()
    f.import_value_bits([100], [7], 8)          # fresh: op log
    assert f.op_n == 10
    f.import_value_bits([200, 100, 300], [1, 2, 3], 8)  # 100 = overwrite
    assert f.op_n == 0                          # snapshotted
    f.import_value_bits([400, 500], [4, 5], 8)  # all fresh again
    assert f.op_n == 20
    f.close()
    f2 = Fragment(p, "i", "f", "standard", 0).open()
    assert f2.field_value(100, 8) == (2, True)
    assert f2.field_value(400, 8) == (4, True)
    f2.close()


def test_cache_sidecar_persistence(tmp_path):
    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0, cache_type="ranked").open()
    f.import_bits([1, 1, 2], [0, 1, 0])
    f.close()
    f2 = Fragment(path, "i", "f", "standard", 0, cache_type="ranked").open()
    assert f2.cache.get(1) == 2
    assert f2.cache.get(2) == 1
    f2.close()


def test_small_import_appends_oplog_and_replays(tmp_path):
    """Small bulk imports take the batch op-log append path (no full
    snapshot) and must survive reopen via replay."""
    p = str(tmp_path / "frag")
    f = Fragment(p, "i", "f", "standard", 0).open()
    f.import_bits([0, 0, 5], [1, 9, 3])
    assert f.op_n == 3  # appended, not snapshotted
    size_after_small = os.path.getsize(p)
    f.close()

    f2 = Fragment(p, "i", "f", "standard", 0).open()
    assert f2.count() == 3
    assert f2.row_count(0) == 2 and f2.row_count(5) == 1
    f2.close()
    assert size_after_small > 0


def test_large_import_snapshots(tmp_path):
    from pilosa_tpu.storage.fragment import MAX_OPN

    p = str(tmp_path / "frag")
    f = Fragment(p, "i", "f", "standard", 0).open()
    n = MAX_OPN + 10
    f.import_bits([0] * n, list(range(n)))
    assert f.op_n == 0  # snapshot reset
    f.close()
    f2 = Fragment(p, "i", "f", "standard", 0).open()
    assert f2.count() == n
    f2.close()


def test_fragment_file_lock(tmp_path):
    """Double-open of the same fragment file is rejected while the
    first holder lives (ref: syscall.Flock fragment.go:203-205).
    flock is per-(process, fd) so the second opener is a subprocess."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = str(tmp_path / "frag")
    f = Fragment(path, "i", "f", "standard", 0).open()
    f.set_bit(1, 2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    code = subprocess.run(
        [sys.executable, "-c", f"""
import sys
from pilosa_tpu import errors as perr
from pilosa_tpu.storage.fragment import Fragment
try:
    Fragment({path!r}, "i", "f", "standard", 0).open()
except perr.ErrFragmentLocked:
    sys.exit(42)
sys.exit(0)
"""],
        env=env, timeout=120,
    ).returncode
    assert code == 42
    f.close()
    # after close the lock is released and the bit survived
    f2 = Fragment(path, "i", "f", "standard", 0).open()
    assert f2.row_count(1) == 1
    f2.close()


def test_high_column_window_stays_narrow(tmp_path):
    """Data clustered in HIGH columns allocates only its cluster's
    window, not the full slice (VERDICT r1: a sparse row touching a
    high column used to allocate full width)."""
    hi = SLICE_WIDTH - 1
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    assert f.set_bit(3, hi)
    assert f.set_bit(3, hi - 100)
    assert f._w64 == 64 and f._w64_base == WORDS64 - 64
    assert f.row_count(3) == 2
    words = f.row_words(3)
    assert words.shape == (WORDS64,)
    assert bool(words[WORDS64 - 1] >> 63 & 1)

    # Device row scatters at the window offset.
    dev = np.asarray(f.device_row(3)).view(np.uint64)
    assert (dev == words).all()

    # Clears outside the window are no-ops and don't grow it.
    assert not f.clear_bit(3, 5)
    assert f._w64 == 64

    # Anti-entropy positions are global, not window-local.
    rows, cols = f.block_data(0)
    assert sorted(cols.tolist()) == [hi - 100, hi]

    # Persistence round-trips narrow: the file stores real containers,
    # and reopen re-derives the same window.
    f.snapshot()
    f.close()
    f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    assert f2.row_count(3) == 2
    # The lazy (pre-fault-in) window is container-granular: it covers
    # the high cluster without touching payloads.
    base32, width32 = f2.win32()
    assert base32 * 32 <= hi - 100 and hi < (base32 + width32) * 32
    assert width32 < 2 * WORDS64
    # A full fault-in re-derives the exact word-granular window.
    with f2.mu:
        pass
    assert f2._w64 == 64 and f2._w64_base == WORDS64 - 64
    assert sorted(f2.block_data(0)[1].tolist()) == [hi - 100, hi]
    f2.close()


def test_window_grows_to_cover_mixed_spans(tmp_path):
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    f.set_bit(1, SLICE_WIDTH - 1)      # narrow high window
    f.set_bit(1, 0)                    # now spans the whole slice
    assert f._w64 == WORDS64 and f._w64_base == 0
    assert f.row_count(1) == 2
    assert sorted(f.block_data(0)[1].tolist()) == [0, SLICE_WIDTH - 1]
    f.close()


def test_window_mid_slice_import(tmp_path):
    """A bulk import clustered mid-slice windows around its span and
    serves TopN with a full-width src filter correctly."""
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0,
                 cache_type="ranked").open()
    base_col = 7 * (SLICE_WIDTH // 16)  # container 7
    cols = [base_col + c for c in range(0, 3000, 3)]
    f.import_bits([1] * len(cols), cols)
    f.import_bits([2] * 500, [base_col + c for c in range(500)])
    assert f._w64 < WORDS64 and f._w64_base > 0
    src = np.zeros(WORDS64, dtype=np.uint64)
    for c in cols[:100]:
        src[c >> 6] |= np.uint64(1) << np.uint64(c & 63)
    pairs = f.top(TopOptions(n=2, src=src))
    expect1 = len(set(cols[:100]))
    assert pairs[0] == (1, expect1)
    f.close()


def test_amortized_snapshot_policy(tmp_path):
    """Bulk loading in B equal batches must NOT snapshot per batch
    (the reference's fixed 2000-op cadence rewrites the whole file
    every batch — O(total²) IO); the threshold scales with the
    cardinality at the last snapshot, so rewrites land at
    geometrically growing sizes while the op log stays bounded."""
    import numpy as np

    from pilosa_tpu.storage.fragment import (
        MAX_OPN, OPLOG_MAX_OPS, Fragment,
    )

    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    snaps = [0]
    real = f.snapshot

    def counting():
        snaps[0] += 1
        real()

    f.snapshot = counting
    rng = np.random.default_rng(3)
    batches = 24
    per = 6000  # every batch far exceeds the reference cadence of 2000
    for b in range(batches):
        cols = rng.choice(100_000, size=per, replace=False)
        rows = np.full(per, b % 7, dtype=np.uint64)
        f.import_bits(rows, cols.astype(np.uint64))
        limit = max(MAX_OPN, min(f._snap_card // 2, OPLOG_MAX_OPS))
        assert f.op_n <= limit
    # Fixed cadence would snapshot ~24 times; geometric growth keeps it
    # logarithmic in the total.
    assert 1 <= snaps[0] <= 7, snaps[0]

    # Reopen replays the (large) op log correctly.
    counts = {r: int(c) for r, c in zip(f._phys_rows, f._row_counts)}
    f.close()
    f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    with f2.mu:
        f2._fault_in_locked()
    assert {r: int(c)
            for r, c in zip(f2._phys_rows, f2._row_counts)} == counts
    f2.close()


def test_snapshot_threshold_resets_on_restore(tmp_path):
    """A backup restore rewrites the file (new snapshot): the
    amortized op-log threshold must follow the RESTORED cardinality,
    not the pre-restore fragment's (review r3: a 10M-bit fragment
    restored to 1k bits must not retain a 4M-op append budget)."""
    import io

    import numpy as np

    from pilosa_tpu.storage.fragment import MAX_OPN, Fragment

    big = Fragment(str(tmp_path / "big"), "i", "f", "standard", 0).open()
    rng = np.random.default_rng(5)
    cols = rng.choice(1_000_000, size=400_000, replace=False)
    big.import_bits(np.zeros(400_000, dtype=np.uint64),
                    cols.astype(np.uint64))
    big.snapshot()
    assert big._snap_card == 400_000

    small = Fragment(str(tmp_path / "small"), "i", "f", "standard",
                     0).open()
    small.import_bits(np.zeros(50, dtype=np.uint64),
                      np.arange(50, dtype=np.uint64))
    buf = io.BytesIO()
    small.write_to(buf)
    buf.seek(0)
    big.read_from(buf)
    assert big._snap_card == 50
    assert not big._op_log_room(MAX_OPN + 1)  # tiny fragment, tiny budget
    small.close()
    big.close()


def test_bsi_import_value_rides_oplog(tmp_path):
    """Chunked BSI value loads append to the op log instead of paying
    a whole-file snapshot per chunk, and values (including overwrites)
    survive close + reopen through last-op-wins replay."""
    import numpy as np

    from pilosa_tpu.storage.fragment import Fragment

    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    # Seed enough cardinality that the amortized threshold has room.
    rng = np.random.default_rng(11)
    seed_cols = rng.choice(500_000, size=60_000, replace=False)
    f.import_bits(np.zeros(60_000, dtype=np.uint64),
                  seed_cols.astype(np.uint64))
    f.snapshot()
    snaps = [0]
    real = f.snapshot
    f.snapshot = lambda: (snaps.__setitem__(0, snaps[0] + 1), real())

    depth = 8
    cols1 = np.arange(1000, dtype=np.uint64)
    vals1 = rng.integers(0, 200, size=1000, dtype=np.uint64)
    f.import_value_bits(cols1, vals1, depth)
    # Second chunk of FRESH columns (disjoint — overwrites snapshot,
    # see test_import_value_overwrite_never_rides_oplog).
    cols2 = np.arange(1000, 1500, dtype=np.uint64)
    vals2 = rng.integers(0, 200, size=500, dtype=np.uint64)
    f.import_value_bits(cols2, vals2, depth)
    assert snaps[0] == 0, "chunked fresh BSI load must not snapshot per call"
    assert f.op_n == (depth + 2) * 1500  # null sandwich + planes per value

    def read_values(frag):
        out = {}
        nn = frag._row_index.get(depth)
        if nn is None:
            return out
        for c in range(1500):
            w, b = c >> 6, c & 63
            if not (frag._matrix[nn][w] >> np.uint64(b)) & np.uint64(1):
                continue
            v = 0
            for i in range(depth):
                p = frag._row_index.get(i)
                if p is not None and (
                        frag._matrix[p][w] >> np.uint64(b)) & np.uint64(1):
                    v |= 1 << i
            out[c] = v
        return out

    want = {int(c): int(v) for c, v in zip(cols1, vals1)}
    want.update({int(c): int(v) for c, v in zip(cols2, vals2)})
    assert read_values(f) == want
    f.close()

    f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    with f2.mu:
        f2._fault_in_locked()
    assert read_values(f2) == want
    f2.close()


def test_bsi_torn_group_reads_null_not_phantom(tmp_path):
    """A crash can tear a FRESH-insert BSI op-log group at any byte.
    The null sandwich (REMOVE not-null first, ADD not-null last,
    column-major) guarantees the torn column reads as NULL — never as
    a phantom partial value (review r3 atomicity finding). Overwrites
    never reach the op log at all (they snapshot, ADVICE r3) — the
    second half checks that, so a tear can never destroy an
    acknowledged value."""
    import numpy as np

    from pilosa_tpu.roaring.codec import OP_SIZE
    from pilosa_tpu.storage.fragment import Fragment

    depth = 8
    p = str(tmp_path / "frag")
    f = Fragment(p, "i", "f", "standard", 0).open()
    # Seed cardinality so the op-log path engages; snapshot to fix the
    # file base. Column 5 has NO value yet.
    f.import_bits(np.zeros(30_000, dtype=np.uint64),
                  np.arange(30_000, dtype=np.uint64) + 64)
    f.snapshot()
    size_before = __import__("os").path.getsize(p)
    # Fresh insert of value 255 — op-log group of depth+2 records —
    # then tear the group at every possible byte.
    f.import_value_bits(np.array([5], dtype=np.uint64),
                        np.array([255], dtype=np.uint64), depth)
    f.close()
    import os

    full = open(p, "rb").read()
    group_bytes = (depth + 2) * OP_SIZE
    assert os.path.getsize(p) == size_before + group_bytes
    for cut in range(1, group_bytes):  # torn anywhere inside the group
        with open(p, "wb") as out:
            out.write(full[: size_before + cut])
        g = Fragment(p, "i", "f", "standard", 0).open()
        with g.mu:
            g._fault_in_locked()
        val, ok = g.field_value(5, depth)
        # Every tear inside the group reads NULL — even when several
        # plane ADDs are durable, the trailing ADD not-null is not, so
        # no phantom partial value is visible.
        assert not ok, (cut, val)
        g.close()
    # The complete group replays to the inserted value.
    with open(p, "wb") as out:
        out.write(full)
    g = Fragment(p, "i", "f", "standard", 0).open()
    with g.mu:
        g._fault_in_locked()
    assert g.field_value(5, depth) == (255, True)
    # OVERWRITE of the now-acknowledged value: must snapshot, not
    # append — after it the op log is empty and the file carries the
    # new value via atomic rename (old-or-new, never null).
    g.import_value_bits(np.array([5], dtype=np.uint64),
                        np.array([0], dtype=np.uint64), depth)
    assert g.op_n == 0
    g.close()
    h = Fragment(p, "i", "f", "standard", 0).open()
    with h.mu:
        h._fault_in_locked()
    assert h.field_value(5, depth) == (0, True)
    h.close()
