"""Fragment — the unit of storage, compute, and replication.

A fragment is one (index, frame, view, slice): a 2^20-column bitmap
matrix (ref: fragment.go:50 SliceWidth, :157-247 storage lifecycle).

TPU-first design
----------------
The reference mmaps a roaring file and computes on containers in place.
Here the fragment keeps **two mirrors** of the same bits:

- a host ``numpy uint64[capacity, 16384]`` row matrix — the mutation
  target, serialization source, and iteration surface (ascending-position
  iteration order matches the reference's container walk, which the
  anti-entropy block checksums require);
- a device ``uint32[capacity, 32768]`` copy in HBM — the compute surface
  for every query kernel. A little-endian view makes the two layouts
  identical, so refresh is a pure DMA with no repacking.

Mutations follow the reference's durability design exactly: every
set/clear appends a 13-byte op-log record to the open roaring file
(roaring.go:740), and once the log outgrows the amortized threshold
(``_op_log_room`` — scales with fragment cardinality, unlike the
reference's fixed 2000-op cadence that makes sustained writes O(n²))
the whole file is rewritten via an atomic temp-file rename
(``snapshot()``, fragment.go:1369-1438).
Device refresh is batched: dirty rows are scattered into HBM only when a
query actually needs the device matrix — the mutation path never blocks
on the TPU (the analog of the reference's opN write-buffer cadence).

Row capacity grows in powers of two so jitted kernel shapes are bucketed
and recompilation is bounded.
"""
import itertools
import json
import logging
import os
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from pilosa_tpu import SLICE_WIDTH, WORDS_PER_SLICE
from pilosa_tpu import errors as perr
from pilosa_tpu import faults
from pilosa_tpu import querystats
from pilosa_tpu import stats as stats_mod
from pilosa_tpu import tracing
from pilosa_tpu import native
from pilosa_tpu.observe import heatmap as heatmap_mod
from pilosa_tpu.observe import kerneltime as kerneltime_mod
from pilosa_tpu.ops import bitops
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.roaring import codec
from pilosa_tpu.storage.cache import new_cache
from pilosa_tpu.utils.xxhash import xxhash64

from pilosa_tpu import lockcheck

_LOG = logging.getLogger("pilosa_tpu.storage.fragment")

WORDS64 = SLICE_WIDTH // 64  # 16384 host words per row

# Snapshot after this many op-log records (ref: fragment.go:67 MaxOpN).
MAX_OPN = 2000
# A snapshot rewrites the whole file — O(cardinality) — so gating it at
# the reference's FIXED op cadence (fragment.go:67 MaxOpN=2000) makes
# sustained writes and batched bulk loads O(total²): every 2000 ops
# re-serializes everything written so far. The snapshot threshold here
# scales with the cardinality at the last snapshot instead (append
# while ops ≤ max(MAX_OPN, card/2)), so rewrites land at geometrically
# growing sizes — O(total) amortized — capped by OPLOG_MAX_OPS to keep
# the on-disk op region (13 B/op) and reopen replay bounded; replay is
# a vectorized parse + two scatters (codec.parse_ops/final_ops), not a
# per-record walk, so a full log replays in well under a second.
OPLOG_MAX_OPS = 4_000_000

# Rows per anti-entropy checksum block (ref: fragment.go:62 HashBlockSize).
HASH_BLOCK_SIZE = 100

_CONTAINERS_PER_ROW = SLICE_WIDTH // (1 << 16)  # 16
_WORDS64_PER_CONTAINER = 1024

# Rows allocate only a power-of-2 WINDOW of 64-bit words covering the
# touched column span — width from 64 words (4096 columns) up, base
# width-aligned anywhere in the slice. Row-heavy / column-narrow
# datasets (e.g. 500k molecule rows x 4096 fingerprint bits, the
# reference's chemical-similarity showcase) cost megabytes instead of
# 128 KB per row, and data clustered in HIGH columns costs its
# cluster's width, not the full slice (VERDICT r1: within-row paging).
# Words outside the window are zero by construction; external APIs pad
# on the way out.
_MIN_W64 = 64

# Sentinel: a lazy (evicted, container-granular) read declined; the
# caller must take the resident path instead. Distinct from None and
# from any legitimate zero-filled result.
_NOT_LAZY = object()

# Rows copied from one device to another because a caller named a device
# other than the one holding the row's only clean copy (the shard
# boundaries of a sharded stack moved), process lifetime: /debug/vars
# ``crossDeviceRows``, beside the per-query key; per thread for the span
# of the build that moved them (executor ``stack.shard`` tag ``moved``).
_cross_mu = threading.Lock()
_cross_rows = 0
_cross_here = threading.local()


def cross_device_rows(this_thread=False):
    return getattr(_cross_here, "rows", 0) if this_thread else _cross_rows


def _zeros(shape, device):
    return jnp.zeros(shape, dtype=jnp.uint32, device=device)


def _on(arr, device, rows):
    """``arr`` on ``device``: as it is where it lives there (or no
    device is named), else copied over the interconnect, and its
    ``rows`` counted in ``crossDeviceRows``."""
    if device is None or device in arr.devices():
        return arr
    global _cross_rows
    with _cross_mu:
        _cross_rows += rows
    _cross_here.rows = getattr(_cross_here, "rows", 0) + rows
    querystats.add("crossDeviceRows", rows)
    return jax.device_put(arr, device)


def _memo_moved(memo, key, version, device, rows):
    """A named device's copy of ``key``, moved from a clean copy that
    ``memo`` holds on another device (``_on``, counted), or None. Memo
    keys end in the device they name (None: the default device,
    uncommitted): a copy committed to one chip never reaches a caller
    that named none, where it would meet another chip's rows in one
    per-slice op. The caller keeps the moved copy beside the first, so
    a row that two stacks lay out differently moves once, not back and
    forth."""
    if device is None:
        return None
    for k, (v, arr) in list(memo.items()):
        if v == version and k[:-1] == key:
            return _on(arr, device, rows)
    return None


def stage_rows(frags, row_id, base32, width32, device):
    """Fill ``device_row_win``'s memo on ``device`` with ``row_id`` of
    each of ``frags`` in ONE transfer: the host words of every resident
    fragment that would upload the row by itself (held at exactly this
    window, no clean mirror, no copy in the memo on any device) go to
    the device in one ``jax.device_put`` of the list, not one call a
    row. What it skips, ``device_row_win`` makes as before; a fragment
    written meanwhile keeps no copy (its version moved)."""
    todo = []
    for f in frags:
        if f is None or not f._resident:
            continue
        with f.mu:
            phys = f._row_index.get(row_id)
            fw = f._w64 * 2
            if phys is None or (f._w64_base * 2, fw) != (base32, width32):
                continue
            if (f._dev is not None and f._dev.shape == (f._cap, fw)
                    and phys not in f._dirty):
                continue   # the mirror serves it
            key = (phys, base32, width32)
            if any(k[:-1] == key and v[0] == f._version
                   for k, v in f._row_dev.items()):
                continue
            todo.append((f, key, f._version,
                         f._matrix[phys].view(np.uint32).copy()))
    if not todo:
        return
    rows = jax.device_put([t[3] for t in todo], device)
    for (f, key, version, _), row in zip(todo, rows):
        with f.mu:
            if f._version == version:
                if len(f._row_dev) >= 64:
                    f._row_dev.clear()
                f._row_dev[key + (device,)] = (version, row)


# Process-wide cap on live LazyReaders. CPython's mmap holds a dup'd
# file descriptor for the mapping's lifetime, so at 100B scale (~95k
# evicted fragments) READERS — not bytes — are the scarce resource:
# unbounded lazy reads exhaust RLIMIT_NOFILE (20k here) long before
# the host-byte governor sees pressure. LRU over fragments holding a
# reader; creating one past the cap drops the oldest fragment's
# reader ONLY — its compressed containers, count memos, and block
# memos stay, and the memo-first read paths serve without it.
try:
    MAX_LAZY_READERS = int(os.environ.get("PILOSA_TPU_MAX_READERS",
                                          "8192"))
except ValueError:  # malformed env must not crash import (cli/server)
    MAX_LAZY_READERS = 8192
_reader_mu = lockcheck.register("storage.fragment._reader_mu",
                                threading.Lock())
_reader_lru = {}  # Fragment -> None (dict preserves insertion order)


def _note_reader(frag):
    """Record reader use (LRU recency) and evict past the cap.
    Victims are acquired non-blocking — a contended fragment is
    skipped, never deadlocked on (the governor's unload discipline);
    the next creation retries the eviction."""
    global _reader_lru
    victims = []
    with _reader_mu:
        _reader_lru.pop(frag, None)
        _reader_lru[frag] = None
        while len(_reader_lru) > max(MAX_LAZY_READERS, 1):
            v = next(iter(_reader_lru))
            if v is frag:
                break
            del _reader_lru[v]
            victims.append(v)
    for v in victims:
        if not v._drop_reader() and v._lazy is not None:
            # Lock-contended victim still holds its reader: put it
            # back at the OLDEST end so the very next eviction retries
            # it — dropping it from the LRU while the fd lives would
            # erode the cap silently, and re-inserting at the
            # recently-used end would defer the retry for a whole LRU
            # cycle. O(n) rebuild, but contended victims are rare.
            with _reader_mu:
                if v not in _reader_lru:
                    _reader_lru = {v: None, **_reader_lru}


def _forget_reader(frag):
    with _reader_mu:
        _reader_lru.pop(frag, None)

# Process-wide mutation epoch: bumped on EVERY fragment version change
# and on fragment open/close. Executors use it as an O(1) "has anything
# changed since I cached this?" test — at 10k-slice scale, re-checking
# per-fragment version tokens on every query costs more than the query's
# device work. Epoch equality is sufficient (never necessary) for cache
# validity: any mutation anywhere invalidates the fast path and falls
# back to the precise per-fragment tokens. The increment is locked —
# a bare `+= 1` is a read-modify-write that can lose counts under
# concurrent writers (readers need no lock: they only compare values).
_index_epochs = {}   # index name -> bump count
_unattributed = 0    # bumps whose index scope is unknown (attr stores)
_epoch_mu = lockcheck.register("storage.fragment._epoch_mu",
                               threading.Lock())

# Replica mode (PILOSA_TPU_READ_ONLY=1, set by WorkerPool for
# exec-reads worker processes — see server/workers.py): this process
# serves reads from the master's data files and must never write them
# — no flock (the master holds LOCK_EX for its lifetime), no
# torn-tail repair snapshot (a live master mid-append is not a crash),
# no cache-sidecar flush, no op-log appends.
REPLICA = os.environ.get("PILOSA_TPU_READ_ONLY", "0") == "1"

# Cross-process epoch publication: the master mmaps two u64 counters
# that replica workers poll per request to decide whether their cached
# state is still valid (read-your-writes: a write bumps word 0 BEFORE
# its HTTP response, so the same client's next read sees a newer count
# and triggers a refresh). Word 0 is this process's epoch total;
# word 1 is the CLUSTER epoch version (cluster/epochs.py registry
# observations, 0 = single-node/cold) so multi-node worker caches go
# cold — never stale — when peer visibility lapses.
_epoch_total = 0     # all bumps, any scope (maintained under _epoch_mu)
_epoch_mm = None
_cluster_version = 0

_PUBLISH_BYTES = 16


def publish_epochs(path):
    """Master side: mirror every epoch bump into an mmap'd counter
    file readable by replica workers."""
    global _epoch_mm
    with open(path, "ab") as f:
        pass
    f = open(path, "r+b")
    f.truncate(_PUBLISH_BYTES)
    import mmap as _mmap

    _epoch_mm = _mmap.mmap(f.fileno(), _PUBLISH_BYTES)
    f.close()
    with _epoch_mu:
        _publish_locked()


def publish_cluster_version(version):
    """Master side, multi-node: publish the cluster epoch-vector
    version (word 1). ``0`` means COLD — worker caches must not
    replay. Called by the epoch registry on every observed change and
    by the staleness monitor."""
    global _cluster_version
    with _epoch_mu:
        _cluster_version = int(version)
        _publish_locked()


def open_published_epochs(path):
    """Replica side: read-only mmap of the master's counters; returns
    a zero-arg reader yielding ``(local_total, cluster_version)``."""
    import mmap as _mmap
    import os as _os
    import struct as _struct

    size = min(_os.path.getsize(path), _PUBLISH_BYTES)
    f = open(path, "rb")
    mm = _mmap.mmap(f.fileno(), size, prot=_mmap.PROT_READ)
    f.close()
    if size < _PUBLISH_BYTES:  # legacy 8-byte file from an old master
        return lambda: (_struct.unpack_from("<Q", mm, 0)[0], 0)
    return lambda: _struct.unpack_from("<QQ", mm, 0)


def epoch_total():
    """Process-wide bump total (any index, any scope) — the memo key
    for cheap has-anything-changed checks (epoch header caching)."""
    return _epoch_total


def _publish_locked():
    if _epoch_mm is not None:
        import struct as _struct

        _struct.pack_into("<QQ", _epoch_mm, 0, _epoch_total,
                          _cluster_version)


_LOCKED_ROOTS = set()  # dir prefixes covered by a holder-level flock

HOLDER_LOCK_NAME = ".holder.lock"


def register_locked_root(path):
    """Announce that ``path`` (a holder data dir) is protected by one
    directory-level flock: fragments beneath it skip their per-file
    lock fd (see Fragment._acquire_lock)."""
    _LOCKED_ROOTS.add(os.path.abspath(path) + os.sep)


def unregister_locked_root(path):
    _LOCKED_ROOTS.discard(os.path.abspath(path) + os.sep)


def try_flock(path, err_cls, transient=False):
    """Nonblocking exclusive flock on ``path`` — THE shared
    implementation for holder-level and per-fragment locks (one copy
    of the BlockingIOError / non-POSIX handling). Returns the held
    file handle; ``transient`` probes and releases immediately
    (returns None) — used to detect a conflicting owner without
    holding an fd. Raises ``err_cls`` when another process holds it."""
    lock = open(path, "ab")
    try:
        import fcntl

        fcntl.flock(lock.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
    except BlockingIOError:
        lock.close()
        raise err_cls()
    except ImportError:  # non-POSIX platform
        pass
    if transient:
        lock.close()  # close releases the flock
        return None
    return lock


_EMPTY_DIGEST = b"\x00" * 8
_MIX_C0 = np.uint64(0x9E3779B97F4A7C15)
_MIX_C1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX_C2 = np.uint64(0x94D049BB133111EB)


def _mix64(x):
    """Vectorized splitmix64 finalizer over a uint64 ndarray: the
    per-global-word-position pseudorandom constants of the digest's
    multilinear hash (public splitmix64 constants; uint64 arithmetic
    wraps mod 2^64 by numpy's C semantics)."""
    z = x + _MIX_C0
    z = (z ^ (z >> np.uint64(30))) * _MIX_C1
    z = (z ^ (z >> np.uint64(27))) * _MIX_C2
    return z ^ (z >> np.uint64(31))


def _bump_epoch(index=None):
    global _unattributed, _epoch_total
    with _epoch_mu:
        _epoch_total += 1
        if index is None:
            _unattributed += 1
        else:
            _index_epochs[index] = _index_epochs.get(index, 0) + 1
        _publish_locked()


def mutation_epoch(index=None):
    """Mutation epoch for validity checks. With ``index``, the scoped
    view: per-index bump count + every unattributed bump — so a
    write-heavy index no longer flushes the epoch-validated memos of
    other (e.g. read-only dashboard) indexes, while an index-blind
    writer still invalidates everything. Both counters are monotone,
    so the sum changes on every relevant bump. Without ``index``, the
    process-wide count (any mutation anywhere)."""
    if index is None:
        # Snapshot under the lock: sum() iterates the dict, and a
        # concurrent first bump of a NEW index resizes it mid-iteration
        # (per-index reads stay lockless — they are single lookups).
        with _epoch_mu:
            return sum(_index_epochs.values()) + _unattributed
    return _index_epochs.get(index, 0) + _unattributed


class TopOptions:
    """TopN options (ref: fragment.go:1004-1021). The filter bitmap
    comes as ``src`` (host words, whatever built them) or, where it is
    a row of the very fragment ``top`` scans, as that row's id in
    ``src_row``: the scan's program then reads the probe from the HBM
    mirror by itself. At most one of the two is given. ``top`` writes
    ``selected`` back: where the selection of a scan with a src ran,
    ``device`` | ``host`` | ``overflow`` (None: nothing was scanned)."""

    def __init__(self, n=0, src=None, row_ids=None, filter_row_ids=None,
                 min_threshold=0, tanimoto_threshold=0, src_row=None):
        self.selected = None
        self.n = n
        self.src = src                      # np.uint64[WORDS64] filter bitmap
        self.src_row = src_row              # or: the id of this fragment's row
        self.row_ids = row_ids              # explicit candidate rows
        self.filter_row_ids = filter_row_ids  # attr-filtered allowed rows
        self.min_threshold = min_threshold
        self.tanimoto_threshold = tanimoto_threshold


class _ResidencyLock:
    """Re-entrant fragment lock that faults host state in on entry.

    Every fragment operation (internal and the executor's external
    ``with frag.mu:`` uses) serializes on this lock, which makes its
    ``__enter__`` the single choke point where an unloaded fragment —
    lazily opened at holder startup, or evicted by the host-memory
    governor — reloads its row matrix from the roaring file. The
    analog of the OS faulting an mmap'd page back in."""

    def __init__(self, frag):
        self._frag = frag
        self._lock = lockcheck.register("storage.Fragment.mu",
                                        threading.RLock(),
                                        allow_device_sync=True)

    def __enter__(self):
        self._lock.acquire()
        try:
            self._frag._fault_in_locked()
        except BaseException:
            self._lock.release()
            raise
        return self

    def __exit__(self, *exc):
        self._lock.release()

    def acquire_raw(self, blocking=True):
        """Acquire WITHOUT faulting in (open/unload bookkeeping).
        With blocking=False returns whether the lock was taken."""
        return self._lock.acquire(blocking=blocking)

    def release_raw(self):
        self._lock.release()

    def owned(self):
        """True iff the CURRENT thread holds this lock."""
        return self._lock._is_owned()


class Fragment:
    _UID_SEQ = itertools.count()

    def __init__(self, path, index, frame, view, slice_num,
                 cache_type="ranked", cache_size=50000):
        self.path = path
        self.index = index
        self.frame = frame
        self.view = view
        self.slice = slice_num
        self.cache_type = cache_type
        self._cache = new_cache(cache_type, cache_size)
        self.stats = stats_mod.NOP
        self.events = None  # flight recorder, view-propagated
        # process-unique id: cache validity tokens pair it with _version
        # so a deleted+recreated fragment can never alias a cache entry
        self._uid = next(self._UID_SEQ)
        # Host-memory governor (storage/memgov.py) wired by the owning
        # View; None = standalone fragment, always resident once used.
        self.governor = None
        self._last_used = 0
        self._opened = False      # open() ran (files + flock held)
        self._resident = False    # host matrices loaded
        self._faulting = False    # re-entrancy guard during fault-in
        self._cache_loaded = False

        self.mu = _ResidencyLock(self)
        self._cap = 0
        self._w64 = _MIN_W64   # window width in 64-bit words (power of 2)
        self._w64_base = 0     # window base word (multiple of _w64)
        self._matrix = np.zeros((0, _MIN_W64), dtype=np.uint64)
        self._row_counts = np.zeros(0, dtype=np.int64)
        self._row_index = {}      # rowID -> physical row
        self._phys_rows = []      # physical row -> rowID
        self._phys_arr = None     # (that list, its uint64 array)
        self._cache_mask = None   # (that array, cache ids, rows in cache)
        self.max_row_id = 0

        self.op_n = 0
        self._snap_card = None    # cardinality at last snapshot
        self._failed = None       # fail-stop latch: first storage fault
        self._op_file = None
        self._lock_file = None
        self._version = 0         # bumped on every mutation
        self._dev = None
        self._dev_version = -1
        self._dirty = set()       # physical rows stale on device
        # (depth, base32, width32, device) -> (version, jnp planes);
        # device None: the default device (see _memo_moved)
        self._planes_cache = {}
        # (phys, base32, width32, device) -> (version, jnp row)
        self._row_dev = {}
        self._rc_dev = None       # (version, jnp int32 row counts) memo
        self._elig_dev = None     # (host cache mask, jnp bool[cap]) memo
        # Container-granular read path for EVICTED fragments: an mmap-
        # backed codec.LazyReader + per-row host memo, so a query
        # touching one row of an unloaded fragment decodes O(that
        # row's containers), not the whole file — and never faults the
        # fragment in (ref: mmap page granularity, fragment.go:190-247).
        self._lazy = None
        self._lazy_rows = {}      # row_id -> {sub: uint64[1024]}
        self._lazy_bytes = 0      # memoized lazy block bytes
        self._lazy_cache_ids = None  # sidecar TopN ids (evicted reads)
        self._lazy_counts = {}    # row_id -> exact count (evicted reads)
        self._win32_memo = None   # (version, (base32, width32) | None)
        self._digest_memo = None  # (version, 8-byte digest)
        # Compressed serving tier (ops/containers.py): phys ->
        # (version, Container) for ARRAY/RUN rows (dense rows wrap the
        # existing device mirrors per call — memoizing them here would
        # pin 128 KB rows past the _row_dev cap), plus the last format
        # each row served as (conversion detection) and the
        # pilosa_container_conversions_total contribution.
        self._cont_dev = {}
        self._cont_fmt = {}
        self._conversions = 0

    # ------------------------------------------------------------------ io

    @property
    def cache(self):
        """TopN cache; reading it faults the fragment in (the sidecar
        ids are only re-counted against loaded row data)."""
        if self._opened and not self._resident:
            with self.mu:  # __enter__ runs the fault-in
                pass
        return self._cache

    @property
    def cache_path(self):
        return self.path + ".cache"

    def open(self):
        """Open files + flock; host state loads lazily on first touch
        (the reference's mmap likewise reads no page at open —
        fragment.go:190-247)."""
        self.mu.acquire_raw()
        try:
            if self._opened:
                return self
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            if not REPLICA:
                if not (os.path.exists(self.path)
                        and os.path.getsize(self.path) > 0):
                    with open(self.path, "wb") as f:
                        f.write(codec.serialize({}))
                self._acquire_lock()
            # Op append handle opens lazily on first WRITE: an eager
            # fd per fragment exhausts RLIMIT_NOFILE (20k hard cap
            # here) at 10k-slice scale when most fragments only serve
            # reads.
            self._op_file = None
            self.op_n = 0  # the fault-in / lazy parse sets the real value
            self._failed = None  # reopen clears the fail-stop latch
            self._opened = True
            _bump_epoch(self.index)  # a new fragment object is now reachable
        finally:
            self.mu.release_raw()
        return self

    def _fault_in_locked(self):
        """Load host state from the roaring file (runs under the
        fragment lock, via _ResidencyLock.__enter__)."""
        if self._resident or self._faulting or not self._opened:
            if self._resident and self.governor is not None:
                self.governor.touch(self)
            return
        self._faulting = True
        try:
            # Becoming resident means mutations (and snapshots) may
            # follow — the lazy reader's view of the file goes stale.
            self._drop_lazy_locked()
            # open/read stay OUTSIDE the quarantine scope: an OSError
            # here is the ENVIRONMENT failing (EMFILE, EIO, EACCES),
            # not the file's content — quarantining a healthy file on
            # a transient fd-exhaustion would silently vanish its data
            # behind an empty replacement. I/O errors propagate (and
            # at boot, partial-open skips the index instead).
            with open(self.path, "rb") as f:
                raw = f.read()
            if (faults.ACTIVE.enabled
                    and faults.ACTIVE.fire("fragment.read.corrupt")):
                raw = bytes(255 - b for b in raw)  # mutilate in place
            try:
                blocks, self.op_n, torn = codec.deserialize(raw)
            except Exception as e:  # noqa: BLE001 — ANY undecodable
                # CONTENT quarantines: corruption surfaces as
                # ValueError from the decoder's own checks but as
                # struct.error (NOT a ValueError subclass) from a
                # truncated meta region — a narrow catch here turned
                # the most common real rot into a 500-forever
                # fragment.
                if REPLICA:
                    # Never rewrite a master's files from a replica; a
                    # transient mid-write read can also land here.
                    raise
                blocks, torn = self._quarantine_locked(e), False
                self.op_n = 0
            self._load_blocks(blocks)
            if self._snap_card is None:
                # Back-fill the amortized-snapshot reference point
                # HERE, before any new mutation lands: the loaded
                # cardinality approximates the last snapshot (off only
                # by the existing log's net effect) — back-filling
                # later, at the gate, would fold the in-flight batch
                # into the threshold and double the op-log bound.
                self._snap_card = int(self._row_counts.sum())
            if torn and not REPLICA:
                # Crash mid-append left a partial op record; rewrite
                # the file from the recovered state so future appends
                # are valid. A replica may read a LIVE master
                # mid-append — the valid prefix is simply the
                # pre-append state, never repaired from here.
                try:
                    self.snapshot()
                except OSError as e:
                    # The repair couldn't land (ENOSPC): serve the
                    # recovered prefix read-only rather than append
                    # after a tail of unknown validity.
                    self._fail_stop_locked(e)
            self._resident = True
            if not self._cache_loaded:
                self._open_cache()
                self._cache_loaded = True
        finally:
            self._faulting = False
        if self.governor is not None:
            self.governor.touch(self)
            self.governor.note_fault()
            self.governor.update(self, self.host_bytes())

    def _op_handle(self):
        """Append handle for the op log, opened on first write and
        closed by snapshot/unload/close — read-only fragments hold no
        descriptor for it."""
        if REPLICA:
            raise RuntimeError(
                "write reached a read-only replica fragment — writes "
                "must route to the master (server/workers.py)")
        if self._op_file is None:
            self._op_file = open(self.path, "ab")
        return self._op_file

    # -------------------------------------------------- fail-stop contract

    def _check_writable(self):
        """Every mutation entry point calls this first: a fragment
        that fail-stopped once rejects ALL further writes (503 at the
        handler) until a close()+open() reloads the durable prefix —
        after an append error the on-disk tail's validity is unknown,
        and appending after it would corrupt the log for real."""
        if self._failed is not None:
            raise perr.ErrFragmentFailStop()

    def _fail_stop_locked(self, exc):
        """Latch the fragment read-only after a storage fault. Reads
        keep serving (the in-memory mirrors and the on-disk prefix are
        both intact); writes raise ErrFragmentFailStop until reopen.
        Caller holds ``self.mu``."""
        if self._failed is not None:
            return
        self._failed = exc
        self.stats.count("fragment_failstop_total", 1)
        ev = self.events
        if ev is not None:
            ev.emit("fragment.failstop", index=self.index,
                    frame=self.frame, slice=self.slice,
                    error=str(exc))
        # Epoch bump: plan-cache / memo entries over this index must
        # recompute — a latched fragment changes what the executor may
        # assume about residency and writability.
        _bump_epoch(self.index)
        _LOG.warning("fragment %s fail-stopped (writes rejected until "
                     "reopen): %s", self.path, exc)
        if self._op_file is not None:
            try:
                self._op_file.close()
            except OSError:
                pass
            self._op_file = None

    def _append_ops_locked(self, data, fsync=False):
        """Append encoded op records under the fail-stop contract.
        Callers must NOT have mutated in-memory state yet: an
        ENOSPC/EIO here (or the ``fragment.append.fsync`` failpoint)
        latches the fragment read-only and raises — memory stays on
        the acknowledged prefix, the write is never acknowledged, and
        any torn bytes the failed flush left are the reopen path's
        (already-tested) torn-tail problem."""
        op = self._op_handle()
        try:
            if faults.ACTIVE.enabled:
                faults.ACTIVE.fire("fragment.append.fsync")
            op.write(data)
            op.flush()
            if fsync:
                os.fsync(op.fileno())
        except OSError as e:
            self._fail_stop_locked(e)
            raise perr.ErrFragmentFailStop() from e

    def _ack_snapshot_locked(self):
        """Ack-bearing snapshot, shared by every bulk install path:
        the batch's durability IS this snapshot, so a failure
        fail-stops the fragment AND rolls memory back to the durable
        file — an errored import must never read back as acknowledged
        (ack-then-lose). Caller holds ``self.mu``."""
        try:
            self.snapshot()
        except OSError as e:
            self._fail_stop_locked(e)
            self._rollback_from_disk_locked()
            raise perr.ErrFragmentFailStop() from e

    def _commit_caches_locked(self, touched):
        """Post-install cache/epoch tail shared by the bulk install
        paths: refresh the TopN cache for every touched physical row,
        then bump the mutation epoch AFTER the bytes flushed (see
        _mutate — the published counter must never lead the file).
        Caller holds ``self.mu``."""
        for p in touched:
            self.cache.bulk_add(self._phys_rows[p],
                                int(self._row_counts[p]))
        self.cache.invalidate()
        _bump_epoch(self.index)

    def _maybe_snapshot_locked(self):
        """Post-append snapshot housekeeping: the write that got us
        here is already durable in the op log, so a failed rewrite
        (ENOSPC) must not fail the acknowledged write — the log just
        stays long and the next threshold crossing retries."""
        if self._op_log_room(0):
            return
        try:
            self.snapshot()
        except OSError as e:
            _LOG.warning("fragment %s deferred snapshot failed "
                         "(op log kept): %s", self.path, e)

    def _rollback_from_disk_locked(self):
        """Reload the durable file after a failed ack-bearing snapshot:
        the in-memory mirrors hold bits the disk never accepted, and
        serving them would turn an errored import into a phantom
        acknowledged one. Best-effort — if even the read-back fails,
        the (already fail-stopped) fragment keeps serving memory."""
        try:
            with open(self.path, "rb") as f:
                blocks, self.op_n, _ = codec.deserialize(f.read())
        except Exception:  # noqa: BLE001 — see the fault-in catch:
            return         # struct.error etc. are not ValueError
        self._reset_storage()
        self._load_blocks(blocks)
        self._snap_card = int(self._row_counts.sum())

    def _quarantine_locked(self, exc):
        """An unreadable fragment file must not take the node down
        (the lazy holder boot means it would otherwise surface as a
        failed query or a failed fault-in): move it aside as
        ``<path>.corrupt`` for the operator, start empty, keep
        serving — anti-entropy refills the bits from replicas. Returns
        the (empty) block map the caller loads."""
        _LOG.warning("fragment %s unreadable, quarantined to "
                     "%s.corrupt: %s", self.path, self.path, exc)
        self.stats.count("fragment_quarantined_total", 1)
        ev = self.events
        if ev is not None:
            ev.emit("fragment.quarantine", index=self.index,
                    frame=self.frame, slice=self.slice,
                    error=str(exc))
        # The fragment's servable content just changed (to empty):
        # every epoch-validated entry over this index — plans,
        # preludes, result memos, response replays — must drop.
        _bump_epoch(self.index)
        if self._op_file is not None:
            try:
                self._op_file.close()
            except OSError:
                pass
            self._op_file = None
        try:
            os.replace(self.path, self.path + ".corrupt")
        except OSError:
            pass
        try:
            with open(self.path, "wb") as f:
                f.write(codec.serialize({}))
        except OSError:
            pass
        return {}

    def host_bytes(self):
        """Host bytes this fragment holds (governor unit): the
        resident matrices, or — when evicted — the lazy-read memos."""
        return int(self._matrix.nbytes + self._row_counts.nbytes
                   + self.lazy_bytes())

    def _mem_changed(self):
        """Report a matrix reallocation to the governor."""
        if self.governor is not None and self._resident:
            self.governor.update(self, self.host_bytes())

    def memory_stats(self):
        """Where this fragment's bytes live, for the holder's
        ``/debug/memory`` rollup and the ``pilosa_memory_*`` gauges:
        packed uint64 block bytes resident on the host, device (HBM)
        mirror bytes (full matrix + per-row/plane/row-count memos),
        evicted-read memo bytes, roaring file bytes on disk, and the
        TopN row-cache entry count. Lock-free by design — gauges
        tolerate a racing mutation reading the pre-write snapshot, the
        same linearizability stance as win32()."""
        dev = 0
        d = self._dev
        if d is not None:
            dev += int(getattr(d, "nbytes", 0))
        for memo in (self._rc_dev, self._elig_dev):
            if memo is not None:
                dev += int(getattr(memo[1], "nbytes", 0))
        for memo in list(self._row_dev.values()):
            dev += int(getattr(memo[1], "nbytes", 0))
        for memo in list(self._planes_cache.values()):
            dev += int(getattr(memo[1], "nbytes", 0))
        for memo in list(self._cont_dev.values()):
            dev += memo[1].device_bytes()
        resident = self._resident
        host = (int(self._matrix.nbytes + self._row_counts.nbytes)
                if resident else 0)
        try:
            disk = os.path.getsize(self.path)
        except OSError:
            disk = 0
        try:
            cache_n = len(self._cache)
        except TypeError:
            cache_n = 0
        return {
            "resident": resident,
            "hostBytes": host,
            "deviceBytes": dev,
            "lazyBytes": int(self.lazy_bytes()),
            "diskBytes": int(disk),
            "cacheEntries": cache_n,
            "containers": self.container_stats(),
        }

    def unload(self, blocking=True):
        """Drop host matrices and device mirrors; the roaring file +
        op log remain the durable source (every mutation is already on
        disk), so the next touch faults everything back in. Called by
        the host-memory governor on LRU eviction — with blocking=False
        there (a busy fragment is skipped, not waited on: the evictor
        may itself hold another fragment's lock, and blocking both ways
        would be an ABBA deadlock). Returns True when resident state
        was actually dropped, False when there was nothing to drop,
        None when the lock was contended under blocking=False."""
        if not blocking and self.mu.owned():
            # Re-entrant acquire would "succeed" and gut state an outer
            # frame of THIS thread is using.
            return None
        if not self.mu.acquire_raw(blocking=blocking):
            return None
        try:
            if not self._resident:
                # Evicted, but possibly holding lazy-read memos — the
                # governor charges those too (compressed containers
                # included: they are version-keyed and cheap to rebuild
                # from the file), so one eviction frees everything.
                if (self._lazy is None and not self._lazy_rows
                        and self._lazy_cache_ids is None
                        and not self._lazy_planes_bytes()
                        and not any(isinstance(k, tuple)
                                    for k in self._cont_dev)):
                    return False
                self._drop_lazy_locked()
            else:
                self._drop_lazy_locked()
                if self._op_file is not None:
                    # Release the append fd with the matrices; the next
                    # write reopens it (10k evicted fragments must not
                    # pin 10k descriptors).
                    self._op_file.close()
                    self._op_file = None
                if self._cache_loaded:
                    self._flush_cache_locked()
                self._cap = 0
                self._w64 = _MIN_W64
                self._w64_base = 0
                self._matrix = np.zeros((0, _MIN_W64), dtype=np.uint64)
                self._row_counts = np.zeros(0, dtype=np.int64)
                self._row_index = {}
                self._phys_rows = []
                self._dev = None
                self._dev_version = -1
                self._dirty = set()
                self._planes_cache = {}
                self._row_dev = {}
                self._rc_dev = None
                self._elig_dev = None
                self._cont_dev = {}
                self._cont_fmt = {}
                self._resident = False
                # _version keeps counting across unload/reload so
                # executor stack-cache tokens never alias across the
                # gap.
                self._version += 1
                _bump_epoch(self.index)
        finally:
            self.mu.release_raw()
        if self.governor is not None:
            self.governor.update(self, 0)
        return True

    def replica_resync(self):
        """Replica-refresh invalidation (view.refresh_replica): drop
        every cached view of the file and advance the executor tokens.
        unload() alone is not enough — its non-resident branch drops
        lazy-read memos WITHOUT bumping ``_version``/epoch (governor
        evictions don't change file contents, so cached stacks stay
        valid there), but a replica resync means the MASTER's bytes
        moved underneath us and everything derived must go."""
        self.unload()
        with self.mu:
            self._version += 1
            _bump_epoch(self.index)

    # ------------------------------------------- evicted-read fast path

    def _drop_lazy_locked(self):
        """Invalidate the container-granular reader (file about to be
        rewritten/appended, the fragment is closing, or the governor
        is evicting this fragment's memos — compressed containers
        included; the reader-only MAX_LAZY_READERS eviction goes
        through ``_drop_reader`` instead)."""
        if self._lazy is not None:
            self._lazy.close()
            self._lazy = None
            _forget_reader(self)
        self._lazy_rows = {}
        self._lazy_bytes = 0
        self._lazy_cache_ids = None
        self._lazy_counts = {}
        if any(isinstance(k, tuple) and k and k[0] == "lazy"
               for k in self._planes_cache):
            self._planes_cache = {
                k: v for k, v in self._planes_cache.items()
                if not (isinstance(k, tuple) and k and k[0] == "lazy")}
        if any(isinstance(k, tuple) and k and k[0] == "lazy"
               for k in self._cont_dev):
            self._cont_dev = {
                k: v for k, v in self._cont_dev.items()
                if not (isinstance(k, tuple) and k and k[0] == "lazy")}
        if any(isinstance(k, tuple) and k and k[0] == "lazy"
               for k in self._cont_fmt):
            self._cont_fmt = {
                k: v for k, v in self._cont_fmt.items()
                if not (isinstance(k, tuple) and k and k[0] == "lazy")}

    def _drop_reader(self):
        """Release the mmap reader ONLY (MAX_LAZY_READERS eviction):
        containers, count memos, and block memos stay — the memo-first
        paths serve without the reader, and a miss recreates it.
        Returns False when the fragment lock was contended (reader
        still live; the caller re-queues it)."""
        if not self.mu.acquire_raw(blocking=False):
            return False
        try:
            if self._lazy is not None:
                self._lazy.close()
                self._lazy = None
        finally:
            self.mu.release_raw()
        return True

    def lazy_bytes(self):
        """Host bytes the evicted-read path holds — block memos, plane
        memos, count/cache-id memos, and a rough reader-header
        estimate — all charged to the governor so bounded residency
        stays bounded even for read-heavy workloads over evicted
        fragments."""
        reader = self._lazy
        overhead = 0
        if reader is not None:
            # Amortized snapshotting can leave multi-MB op tails; the
            # reader's parsed op index (per-key typ/bit arrays) is real
            # host memory and must count against the cap.
            overhead = len(reader.metas) * 64 + reader.op_index_bytes
        overhead += len(self._lazy_counts) * 64
        if self._lazy_cache_ids is not None:
            overhead += 32 + len(self._lazy_cache_ids) * 32
        overhead += self._lazy_planes_bytes()
        # Compressed containers built from lazy decodes: small
        # payloads, but governor-charged like every other lazy memo so
        # an evicted index's serving tier stays inside the budget.
        overhead += sum(v[1].nbytes()
                        for k, v in list(self._cont_dev.items())
                        if isinstance(k, tuple))
        return self._lazy_bytes + overhead

    def _lazy_planes_bytes(self):
        return sum(v[1].nbytes for k, v in self._planes_cache.items()
                   if isinstance(k, tuple) and k and k[0] == "lazy")

    def _lazy_serve(self, fn):
        """Serve one read from the container-granular reader when the
        fragment is open but evicted. Returns _NOT_LAZY when the
        fragment is resident (or unreadable lazily) — the caller then
        takes the normal resident path, which faults the matrix in.
        The whole serve runs under the raw lock (no fault-in), so a
        governor-evicted fragment answers row reads while holding only
        O(touched containers) host bytes — which are themselves
        governor-charged and evictable (unload drops them)."""
        if self._resident or not self._opened:
            return _NOT_LAZY  # cheap pre-check; verified under lock
        self.mu.acquire_raw()
        try:
            if self._resident or not self._opened:
                return _NOT_LAZY
            created = False
            if self._lazy is None:
                try:
                    self._lazy = codec.LazyReader(self.path)
                except (OSError, ValueError):
                    return _NOT_LAZY
                created = True
                # The reader parses the op log anyway; surface the
                # count so open()+read without a full fault-in still
                # reports op_n (snapshot-cadence monitors read it).
                self.op_n = self._lazy.op_n
            # LRU-bound the process-wide reader population (each mmap
            # pins a dup'd fd — see MAX_LAZY_READERS above).
            _note_reader(self)
            before = self.lazy_bytes()
            out = fn(self._lazy)
            changed = created or self.lazy_bytes() != before
            charge = self.host_bytes() if changed else None
        finally:
            self.mu.release_raw()
        if self.governor is not None:
            self.governor.touch(self)
            if charge is not None:
                # Only on actual growth/shrink: update() probes the
                # budget under a global lock — memo hits must not pay
                # that per row read.
                self.governor.update(self, charge)
        return out

    def _lazy_row_blocks(self, reader, row_id):
        """{sub: uint64[1024]} populated containers for one row,
        decoded from O(row) containers and memoized (8 KB per block —
        proportional to the data actually touched, never full row
        width)."""
        memo = self._lazy_rows.get(row_id)
        qs = querystats.active()
        if memo is not None:
            if qs is not None:
                qs.add("cacheHits", 1)
            return memo
        if qs is not None:
            qs.add("cacheMisses", 1)
        blocks = {}
        base_key = row_id * _CONTAINERS_PER_ROW
        for sub in range(_CONTAINERS_PER_ROW):
            block = reader.container(base_key + sub)
            if block is not None:
                blocks[sub] = block
        if len(self._lazy_rows) >= 16:
            # Evict the OLDEST single memo (dict preserves insertion
            # order) — clearing everything would re-decode the whole
            # working set each pass for 17+-row cycles.
            old = self._lazy_rows.pop(next(iter(self._lazy_rows)))
            self._lazy_bytes -= sum(b.nbytes for b in old.values())
        self._lazy_rows[row_id] = blocks
        self._lazy_bytes += sum(b.nbytes for b in blocks.values())
        return blocks

    @staticmethod
    def _blit_block(dst, block, sub, b64, w64):
        """Copy container ``sub``'s overlap with the word span
        [b64, b64+w64) into ``dst`` (uint64[w64]) — the ONE copy of
        the container→span window math, shared by the lazy row and
        lazy plane assemblies."""
        cbase = sub * _WORDS64_PER_CONTAINER
        lo = max(cbase, b64)
        hi = min(cbase + _WORDS64_PER_CONTAINER, b64 + w64)
        if lo < hi:
            dst[lo - b64 : hi - b64] = block[lo - cbase : hi - cbase]

    def _lazy_row64_span(self, reader, row_id, b64, w64):
        """uint64[w64] host row span [b64, b64+w64) assembled from the
        row's populated container blocks."""
        row = np.zeros(w64, dtype=np.uint64)
        for sub, block in self._lazy_row_blocks(reader, row_id).items():
            self._blit_block(row, block, sub, b64, w64)
        return row

    def cache_entry_ids(self):
        """TopN candidate row ids (cache membership) WITHOUT forcing
        residency: the loaded cache when resident (snapshotted under
        the fragment lock — concurrent imports mutate the dict), else
        the memoized sidecar ids through the lazy path. Batched TopN
        phase 1 reads this for every fragment of a slice list; going
        through the ``cache`` property would fault each one in."""
        from pilosa_tpu.storage.cache import NopCache

        if isinstance(self._cache, NopCache):
            return frozenset()
        if not self._resident and self._opened:
            # Unlike _lazy_serve this never constructs the container
            # reader — the candidate ids come from the JSON sidecar
            # (or the already-loaded cache), so an all-empty phase 1
            # over a cold slice list costs no header parses.
            self.mu.acquire_raw()
            try:
                if not self._resident and self._opened:
                    fresh = (self._lazy_cache_ids is None
                             and not self._cache_loaded)
                    out = frozenset(self._lazy_cache_ids_locked())
                else:
                    fresh, out = False, None
            finally:
                self.mu.release_raw()
            if out is not None:
                if self.governor is not None:
                    # Touch on EVERY read (LRU recency — a hot TopN
                    # candidate list must not age to the tail and get
                    # its sidecar memo evicted each cycle); charge
                    # only on first load.
                    self.governor.touch(self)
                    if fresh:
                        self.governor.update(self, self.host_bytes())
                return out
        with self.mu:
            return frozenset(self.cache.entries)

    def cache_entry_count(self):
        """How many ids ``cache_entry_ids`` holds, without building
        the set where the fragment is resident."""
        if self._resident or not self._opened:
            with self.mu:
                return len(self.cache)
        return len(self.cache_entry_ids())

    def _lazy_cache_ids_locked(self):
        if self._cache_loaded:
            return list(self._cache.entries)
        ids = self._lazy_cache_ids
        if ids is None:
            try:
                with open(self.cache_path) as f:
                    ids = json.load(f)
            except (OSError, ValueError):
                ids = []
            self._lazy_cache_ids = ids
        return ids

    def _lazy_top(self, reader, opt):
        """Src-less TopN on an evicted fragment: candidate ids from
        the loaded cache or its sidecar, exact counts from header
        cardinalities (+ op-touched container decodes) — same
        semantics as the resident walk in top(), zero fault-in."""
        from pilosa_tpu.storage.cache import NopCache

        if opt.row_ids is not None:
            allowed = set(opt.row_ids)
        else:
            if isinstance(self._cache, NopCache):
                return []
            allowed = set(self._lazy_cache_ids_locked())
        if opt.filter_row_ids is not None:
            allowed &= set(opt.filter_row_ids)
        pairs = []
        for rid in allowed:
            cnt = self._lazy_row_count(reader, rid)
            if cnt <= 0 or cnt < opt.min_threshold:
                continue
            pairs.append((int(rid), int(cnt)))
        pairs.sort(key=lambda rc: (-rc[1], rc[0]))
        if opt.n and opt.row_ids is None:
            pairs = pairs[: opt.n]
        return pairs

    def _lazy_planes(self, reader, depth, base32, width32, device=None):
        """Windowed BSI plane matrix from lazy row decodes, memoized
        in _planes_cache exactly like the resident build (the version
        is stable while the reader lives — file immutable)."""
        key = ("lazy", depth, base32, width32)
        cached = self._planes_cache.get(key + (device,))
        if cached and cached[0] == self._version:
            return cached[1]
        planes = _memo_moved(self._planes_cache, key, self._version,
                             device, depth + 1)
        if planes is not None:
            return self._planes_put(key, self._version, device, planes)
        b64, w64 = base32 // 2, width32 // 2
        mat = np.zeros((depth + 1, w64), dtype=np.uint64)
        # Decode containers directly — routing 20+ plane rows through
        # the 16-entry shared row memo would cycle it every build and
        # flush the memos concurrent Count/TopN lazy reads rely on.
        for i in range(depth + 1):
            base_key = i * _CONTAINERS_PER_ROW
            for sub in range(_CONTAINERS_PER_ROW):
                block = reader.container(base_key + sub)
                if block is not None:
                    self._blit_block(mat[i], block, sub, b64, w64)
        return self._planes_put(key, self._version, device,
                                jax.device_put(mat.view(np.uint32), device))

    def _planes_put(self, key, version, device, planes):
        """``planes`` memoized under ``key`` on ``device``, beside the
        same planes on other devices and in place of any other key's."""
        self._planes_cache = {
            k: v for k, v in self._planes_cache.items()
            if k[:-1] == key and v[0] == version}
        self._planes_cache[key + (device,)] = (version, planes)
        return planes

    def _lazy_win32(self, reader):
        """Column window from container SPANS, not just keys: the
        header alone bounds each key to its whole 1,024-word container,
        which for clustered data over-covers by up to 16x — at
        10k-slice scale that inflated every device stack and the fused
        kernels' compute by the same factor (measured 53 ms vs 3 ms per
        10B-col Count on the CPU backend). word_span peeks 4 bytes for
        sorted array/run payloads and scans bitmap containers' own 8 KB
        once, so the bound is word-exact for the outermost containers;
        interior containers never affect the window."""
        keys = reader.keys()
        if not keys:
            return None
        by_sub = {}
        for k in keys:
            by_sub.setdefault(k % _CONTAINERS_PER_ROW, []).append(k)

        def edge(reverse, pick, side):
            # First sub (in the given direction) with any non-empty
            # span holds that edge of the global window.
            for sub in sorted(by_sub, reverse=reverse):
                spans = [s for s in (reader.word_span(k)
                                     for k in by_sub[sub])
                         if s is not None]
                if spans:
                    return sub * _WORDS64_PER_CONTAINER + pick(
                        s[side] for s in spans)
            return None

        lo = edge(False, min, 0)
        if lo is None:
            return None
        hi = edge(True, max, 1)
        w = _MIN_W64
        while True:
            b = lo // w * w
            if hi < b + w or w >= WORDS64:
                break
            w *= 2
        if w >= WORDS64:
            return 0, WORDS_PER_SLICE
        return b * 2, w * 2

    def close(self):
        self.mu.acquire_raw()
        try:
            _bump_epoch(self.index)  # this object stops being servable
            # Advance the executor stack-cache token too (same
            # discipline as unload/_reset_storage): after a
            # close()+open() recovery cycle the next read must fault
            # in from disk — the durable prefix may differ from the
            # device mirrors a pre-close stack cached (fail-stop
            # rollback, external repair, quarantine).
            self._version += 1
            self._drop_lazy_locked()
            if self._cache_loaded:
                self._flush_cache_locked()
            if self._op_file:
                self._op_file.close()
                self._op_file = None
            if self._lock_file:
                self._lock_file.close()
                self._lock_file = None
            self._opened = False
            self._resident = False
            self._matrix = np.zeros((0, _MIN_W64), dtype=np.uint64)
            self._row_counts = np.zeros(0, dtype=np.int64)
            self._row_index = {}
            self._phys_rows = []
            self._cap = 0
            self._w64 = _MIN_W64
            self._w64_base = 0
            self._dev = None
            self._planes_cache = {}
            self._row_dev = {}
            self._rc_dev = None
            self._elig_dev = None
            self._cont_dev = {}
            self._cont_fmt = {}
        finally:
            self.mu.release_raw()
        if self.governor is not None:
            self.governor.update(self, 0)

    def _load_blocks(self, blocks):
        rows = sorted({key // _CONTAINERS_PER_ROW for key in blocks})
        # One pass for the global word span (so the window is sized and
        # placed once, not re-grown per block), one pass to fill.
        spans = {}
        lo_w = hi_w = None
        for key, block in blocks.items():
            if block[0] and block[-1]:   # a dense container: no scan
                spans[key] = (0, len(block) - 1)
            else:
                nz = np.flatnonzero(block)
                if len(nz) == 0:
                    continue
                spans[key] = (int(nz[0]), int(nz[-1]))
            cbase = (key % _CONTAINERS_PER_ROW) * _WORDS64_PER_CONTAINER
            glo, ghi = cbase + spans[key][0], cbase + spans[key][1]
            lo_w = glo if lo_w is None else min(lo_w, glo)
            hi_w = ghi if hi_w is None else max(hi_w, ghi)
        if lo_w is not None:
            self._ensure_window(lo_w, hi_w)
        self._grow_rows_locked(len(self._phys_rows) + sum(
            r not in self._row_index for r in rows))
        base = self._w64_base
        for row_id in rows:
            phys = self._ensure_row(row_id)
            for sub in range(_CONTAINERS_PER_ROW):
                key = row_id * _CONTAINERS_PER_ROW + sub
                if key in spans:
                    lo, hi = spans[key]
                    dst = sub * _WORDS64_PER_CONTAINER + lo - base
                    self._matrix[phys, dst : dst + hi - lo + 1] = (
                        blocks[key][lo : hi + 1])
        if len(self._phys_rows):
            self._recount_rows(range(len(self._phys_rows)))
        self._version += 1
        _bump_epoch(self.index)
        self._dirty.update(range(len(self._phys_rows)))

    def _to_arrays(self):
        """(sorted uint64[n] container keys, uint64[n, 1024] blocks) —
        one vectorized nonzero-container scan + one gather."""
        n = len(self._phys_rows)
        if n == 0:
            return (np.zeros(0, dtype=np.uint64),
                    np.zeros((0, _WORDS64_PER_CONTAINER), dtype=np.uint64))
        w = self._w64
        base = self._w64_base
        if w >= _WORDS64_PER_CONTAINER:
            # base is a multiple of w ≥ 1024, hence container-aligned.
            c0 = base // _WORDS64_PER_CONTAINER
            tiled = self._matrix[:n].reshape(
                n, w // _WORDS64_PER_CONTAINER, _WORDS64_PER_CONTAINER)
        else:
            # A sub-container window lies inside ONE container (base is
            # w-aligned and w divides 1024): pad only the PRESENT rows'
            # blocks, not the whole matrix.
            tiled = None
        if tiled is not None:
            present = tiled.any(axis=2)
            phys_idx, sub_idx = np.nonzero(present)
            row_ids = np.asarray(self._phys_rows, dtype=np.uint64)
            keys = (row_ids[phys_idx] * _CONTAINERS_PER_ROW
                    + (sub_idx + c0).astype(np.uint64))
            order = np.argsort(keys, kind="stable")  # phys != key order
            return keys[order], tiled[phys_idx[order], sub_idx[order]]
        present = self._matrix[:n].any(axis=1)
        phys_idx = np.flatnonzero(present)
        row_ids = np.asarray(self._phys_rows, dtype=np.uint64)
        c0 = base // _WORDS64_PER_CONTAINER
        off = base - c0 * _WORDS64_PER_CONTAINER
        keys = (row_ids[phys_idx] * _CONTAINERS_PER_ROW
                + np.uint64(c0))
        order = np.argsort(keys, kind="stable")
        if off == 0:
            # Container-aligned narrow window: hand the serializer the
            # NARROW rows directly (words beyond the width implicitly
            # zero) — zero-padding every container to 1024 words made
            # the snapshot scan up to 16× the data's actual bytes, the
            # dominant bulk-load cost on row-heavy narrow fragments.
            return keys[order], np.ascontiguousarray(
                self._matrix[:n][phys_idx[order]])
        blocks = np.zeros((len(phys_idx), _WORDS64_PER_CONTAINER),
                          dtype=np.uint64)
        blocks[:, off : off + w] = self._matrix[:n][phys_idx[order]]
        return keys[order], blocks

    def _acquire_lock(self):
        """Guard against two processes opening the same fragment
        (ref: syscall.Flock fragment.go:203-205). The lock lives on a
        sidecar ``.lock`` file whose fd stays open for the fragment's
        whole lifetime, so snapshot()/read_from() can freely close and
        reopen the data file without a release→reacquire window.

        Fragments under a HOLDER-level lock hold no per-file fd: one
        flock fd per fragment exhausted RLIMIT_NOFILE (20k here) at
        10B-column scale — ~9.5k lock fds per holder for a guard one
        directory-level flock provides (holder.py registers the root).
        Mixed-era safety, both directions, via TRANSIENT probes (no
        held fd): under a locked root we still probe our own ``.lock``
        so a standalone tool/old binary holding it is refused; outside
        any locked root we probe an enclosing ``.holder.lock`` so a
        running holder process refuses us."""
        me = os.path.abspath(self.path)
        if any(me.startswith(root) for root in _LOCKED_ROOTS):
            # Our process's holder owns the tree; refuse if some OTHER
            # process still holds this fragment's per-file lock. Probe
            # only when a .lock file exists (probing would otherwise
            # recreate the files this path exists to avoid).
            if os.path.exists(self.path + ".lock"):
                try_flock(self.path + ".lock", perr.ErrFragmentLocked,
                          transient=True)
            return
        # Standalone open: if an enclosing holder (this or another
        # process... but ours would be in _LOCKED_ROOTS) holds the
        # directory lock, the probe fails — refuse rather than write
        # under a live holder. Fragment paths sit ≤ 5 levels below
        # the holder root (<root>/<index>/<frame>/views/<view>/
        # fragments/<slice>).
        d = os.path.dirname(me)
        for _ in range(6):
            marker = os.path.join(d, HOLDER_LOCK_NAME)
            if os.path.exists(marker):
                try_flock(marker, perr.ErrFragmentLocked, transient=True)
                break
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent
        self._lock_file = try_flock(self.path + ".lock",
                                    perr.ErrFragmentLocked)

    def snapshot(self):
        """Atomic full rewrite + op-log reset (ref: fragment.go:1393-1438;
        duration histogram per track() :1387-1392).

        Failure contract: the temp-file + rename design makes a failed
        snapshot ATOMIC — the previous on-disk file (snapshot + op
        tail) is untouched and remains the durable source. On
        ENOSPC/EIO (or the ``fragment.snapshot.rename`` failpoint) the
        debris is removed, ``pilosa_snapshot_failed_total`` counts it,
        and the OSError propagates: housekeeping callers swallow it
        (the triggering write is already in the op log), while import
        paths whose durability DEPENDS on this snapshot fail-stop."""
        if REPLICA or self._failed is not None:
            return
        with stats_mod.Timer(self.stats, "SnapshotDurationSeconds"), \
                self.mu:
            self._drop_lazy_locked()  # file is about to be rewritten
            data = codec.serialize_arrays(*self._to_arrays())
            tmp = self.path + ".snapshotting"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                if faults.ACTIVE.enabled:
                    faults.ACTIVE.fire("fragment.snapshot.rename")
                if self._op_file:
                    self._op_file.close()
                    self._op_file = None
                os.replace(tmp, self.path)
            except OSError:
                self.stats.count("snapshot_failed_total", 1)
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self.op_n = 0
            self._snap_card = int(self._row_counts.sum())

    def _op_log_room(self, extra):
        """True while appending ``extra`` more ops beats snapshotting
        (see OPLOG_MAX_OPS above). Callers hold ``self.mu``;
        ``_snap_card`` is set by snapshot()/read_from() and back-filled
        at fault-in (every mutation faults in first)."""
        if self._snap_card is None:
            # Fault-in back-fills this before any mutation can reach a
            # gate; a still-unset value here means an exotic path, so
            # be conservative (reference cadence) rather than derive a
            # threshold from a post-mutation cardinality.
            self._snap_card = 0
        limit = max(MAX_OPN, min(self._snap_card // 2, OPLOG_MAX_OPS))
        return self.op_n + extra <= limit

    def _open_cache(self):
        """Restore the TopN cache sidecar (ref: fragment.go:250-289);
        counts are recomputed from storage, the sidecar only carries ids."""
        if not os.path.exists(self.cache_path):
            return
        try:
            with open(self.cache_path) as f:
                ids = json.load(f)
        except (ValueError, OSError):
            return
        for row_id in ids:
            phys = self._row_index.get(row_id)
            if phys is not None:
                self.cache.bulk_add(row_id, int(self._row_counts[phys]))
        self.cache.invalidate()

    def flush_cache(self):
        # Raw lock: flushing the sidecar of an evicted/never-touched
        # fragment must not fault its whole matrix back in (the
        # periodic holder cache-flush monitor walks EVERY fragment —
        # reloading each would defeat the host-bytes budget).
        self.mu.acquire_raw()
        try:
            if self._cache_loaded:
                self._flush_cache_locked()
        finally:
            self.mu.release_raw()

    def _flush_cache_locked(self):
        if REPLICA:
            return
        with open(self.cache_path, "w") as f:
            json.dump(self._cache.ids(), f)

    def recalculate_cache(self):
        """Rebuild the TopN cache from storage counts — recovers ranked
        TopN after a crash lost the cache sidecar (ref: Cache.
        Recalculate via handleRecalculateCaches handler.go:2016)."""
        with self.mu:
            for phys, row_id in enumerate(self._phys_rows):
                n = int(self._row_counts[phys])
                if n:
                    self.cache.bulk_add(row_id, n)
            self.cache.invalidate()
            # The ranked cache's membership may have changed: memos of
            # it (TopN phase 1's, on the plan cache's FragList) go.
            _bump_epoch(self.index)

    # ------------------------------------------------------- row plumbing

    def _ensure_row(self, row_id):
        phys = self._row_index.get(row_id)
        if phys is not None:
            return phys
        n = len(self._phys_rows)
        if n >= self._cap:
            self._grow_rows_locked(n + 1)
        self._row_index[row_id] = n
        self._phys_rows.append(row_id)
        self.max_row_id = max(self.max_row_id, row_id)
        return n

    def _grow_rows_locked(self, need):
        """Grow row capacity (powers of two) to hold ``need`` physical
        rows — THE one copy of the matrix/counts reallocation (bulk
        installs pre-grow once instead of doubling per row). Caller
        holds ``self.mu``."""
        if need <= self._cap:
            return
        new_cap = max(8, self._cap or 8)
        while new_cap < need:
            new_cap *= 2
        grown = np.zeros((new_cap, self._w64), dtype=np.uint64)
        grown[: self._cap] = self._matrix
        self._matrix = grown
        counts = np.zeros(new_cap, dtype=np.int64)
        counts[: self._cap] = self._row_counts
        self._row_counts = counts
        self._cap = new_cap
        self._dev = None  # shape changed; full re-upload
        self._mem_changed()

    def _ensure_window(self, lo_word, hi_word):
        """Grow (or, while still empty, relocate) the column window to
        cover global 64-bit word indices [lo_word, hi_word]. Width is a
        power of two and the base stays width-aligned, so an all-zero
        fragment whose first data lands in high containers allocates
        only its cluster's width — never the full slice."""
        base, w = self._w64_base, self._w64
        if base <= lo_word and hi_word < base + w:
            return
        if self._cap and self._matrix.any():
            # Existing data pins the current window inside the new one.
            lo_word = min(lo_word, base)
            hi_word = max(hi_word, base + w - 1)
            w2 = w
        else:
            w2 = _MIN_W64
        while True:
            b2 = lo_word // w2 * w2
            if hi_word < b2 + w2 or w2 >= WORDS64:
                break
            w2 *= 2
        if w2 >= WORDS64:
            w2, b2 = WORDS64, 0
        grown = np.zeros((self._cap, w2), dtype=np.uint64)
        if self._cap and self._matrix.any():
            off = base - b2
            grown[:, off : off + w] = self._matrix
        self._matrix = grown
        self._w64 = w2
        self._w64_base = b2
        self._dev = None          # device mirror shape changed
        self._row_dev.clear()
        self._planes_cache = {}
        self._mem_changed()

    def _recount_rows(self, phys_iter):
        idx = list(phys_iter)
        if not idx:
            return
        counts = native.popcount_rows(self._matrix, idx)
        if counts is None:
            counts = np.bitwise_count(self._matrix[idx]).sum(
                axis=-1, dtype=np.int64)
        self._row_counts[idx] = counts

    def rows(self):
        """Row ids present in storage. Served from container keys on
        an EVICTED fragment (no fault-in); a resident-allocated row
        whose bits were all cleared before the last snapshot is
        omitted there — observably equivalent, since zero-bit rows
        contribute nothing to any consumer (export, TopN walks,
        iteration)."""
        lazy = self._lazy_serve(self._lazy_row_ids)
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            return sorted(self._row_index)

    def _lazy_row_count(self, reader, row_id):
        """Exact count for one row on an evicted fragment, memoized —
        TopN cache walks re-read the same rows every query, and 16
        header lookups per row per call is Python-loop-bound at
        1,000-slice scale."""
        cnt = self._lazy_counts.get(row_id)
        if cnt is None:
            cnt = sum(
                reader.cardinality(row_id * _CONTAINERS_PER_ROW + sub)
                for sub in range(_CONTAINERS_PER_ROW))
            # FIFO-evict one (never clear-all: a wipe would recompute
            # ~the whole working set every query for big caches). The
            # bound covers the reference's 50k default cache size.
            while len(self._lazy_counts) >= 65536:
                self._lazy_counts.pop(next(iter(self._lazy_counts)))
            self._lazy_counts[row_id] = cnt
        return cnt

    def row_count(self, row_id):
        lazy = self._lazy_serve(
            lambda r: self._lazy_row_count(r, row_id))
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            phys = self._row_index.get(row_id)
            return int(self._row_counts[phys]) if phys is not None else 0

    def row_words(self, row_id):
        """Host uint64[WORDS64] for one row (zero if absent, padded to
        full slice width). The analog of Fragment.row's OffsetRange
        extraction (fragment.go:355-384)."""
        querystats.add("blocks", 1)
        hm = heatmap_mod.ACTIVE
        if hm.enabled:
            hm.touch_read(self.index, self.frame, row_id, self.slice,
                          weight=WORDS64 * 8)
        lazy = self._lazy_serve(
            lambda r: self._lazy_row64_span(r, row_id, 0, WORDS64))
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            phys = self._row_index.get(row_id)
            if phys is None:
                return np.zeros(WORDS64, dtype=np.uint64)
            if self._w64 == WORDS64:
                return self._matrix[phys]
            out = np.zeros(WORDS64, dtype=np.uint64)
            base = self._w64_base
            out[base : base + self._w64] = self._matrix[phys]
            return out

    # ------------------------------------------- compressed serving tier

    def row_container(self, row_id):
        """``containers.Container`` for one row at FULL slice width —
        the compressed serving tier. The per-row format is chosen from
        the density stats the fragment already keeps (``_row_counts``
        plus one vectorized run scan), the roaring thresholds verbatim
        (containers.choose_format): ≤4096 set bits → sorted-position
        ARRAY, few long runs → RUN, else the existing DENSE device
        mirror wrapped with its (host-known) cardinality. ARRAY/RUN
        containers memoize per (phys, version); a mutation bumps
        ``_version`` and the next read rebuilds — when the rebuild
        lands in a different format, that's a conversion
        (``pilosa_container_conversions_total``).

        EVICTED fragments classify from the lazy row decode: compressed
        results memoize (tiny payloads — the 100B-scale case is exactly
        an evicted-host, compressed-device index), dense rows re-wrap
        per call like the existing lazy device_row path."""
        from pilosa_tpu.ops import containers

        hm = heatmap_mod.ACTIVE
        if hm.enabled:
            hm.touch_read(self.index, self.frame, row_id, self.slice)

        if not self._resident and self._opened:
            # Memo-first, BEFORE _lazy_serve: a warm compressed tier
            # must serve without recreating the mmap reader (each
            # reader pins a dup'd fd — the resource that bounds
            # resident fragments at 100B scale). Lock-free racy read,
            # version-keyed like win32().
            memo = self._cont_dev.get(("lazy", row_id))
            if memo is not None and memo[0] == self._version:
                if self.governor is not None:
                    # Lock-free recency stamp: without it the HOTTEST
                    # compressed fragments would keep their stalest
                    # stamps (only _lazy_serve touches) and be evicted
                    # FIRST under budget pressure — LRU inversion
                    # thrashing the warm tier.
                    self.governor.touch(self)
                querystats.add("blocks", 1)
                querystats.add("containerBlocks"
                               + memo[1].fmt.capitalize(), 1)
                return memo[1]
            out = self._lazy_serve(
                lambda r: self._lazy_container(r, row_id, containers))
            if out is not _NOT_LAZY:
                querystats.add("blocks", 1)
                querystats.add("containerBlocks"
                               + out.fmt.capitalize(), 1)
                return out
        with self.mu:
            phys = self._row_index.get(row_id)
            if phys is None:
                querystats.add("blocks", 1)
                querystats.add("containerBlocksArray", 1)
                return containers.empty_container(WORDS_PER_SLICE)
            memo = self._cont_dev.get(phys)
            if memo is not None and memo[0] == self._version:
                querystats.add("blocks", 1)
                querystats.add("containerBlocks"
                               + memo[1].fmt.capitalize(), 1)
                return memo[1]
            fm = self._cont_fmt.get(phys)
            if fm is not None and fm == (self._version, bitops.FMT_DENSE):
                # Classified DENSE at this version already: skip the
                # run scan and wrap the existing device mirror — a
                # repeated serial-path read of a hot dense row must
                # stay a dict-hit + wrap, not a window re-scan
                # (device_row_win charges this read's "blocks").
                row_id = self._phys_rows[phys]
                cont = containers.dense_container(
                    self.device_row_win(row_id, 0, WORDS_PER_SLICE),
                    WORDS_PER_SLICE, int(self._row_counts[phys]))
                querystats.add("containerBlocksDense", 1)
                return cont
            cont = self._build_container_locked(phys, containers)
            if cont.fmt != bitops.FMT_DENSE:
                # The dense branch's device_row_win already charged
                # this read's "blocks" — formats on/off must report
                # identical block counts for the same query.
                querystats.add("blocks", 1)
            if fm is not None and fm[1] != cont.fmt:
                self._conversions += 1
                containers.note_conversion()
                self.stats.count("container_conversions_total", 1)
                if hm.enabled:
                    hm.note_conversion(self.index, self.frame)
            self._cont_fmt[phys] = (self._version, cont.fmt)
            if cont.fmt != bitops.FMT_DENSE:
                self._memo_container(phys, cont)
            querystats.add("containerBlocks" + cont.fmt.capitalize(), 1)
            return cont

    def _lazy_container(self, reader, row_id, containers):
        """Container for one row of an EVICTED fragment, classified
        from the lazy container decode — a sparse row costs one
        transient 128 KB host assembly and then lives as its compressed
        payload. Only compressed results memoize (a dense wrap would
        pin a 128 KB device row per entry; the dense lazy path already
        re-uploads per call, backed by the _lazy_rows decode memo)."""
        key = ("lazy", row_id)
        memo = self._cont_dev.get(key)
        if memo is not None and memo[0] == self._version:
            return memo[1]
        words = self._lazy_row64_span(reader, row_id, 0, WORDS64)
        fm = self._cont_fmt.get(key)
        if fm is not None and fm == (self._version, bitops.FMT_DENSE):
            # Classified DENSE at this version already: skip the
            # popcount + run scan and wrap the assembled words — a
            # repeated read of a hot dense evicted row then pays only
            # what the formats-off lazy path pays (assembly + upload),
            # with the count from the evicted-read memo when present.
            cnt = self._lazy_counts.get(row_id)
            if cnt is None:
                cnt = int(np.bitwise_count(
                    np.ascontiguousarray(words, np.uint64)).sum())
            import jax.numpy as jnp

            return containers.dense_container(
                jnp.asarray(np.ascontiguousarray(
                    words, np.uint64).view(np.uint32)),
                WORDS_PER_SLICE, cnt)
        cont = containers.build_container(words, WORDS_PER_SLICE)
        if fm is not None and fm[1] != cont.fmt:
            self._conversions += 1
            containers.note_conversion()
            self.stats.count("container_conversions_total", 1)
            hm = heatmap_mod.ACTIVE
            if hm.enabled:
                hm.note_conversion(self.index, self.frame)
        self._cont_fmt[key] = (self._version, cont.fmt)
        if cont.fmt != bitops.FMT_DENSE:
            self._memo_container(key, cont)
        return cont

    def _memo_container(self, key, cont):
        """Memoize a compressed container, oldest-evicting one entry
        past the cap (insertion order) — payloads are small, but the
        tier must not grow unbounded under row churn."""
        if len(self._cont_dev) >= 8192:
            self._cont_dev.pop(next(iter(self._cont_dev)))
        self._cont_dev[key] = (self._version, cont)

    def row_compressed(self, row_id):
        """Cheap probe: should this row be served from the compressed
        tier rather than staged into a dense device stack? True only
        for an EVICTED fragment whose row passes the density check
        (count ≤ ARRAY_MAX_BITS, or absent) — the 100B-scale shape,
        where the host matrix is cold and re-densifying rows into HBM
        stacks is exactly the memory cliff the container tier removes.
        Resident (hot) fragments keep the fused batched path: their
        dense mirrors are already paid for and budget-bounded. A
        dense-count row the run scan would still compress (all-full)
        reads as dense here — that only routes it to the batched dense
        path, never changes results."""
        from pilosa_tpu.ops import containers

        if not containers.enabled():
            return False
        if self._resident or not self._opened:
            return False
        # Memo-first: a warm compressed tier answers the probe from
        # the served container's own format without touching the
        # (possibly evicted) reader.
        memo = self._cont_dev.get(("lazy", row_id))
        if memo is not None and memo[0] == self._version:
            return memo[1].fmt != bitops.FMT_DENSE
        return self.row_count(row_id) <= containers.ARRAY_MAX_BITS

    def row_format_probe(self, row_id):
        """Read-only classification guess for one row — "dense",
        "array" or "run" — for the query inspector's per-leaf format
        mix and the cost model's cell selection. Answers from the
        serving memos when warm (exact), else from the density stats
        (count ≤ ARRAY_MAX_BITS → array; the run/array distinction
        needs a scan the probe refuses to pay). Never builds a
        container and never writes a serving memo — the explain-only
        contract. Lock-free racy reads, version-keyed like
        container_stats."""
        from pilosa_tpu.ops import containers

        if not containers.enabled():
            return bitops.FMT_DENSE
        version = self._version
        if not self._resident and self._opened:
            memo = self._cont_dev.get(("lazy", row_id))
            if memo is not None and memo[0] == version:
                return memo[1].fmt
            fm = self._cont_fmt.get(("lazy", row_id))
            if fm is not None and fm[0] == version:
                return fm[1]
            return (bitops.FMT_ARRAY
                    if self.row_count(row_id) <= containers.ARRAY_MAX_BITS
                    else bitops.FMT_DENSE)
        phys = self._row_index.get(row_id)
        if phys is None:
            return bitops.FMT_ARRAY  # absent rows serve empty arrays
        memo = self._cont_dev.get(phys)
        if memo is not None and memo[0] == version:
            return memo[1].fmt
        fm = self._cont_fmt.get(phys)
        if fm is not None and fm[0] == version:
            return fm[1]
        # Resident, unclassified: the batched/dense mirror serves it.
        return bitops.FMT_DENSE

    def _build_container_locked(self, phys, containers):
        """Classify + build one row's container from its window words
        via the ONE shared pipeline (containers.build_container):
        positions/runs rebase by the window offset to slice-global bit
        coordinates so the container is window-agnostic, and the dense
        outcome wraps the existing device mirror instead of
        re-uploading. Caller holds ``self.mu``."""
        row_id = self._phys_rows[phys]
        return containers.build_container(
            self._matrix[phys], WORDS_PER_SLICE,
            count=int(self._row_counts[phys]),
            offset=self._w64_base * 64,
            dense_fn=lambda: self.device_row_win(
                row_id, 0, WORDS_PER_SLICE))

    def container_stats(self):
        """Per-format snapshot of the compressed serving tier: block
        counts + resident payload bytes by format, the bytes the dense
        tier would hold for those same blocks (this fragment's window
        width — dense rows already page to their window), and the
        conversion count. Lock-free like memory_stats: gauges tolerate
        a racing mutation's pre-write snapshot."""
        out = {bitops.FMT_DENSE: {"blocks": 0, "bytes": 0},
               bitops.FMT_ARRAY: {"blocks": 0, "bytes": 0},
               bitops.FMT_RUN: {"blocks": 0, "bytes": 0}}
        dense_row_bytes = 2 * self._w64 * 4
        equiv = 0
        version = self._version
        for key, memo in list(self._cont_dev.items()):
            if memo[0] != version:
                continue
            c = memo[1]
            out[c.fmt]["blocks"] += 1
            out[c.fmt]["bytes"] += c.nbytes()
            # Resident rows' dense equivalent is this fragment's
            # window width (the dense tier pages rows to it); evicted
            # ("lazy"-keyed) rows would densify at full container
            # width, which is what the wrap charges.
            equiv += (c.dense_equiv_bytes() if isinstance(key, tuple)
                      else dense_row_bytes)
        for key, (ver, fmt) in list(self._cont_fmt.items()):
            if fmt == bitops.FMT_DENSE and ver == version:
                # Resident dense rows page to this fragment's window;
                # evicted ("lazy"-keyed) dense rows serve full-width
                # uploads per call.
                b = (WORDS_PER_SLICE * 4 if isinstance(key, tuple)
                     else dense_row_bytes)
                out[fmt]["blocks"] += 1
                out[fmt]["bytes"] += b
                equiv += b
        return {"formats": out, "denseEquivBytes": equiv,
                "conversions": self._conversions}

    # ------------------------------------------------------ device mirror

    def win32(self):
        """Current column window as (base, width) in uint32 device
        words, or None when the fragment holds no rows. Executors union
        these across a plan's fragments to size device stacks to the
        data instead of the full 32,768-word slice (the HBM analog of
        the reference's containers never materializing empty space,
        roaring.go:1011-1024).

        Version-keyed memo, read without the lock: batched executors
        call this once per fragment whenever they re-read a fragment
        list (executor._list_extent) — 954 locked window computations
        measured as ~half of a billion-column count's latency when
        every query paid them. A racing mutation serves the consistent
        pre-write snapshot (same linearizability as the stack caches'
        token race)."""
        memo = self._win32_memo
        if memo is not None and memo[0] == self._version:
            return memo[1]
        version = self._version
        lazy = self._lazy_serve(self._lazy_win32)
        if lazy is not _NOT_LAZY:
            self._win32_memo = (version, lazy)
            return lazy
        with self.mu:
            val = ((self._w64_base * 2, self._w64 * 2)
                   if self._row_index else None)
            self._win32_memo = (self._version, val)
            return val

    def device_matrix(self):
        """uint32[cap, 2·width] HBM copy, refreshed lazily — NARROW
        when the fragment is (width ≤ 32768 device words); callers must
        trim full-slice COLUMN operands to match, as top() does its
        host src. Its rows are the mirror's ``_cap``, a power of two,
        zero past ``len(_phys_rows)``: scan the array as it is and cut
        the counts on the host, as top() does (a device-side ``[:n]``
        is a program of its own that copies the matrix)."""
        with self.mu:
            if self._cap == 0:
                return jnp.zeros((0, WORDS_PER_SLICE), dtype=jnp.uint32)
            qs = querystats.active()
            obs = kerneltime_mod.ACTIVE
            if (self._dev is None or self._dev.shape[0] != self._cap
                    or self._dev.shape[1] != 2 * self._w64):
                t0 = time.perf_counter()
                with tracing.span("fragment.device_put", rows=self._cap,
                                  words32=2 * self._w64, slice=self.slice):
                    self._dev = jnp.asarray(self._matrix.view(np.uint32))
                self._dirty.clear()
                if qs is not None:
                    qs.add("deviceTransfers", 1)
                    qs.add("deviceTransferBytes",
                           int(self._matrix.nbytes))
                if obs.enabled:
                    obs.note_transfer(int(self._matrix.nbytes),
                                      time.perf_counter() - t0)
            elif self._dev_version != self._version and self._dirty:
                idx = sorted(self._dirty)
                t0 = time.perf_counter()
                with tracing.span("fragment.device_update",
                                  rows=len(idx), slice=self.slice):
                    vals = jnp.asarray(self._matrix[idx].view(np.uint32))
                    self._dev = self._dev.at[jnp.asarray(idx)].set(vals)
                self._dirty.clear()
                if qs is not None:
                    qs.add("deviceTransfers", 1)
                    qs.add("deviceTransferBytes",
                           len(idx) * 2 * self._w64 * 8)
                if obs.enabled:
                    obs.note_transfer(len(idx) * 2 * self._w64 * 8,
                                      time.perf_counter() - t0)
            self._dev_version = self._version
            return self._dev

    def _row_counts_device(self):
        """int32[cap] device copy of the per-row cardinalities (one a
        row of ``device_matrix()``, zero past the last physical row),
        memoized against the mutation version — the Tanimoto
        denominator reads it every query and would otherwise be
        uploaded per query. The version check subsumes every
        invalidation site (any mutation bumps ``_version``); callers
        hold ``self.mu``."""
        rc = self._rc_dev
        if (rc is None or rc[0] != self._version
                or rc[1].shape[0] != self._cap):
            arr = jnp.asarray(self._row_counts.astype(np.int32))
            self._rc_dev = rc = (self._version, arr)
        return rc[1]

    def _note_row_read(self, row_id, width32):
        """One row-block read of ``width32`` device words, for the
        query's ``blocks`` and the heatmap."""
        querystats.add("blocks", 1)
        hm = heatmap_mod.ACTIVE
        if hm.enabled:
            # Per-slice/per-row heat from the read layer: only work
            # that touches INDIVIDUAL slices reaches here (serial
            # loops, stack-cache misses, lane builds) — the uniform
            # batched warm path never does, by design. Stride-sampled
            # inside touch_read so the hottest read loops pay one
            # counter increment per call, not decay math.
            hm.touch_read(self.index, self.frame, row_id, self.slice,
                          weight=width32 * 4)

    def device_row(self, row_id):
        """uint32[32768] device bitmap for one row (full slice width —
        the window-agnostic API; batched executors use device_row_win
        to stay narrow)."""
        return self.device_row_win(row_id, 0, WORDS_PER_SLICE)

    def device_row_win(self, row_id, base32, width32, device=None):
        """uint32[width32] device bitmap for one row, rebased into the
        requested column window [base32, base32+width32) of uint32
        device words; bits outside the request read as zero. Serves
        from the HBM matrix mirror when the row is clean and the
        request matches the fragment's own window; otherwise builds
        (and memoizes per (row, window, version)) one rebased copy —
        never forcing the full-matrix dirty refresh, whose functional
        update copies the entire buffer (ruinous for single-row reads
        after small writes).

        ``device`` is where the caller's shard of a sharded stack
        lives: host words go there, the memo keeps a copy a device, and
        a row whose only clean copy lies on another device is copied
        over (``crossDeviceRows``). None: the default device,
        uncommitted, as with one device.

        On an EVICTED fragment this serves from the container-granular
        reader — O(row) containers decoded, no fault-in — so batched
        executor stacks over cold fragments never pull whole matrices
        into host memory."""
        self._note_row_read(row_id, width32)
        lazy = self._lazy_serve(
            lambda r: jax.device_put(
                self._lazy_row64_span(r, row_id, base32 // 2,
                                      width32 // 2).view(np.uint32),
                device))
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            phys = self._row_index.get(row_id)
            if phys is None:
                return _zeros(width32, device)
            fb, fw = self._w64_base * 2, self._w64 * 2
            clean = (self._dev is not None
                     and self._dev.shape[0] == self._cap
                     and self._dev.shape[1] == fw
                     and phys not in self._dirty)
            if clean and fb == base32 and fw == width32:
                return _on(self._dev[phys], device, 1)
            key = (phys, base32, width32)
            hit = self._row_dev.get(key + (device,))
            if hit is not None and hit[0] == self._version:
                return hit[1]
            row = _memo_moved(self._row_dev, key, self._version, device,
                              1)
            if row is None:
                row = self._rebased_row(phys, clean, base32, width32,
                                        device)
            if len(self._row_dev) >= 64:
                self._row_dev.clear()
            self._row_dev[key + (device,)] = (self._version, row)
            return row

    def _rebased_row(self, phys, clean, base32, width32, device):
        """Row ``phys`` rebased into the window, made on ``device`` from
        the mirror (``clean``) or the host words."""
        fb, fw = self._w64_base * 2, self._w64 * 2
        raw = (_on(self._dev[phys], device, 1) if clean
               else jax.device_put(self._matrix[phys].view(np.uint32),
                                   device))
        lo = max(fb, base32)
        hi = min(fb + fw, base32 + width32)
        if lo >= hi:
            return _zeros(width32, device)
        if fb == base32 and fw == width32:
            return raw
        return _zeros(width32, device).at[
            lo - base32 : hi - base32].set(raw[lo - fb : hi - fb])

    def device_rows_win(self, row_ids, n_rows, base32, width32,
                        device=None):
        """uint32[n_rows, width32]: ``device_row_win`` of each of
        ``row_ids`` in order, zero rows after them, on ``device``
        (None: the default device). Where the HBM mirror holds the
        fragment clean at the requested window this is ONE gather,
        whatever the number of rows (a row the fragment lacks reads as
        zero): TopN's exact re-query used to pay a device slice and a
        stack per candidate. Elsewhere (no mirror, a dirty row, another
        window, an evicted fragment) row by row, with
        ``device_row_win``'s own care not to force a refresh."""
        if self._resident:
            with self.mu:
                dev = self._dev
                if (dev is not None and not self._dirty
                        and dev.shape == (self._cap, self._w64 * 2)
                        and (self._w64_base * 2, self._w64 * 2)
                        == (base32, width32)):
                    querystats.add("blocks", len(row_ids))
                    # Past the last row: gathers as the fill value.
                    idx = np.full(n_rows, self._cap, dtype=np.int32)
                    idx[: len(row_ids)] = [
                        self._row_index.get(r, self._cap) for r in row_ids]
                    return _on(dev.at[jnp.asarray(idx)].get(
                        mode="fill", fill_value=0), device, len(row_ids))
        rows = [self.device_row_win(r, base32, width32, device)
                for r in row_ids]
        rows.extend([_zeros(width32, device)] * (n_rows - len(rows)))
        return jnp.stack(rows)

    # ---------------------------------------------------------- mutations

    def _pos(self, row_id, column_id):
        """pos = row·2^20 + col%2^20 (ref: fragment.go:800-809, Pos :1904)."""
        if column_id // SLICE_WIDTH != self.slice:
            raise ValueError(
                f"column:{column_id} out of bounds for slice {self.slice}")
        return row_id * SLICE_WIDTH + column_id % SLICE_WIDTH

    def _mutate(self, row_id, column_id, set_value):
        pos = self._pos(row_id, column_id)
        self._check_writable()
        if self._opened:
            # Secure the op-log fd BEFORE touching state: a lazy open
            # failing (EMFILE) after the matrix flipped would diverge
            # durable state from memory.
            self._op_handle()
        phys = self._ensure_row(row_id)
        col = column_id % SLICE_WIDTH
        word, mask = col >> 6, np.uint64(1 << (col & 63))
        if not (self._w64_base <= word < self._w64_base + self._w64):
            if not set_value:
                return False  # out-of-window bits are zero: no-op clear
            self._ensure_window(word, word)
        word -= self._w64_base
        cur = bool(self._matrix[phys, word] & mask)
        if cur == set_value:
            return False
        if self._opened:
            # Op record BEFORE the in-memory flip (fail-stop
            # contract): an append error must leave memory on the
            # acknowledged prefix, not holding a bit the log never
            # recorded.
            self._append_ops_locked(codec.op_record(
                codec.OP_ADD if set_value else codec.OP_REMOVE, pos))
            self.op_n += 1
        if set_value:
            self._matrix[phys, word] |= mask
            self._row_counts[phys] += 1
        else:
            self._matrix[phys, word] &= ~mask
            self._row_counts[phys] -= 1
        self._version += 1
        self._dirty.add(phys)
        if self._opened:
            self._maybe_snapshot_locked()
        # Epoch bump AFTER the bytes are flushed: the published counter
        # (replica workers, server/workers.py) must never lead the
        # file, or a refresh racing this write latches the new epoch
        # against the old bytes and the write stays invisible until
        # the next unrelated bump.
        _bump_epoch(self.index)
        self.cache.add(row_id, int(self._row_counts[phys]))
        return True

    def set_bit(self, row_id, column_id):
        """Returns True iff the bit changed (ref: fragment.go:388-434)."""
        with self.mu:
            changed = self._mutate(row_id, column_id, True)
        if changed:  # emission point (ref: fragment.go:427)
            self.stats.count("setBit", 1)
        return changed

    def clear_bit(self, row_id, column_id):
        with self.mu:
            changed = self._mutate(row_id, column_id, False)
        if changed:
            self.stats.count("clearBit", 1)
        return changed

    def bulk_set_bits(self, row_ids, column_ids):
        """Vectorized SetBit burst: per-bit changed flags (original
        order; within-batch duplicates change at most once) with
        set_bit's per-op semantics — op record per changed bit,
        snapshot when the op log exceeds MaxOpN, cache/count updates
        (ref: fragment.go:388-434 applied per bit)."""
        return self._bulk_bits(row_ids, column_ids, set_value=True)

    def bulk_clear_bits(self, row_ids, column_ids):
        """Vectorized ClearBit burst: AND-NOT apply + OP_REMOVE
        records; rows absent from storage are never allocated."""
        return self._bulk_bits(row_ids, column_ids, set_value=False)

    def _bulk_bits(self, row_ids, column_ids, set_value):
        with self.mu:
            self._check_writable()
            row_ids = np.asarray(row_ids, dtype=np.uint64)
            column_ids = np.asarray(column_ids, dtype=np.uint64)
            bad = column_ids // SLICE_WIDTH != self.slice
            if bad.any():
                raise ValueError(
                    f"column:{int(column_ids[bad][0])} out of bounds for "
                    f"slice {self.slice}")
            if self._opened:
                self._op_handle()  # secure the fd before any mutation
            cols = column_ids % SLICE_WIDTH
            changed = np.zeros(len(row_ids), dtype=bool)
            if set_value:
                sub = np.arange(len(row_ids))
                uniq_rows, inverse = np.unique(row_ids, return_inverse=True)
                phys = np.asarray(
                    [self._ensure_row(int(r)) for r in uniq_rows],
                    dtype=np.int64)[inverse]
            else:
                # Clears touch only rows that exist — never allocate.
                present = np.asarray(
                    [int(r) in self._row_index for r in row_ids.tolist()])
                if not present.any():
                    return changed
                sub = np.flatnonzero(present)
                phys = np.asarray([self._row_index[int(r)]
                                   for r in row_ids[sub].tolist()],
                                  dtype=np.int64)
            scols = cols[sub]
            words = (scols >> np.uint64(6)).astype(np.int64)
            if len(words):
                if set_value:
                    self._ensure_window(int(words.min()), int(words.max()))
                else:
                    # Out-of-window bits are zero: clears there are
                    # no-ops and must not grow the narrow matrix.
                    base = self._w64_base
                    keep = (words >= base) & (words < base + self._w64)
                    if not keep.all():
                        sub = sub[keep]
                        phys = phys[keep]
                        scols = scols[keep]
                        words = words[keep]
                        if not len(words):
                            return changed
                words = words - self._w64_base
            masks = np.uint64(1) << (scols & np.uint64(63))
            cur = (self._matrix[phys, words] & masks) != 0
            # Only the first occurrence of each (row, col) can change,
            # like the serial per-op loop applied in order.
            key = phys * np.int64(SLICE_WIDTH) + scols.astype(np.int64)
            order = np.argsort(key, kind="stable")
            k_sorted = key[order]
            first_sorted = np.concatenate(
                ([True], k_sorted[1:] != k_sorted[:-1]))
            first = np.zeros(len(key), dtype=bool)
            first[order] = first_sorted
            sub_changed = first & (~cur if set_value else cur)
            n_changed = int(sub_changed.sum())
            changed[sub] = sub_changed
            if n_changed == 0:
                return changed
            if self._opened:
                # Op records BEFORE the in-memory apply — the
                # _mutate fail-stop contract, batched.
                positions = (row_ids[sub][sub_changed]
                             * np.uint64(SLICE_WIDTH)
                             + scols[sub_changed]).astype(np.uint64)
                typs = np.full(
                    len(positions),
                    codec.OP_ADD if set_value else codec.OP_REMOVE,
                    dtype=np.uint8)
                self._append_ops_locked(codec.op_records(typs, positions))
                self.op_n += n_changed
            target = (phys[sub_changed], words[sub_changed])
            if set_value:
                np.bitwise_or.at(self._matrix, target, masks[sub_changed])
            else:
                np.bitwise_and.at(self._matrix, target, ~masks[sub_changed])
            per_row = np.bincount(
                phys[sub_changed],
                minlength=len(self._row_counts)).astype(
                    self._row_counts.dtype)
            if set_value:
                self._row_counts += per_row
            else:
                self._row_counts -= per_row
            touched = np.unique(phys[sub_changed])
            self._version += 1
            self._dirty.update(touched.tolist())
            if self._opened:
                self._maybe_snapshot_locked()
            _bump_epoch(self.index)  # after the flush — see _mutate
            for p in touched.tolist():
                self.cache.add(self._phys_rows[p],
                               int(self._row_counts[p]))
        self.stats.count("setBit" if set_value else "clearBit", n_changed)
        return changed

    def import_bits(self, row_ids, column_ids):
        """Bulk import: vectorized host write + one snapshot
        (ref: fragment.go:1266-1333)."""
        with self.mu:
            self._check_writable()
            row_ids = np.asarray(row_ids, dtype=np.uint64)
            column_ids = np.asarray(column_ids, dtype=np.uint64)
            if len(row_ids) != len(column_ids):
                raise ValueError("row/column id length mismatch")
            if len(row_ids) == 0:
                return
            if self._opened:
                self._op_handle()  # secure the fd before any mutation
            bad = column_ids // SLICE_WIDTH != self.slice
            if bad.any():
                raise ValueError(
                    f"column:{int(column_ids[bad][0])} out of bounds for "
                    f"slice {self.slice}")
            cols = column_ids % SLICE_WIDTH
            # Small batches append to the op log (one batch-encoded
            # write, replayed idempotently on open) instead of paying a
            # full-file snapshot; large batches snapshot once, as the
            # reference always does (fragment.go:1331).
            use_oplog = self._opened and self._op_log_room(len(row_ids))
            if use_oplog:
                positions = (row_ids * np.uint64(SLICE_WIDTH)
                             + cols).astype(np.uint64)
                typs = np.full(len(positions), codec.OP_ADD, dtype=np.uint8)
                # Log BEFORE the scatter (fail-stop contract), fsync'd:
                # bulk imports are acknowledged durable (the snapshot
                # path they replace fsync'd); single set_bit stays
                # flush-only, as the reference's op writer does.
                self._append_ops_locked(codec.op_records(typs, positions),
                                        fsync=True)
                self.op_n += len(positions)
            uniq_rows, inverse = np.unique(row_ids, return_inverse=True)
            phys_u = np.asarray(
                [self._ensure_row(int(r)) for r in uniq_rows],
                dtype=np.int64)
            phys = phys_u[inverse]
            self._ensure_window(int(cols.min()) >> 6, int(cols.max()) >> 6)
            # Window-local columns: subtracting the base keeps word AND
            # in-word bit math intact (the base is 64-word-aligned).
            lcols = cols - np.uint64(self._w64_base * 64)
            if not native.scatter_or(self._matrix, phys, lcols):
                words = (lcols >> np.uint64(6)).astype(np.int64)
                masks = np.uint64(1) << (lcols & np.uint64(63))
                # OR-fold duplicate (row, word) hits before touching the
                # matrix: one sort + reduceat beats an unbuffered ufunc.at.
                w = self._w64
                key = phys * np.int64(w) + words
                order, starts, _, folded = codec.group_sorted(key)
                ored = np.bitwise_or.reduceat(masks[order], starts)
                self._matrix[folded // w, folded % w] |= ored
            touched = sorted(phys_u.tolist())
            self._recount_rows(touched)
            self._version += 1
            self._dirty.update(touched)
            if not use_oplog:
                self._ack_snapshot_locked()
            self._commit_caches_locked(touched)

    def install_batch(self, row_ids, column_ids, containers_by_row=None,
                      counts_by_row=None, positions=None):
        """Batch-install path for the streaming ingest pipeline
        (ingest/pipeline.py). Same durability contract as import_bits
        — op records appended (fsync'd) BEFORE the in-memory apply,
        fail-stop + rollback on a failed ack-bearing snapshot, ONE
        epoch bump so every epoch-validated tier (plan cache, result
        memos, response replays) invalidates exactly once — but built
        for the pipeline's PRE-SORTED, DEDUPLICATED input:

        - no re-sort: (row, column) groups come off one boundary scan
          of the already-ordered batch, and the matrix scatter is a
          single reduceat OR-fold;
        - bulk op-log rule: a batch appends while the log stays under
          OPLOG_MAX_OPS (the documented replay/region bound) instead
          of the card/2 housekeeping cadence — one 13 B/op sequential
          append + fsync beats re-serializing the whole fragment per
          batch, which is exactly the O(total²) the legacy cadence
          cost bulk loads;
        - row cardinalities for rows the batch CREATED come from the
          device classify stats (``counts_by_row``) — no post-install
          recount scan; pre-existing rows recount as usual;
        - compressed-container landing: pre-classified ARRAY/RUN
          containers seed the serving memos for created rows, so the
          first read serves compressed with zero re-scan and zero
          conversion churn. Rows that already held bits are left for
          the read path (a batch-only container would miss their
          pre-existing bits).

        ``containers_by_row``: row_id -> (fmt, Container|None); None
        seeds the format memo only (the DENSE cell — such rows serve
        from the fragment's own device mirrors). Input NOT sorted by
        (row, column) or not deduplicated falls back to import_bits —
        correctness never depends on the caller's ordering claim."""
        row_ids = np.asarray(row_ids, dtype=np.uint64)
        column_ids = np.asarray(column_ids, dtype=np.uint64)
        if len(row_ids) != len(column_ids):
            raise ValueError("row/column id length mismatch")
        if len(row_ids) == 0:
            return
        with self.mu:
            self._check_writable()
            bad = column_ids // SLICE_WIDTH != self.slice
            if bad.any():
                raise ValueError(
                    f"column:{int(column_ids[bad][0])} out of bounds "
                    f"for slice {self.slice}")
            cols = column_ids % SLICE_WIDTH
            if positions is None:
                # The global bit positions double as the (row, column)
                # sort key; the pipeline passes its own copy through.
                positions = (row_ids * np.uint64(SLICE_WIDTH)
                             + cols).astype(np.uint64)
            if len(positions) > 1 and not (
                    positions[1:] > positions[:-1]).all():
                # Ordering claim violated: the general path re-sorts.
                self.import_bits(row_ids, column_ids)
                return self._seed_containers_locked(containers_by_row)
            if self._opened:
                self._op_handle()  # secure the fd before any mutation
            use_oplog = (self._opened
                         and self.op_n + len(positions) <= OPLOG_MAX_OPS)
            if use_oplog:
                typs = np.full(len(positions), codec.OP_ADD,
                               dtype=np.uint8)
                # Log BEFORE the scatter (fail-stop contract), fsync'd:
                # bulk installs are acknowledged durable.
                self._append_ops_locked(codec.op_records(typs, positions),
                                        fsync=True)
                self.op_n += len(positions)
            # Per-row groups off the sorted batch: one boundary scan.
            row_bounds = np.flatnonzero(
                np.concatenate(([True], row_ids[1:] != row_ids[:-1])))
            uniq_rows = row_ids[row_bounds]
            # Pre-grow row capacity ONCE for every new row in the
            # batch — per-row doubling would reallocate (and zero +
            # copy) the matrix log2(new/old) times per bulk batch.
            n_new = sum(1 for r in uniq_rows.tolist()
                        if r not in self._row_index)
            self._grow_rows_locked(len(self._phys_rows) + n_new)
            fresh = []
            phys_u = np.empty(len(uniq_rows), dtype=np.int64)
            for i, r in enumerate(uniq_rows.tolist()):
                phys = self._row_index.get(r)
                if phys is None or self._row_counts[phys] == 0:
                    fresh.append(i)
                phys_u[i] = self._ensure_row(int(r))
            self._ensure_window(int(cols.min()) >> 6,
                                int(cols.max()) >> 6)
            lcols = cols - np.uint64(self._w64_base * 64)
            counts_per_row = np.diff(np.append(row_bounds,
                                               len(row_ids)))
            phys = np.repeat(phys_u, counts_per_row)
            words = (lcols >> np.uint64(6)).astype(np.int64)
            masks = np.uint64(1) << (lcols & np.uint64(63))
            # One reduceat OR-fold over (row, word) groups — the batch
            # is sorted, so groups are contiguous and each (row, word)
            # target is unique: plain fancy |= needs no unbuffered
            # ufunc.at.
            key = phys * np.int64(self._w64) + words
            starts = np.flatnonzero(
                np.concatenate(([True], key[1:] != key[:-1])))
            ored = np.bitwise_or.reduceat(masks, starts)
            folded = key[starts]
            self._matrix[folded // self._w64,
                         folded % self._w64] |= ored
            # Cardinalities: created rows take the batch counts (the
            # device classify stats — their final truth); pre-existing
            # rows recount.
            fresh_set = set(fresh)
            recount = [int(phys_u[i]) for i in range(len(uniq_rows))
                       if i not in fresh_set]
            for i in fresh_set:
                r = int(uniq_rows[i])
                cnt = (counts_by_row or {}).get(r)
                if cnt is None:
                    cnt = int(counts_per_row[i])
                self._row_counts[phys_u[i]] = cnt
            self._recount_rows(recount)
            touched = sorted(phys_u.tolist())
            self._version += 1
            self._dirty.update(touched)
            if not use_oplog:
                self._ack_snapshot_locked()
            self._commit_caches_locked(touched)
            return self._seed_containers_locked(
                containers_by_row,
                fresh={int(uniq_rows[i]) for i in fresh_set})

    def _seed_containers_locked(self, containers_by_row, fresh=None):
        """Seed pre-classified containers into the serving memos for
        rows the batch created; returns {format: count} of what
        actually seeded (the pilosa_ingest_containers_seeded_total
        truth). Caller holds ``self.mu``; ``fresh`` None means compute
        freshness as rows whose only bits are the batch's (the
        fallback path already installed, so 'count equals the memo's
        count' is the test)."""
        seeded = {}
        if not containers_by_row:
            return seeded
        from pilosa_tpu.ops import containers as containers_mod

        if not containers_mod.enabled():
            return seeded
        ver = self._version
        for row_id, (fmt, cont) in containers_by_row.items():
            phys = self._row_index.get(row_id)
            if phys is None:
                continue
            if fresh is not None:
                if row_id not in fresh:
                    continue
            elif cont is None or int(self._row_counts[phys]) != cont.count:
                continue
            self._cont_fmt[phys] = (ver, fmt)
            if cont is not None and fmt != bitops.FMT_DENSE:
                self._memo_container(phys, cont)
            seeded[fmt] = seeded.get(fmt, 0) + 1
        return seeded

    def import_value_bits(self, column_ids, base_values, bit_depth):
        """Bulk BSI import: vectorized plane writes — the analog of
        ImportValue (ref: fragment.go:1335-1367). Overwrites any
        previous value (stale plane bits are cleared). Durability rides
        the op log while the amortized threshold allows (a value write
        is one ADD/REMOVE per plane bit, and replay is last-op-wins, so
        overwrite semantics round-trip) — but ONLY when every column is
        a fresh insert: a torn group replays as null, which for an
        overwrite would destroy the previously acknowledged value. The
        reference's snapshot + atomic rename guarantees old-or-new,
        never neither (fragment.go:1335-1367), so batches touching any
        existing value snapshot too. Larger fresh loads also snapshot —
        the reference's per-call snapshot made chunked BSI loads
        O(total²), exactly like the set-bit cadence."""
        with self.mu:
            self._check_writable()
            column_ids = np.asarray(column_ids, dtype=np.uint64)
            base_values = np.asarray(base_values, dtype=np.uint64)
            if len(column_ids) == 0:
                return
            bad = column_ids // SLICE_WIDTH != self.slice
            if bad.any():
                raise ValueError(
                    f"column:{int(column_ids[bad][0])} out of bounds for "
                    f"slice {self.slice}")
            cols = column_ids % SLICE_WIDTH
            self._ensure_window(int(cols.min()) >> 6, int(cols.max()) >> 6)
            # Last write wins for duplicate columns within one batch
            # (the reference applies pairs sequentially,
            # fragment.go:1335); without this the clear-then-set plane
            # writes would OR the duplicate values' bits together.
            _, last_rev = np.unique(cols[::-1], return_index=True)
            if len(last_rev) != len(cols):
                keep = np.sort(len(cols) - 1 - last_rev)
                cols = cols[keep]
                base_values = base_values[keep]
            lcols = cols - np.uint64(self._w64_base * 64)
            words = (lcols >> np.uint64(6)).astype(np.int64)
            masks = np.uint64(1) << (lcols & np.uint64(63))
            # Overwrite check BEFORE mutation: any target column whose
            # not-null bit is already set holds an acknowledged value.
            # Those batches must snapshot — the op-log group's torn-tail
            # semantics (null) may only erase unacknowledged writes.
            nn_phys = self._row_index.get(bit_depth)
            any_overwrite = (nn_phys is not None and bool(
                (self._matrix[nn_phys, words] & masks).any()))
            n_ops = (bit_depth + 2) * len(cols)
            use_oplog = (self._opened and not any_overwrite
                         and self._op_log_room(n_ops))
            if use_oplog:
                # Fresh inserts only (checked above). COLUMN-MAJOR
                # records with a null sandwich per value: [REMOVE
                # not-null, plane ops..., ADD not-null]. A crash can
                # tear the appended group at any byte; replay is
                # last-op-wins, so a column whose group is torn before
                # its final ADD ends with the not-null bit CLEARED — it
                # reads as null (unacknowledged write absent), never as
                # a phantom mix of old and new plane bits. Plane-major
                # order would leave exactly that mix. Appended BEFORE
                # the plane writes (fail-stop contract).
                plane_ids = np.arange(bit_depth, dtype=np.uint64)
                sel = ((base_values[None, :] >> plane_ids[:, None])
                       & np.uint64(1)) == 1
                nn_pos = np.uint64(bit_depth * SLICE_WIDTH) + cols
                # Rows of the record matrix: 0 = REMOVE nn, 1..depth =
                # plane ops, depth+1 = ADD nn; ravel(order="F") lays
                # the records out column-by-column.
                pos_m = np.empty((bit_depth + 2, len(cols)),
                                 dtype=np.uint64)
                typ_m = np.empty((bit_depth + 2, len(cols)),
                                 dtype=np.uint8)
                pos_m[0] = nn_pos
                typ_m[0] = codec.OP_REMOVE
                pos_m[1:-1] = (plane_ids[:, None]
                               * np.uint64(SLICE_WIDTH) + cols[None, :])
                typ_m[1:-1] = np.where(sel, codec.OP_ADD,
                                       codec.OP_REMOVE)
                pos_m[-1] = nn_pos
                typ_m[-1] = codec.OP_ADD
                self._append_ops_locked(
                    codec.op_records(typ_m.ravel(order="F"),
                                     pos_m.ravel(order="F")),
                    fsync=True)  # acknowledged durable, as import
                self.op_n += n_ops
            touched = []
            for i in range(bit_depth + 1):
                phys = self._ensure_row(i)
                touched.append(phys)
                if i == bit_depth:
                    sel = np.ones(len(cols), dtype=bool)  # not-null row
                else:
                    sel = ((base_values >> np.uint64(i)) & np.uint64(1)) == 1
                # Clear all stale bits for these columns, then set selected.
                np.bitwise_and.at(self._matrix, (phys, words), ~masks)
                np.bitwise_or.at(self._matrix, (phys, words[sel]), masks[sel])
            self._recount_rows(touched)
            self._version += 1
            self._dirty.update(touched)
            if not use_oplog:
                self._ack_snapshot_locked()
            self._commit_caches_locked(touched)

    # ------------------------------------------------------------ queries

    def count(self):
        with self.mu:
            return int(self._row_counts[: len(self._phys_rows)].sum())

    def checksum(self):
        """Hash of block hashes (ref: fragment.go:1023)."""
        h = b"".join(cs for _, cs in self.blocks())
        return xxhash64(h).to_bytes(8, "little")

    def _block_pairs(self, block_id):
        lo, hi = block_id * HASH_BLOCK_SIZE, (block_id + 1) * HASH_BLOCK_SIZE
        rows, cols = [], []
        for row_id in self.rows():
            if row_id < lo or row_id >= hi:
                continue
            phys = self._row_index[row_id]
            if not self._row_counts[phys]:
                continue
            bits = self._extract_bits(self._matrix[phys])
            bits = bits + np.uint64(self._w64_base * 64)  # window → global
            rows.append(np.full(len(bits), row_id, dtype=np.uint64))
            cols.append(bits)
        if not rows:
            return np.empty(0, np.uint64), np.empty(0, np.uint64)
        return np.concatenate(rows), np.concatenate(cols)

    def _lazy_row_full(self, reader, row_id):
        """uint64[WORDS64] full-width row streamed straight from the
        container reader — NO memoization: anti-entropy walks every
        row once, and caching them would cycle the shared memo and
        hold bytes the walk never reuses."""
        row = np.zeros(WORDS64, dtype=np.uint64)
        base_key = row_id * _CONTAINERS_PER_ROW
        for sub in range(_CONTAINERS_PER_ROW):
            block = reader.container(base_key + sub)
            if block is not None:
                row[sub * _WORDS64_PER_CONTAINER
                    : (sub + 1) * _WORDS64_PER_CONTAINER] = block
        return row

    @staticmethod
    def _extract_bits(words64):
        """Bit positions of a uint64 row (native fast path, NumPy
        fallback) — the ONE extraction used by both resident and lazy
        block walks, so their checksums can never drift."""
        from pilosa_tpu import native

        if native.available():
            bits = native.extract_positions(words64)
            if bits is not None:
                return np.asarray(bits, dtype=np.uint64)
        return np.flatnonzero(np.unpackbits(
            words64.view(np.uint8), bitorder="little")).astype(np.uint64)

    @staticmethod
    def _block_checksum(rows, cols):
        """Anti-entropy checksum over one block's (row, col) pairs —
        shared by resident and lazy walks (layout drift between the
        two would make a node's replicas disagree every pass)."""
        buf = np.stack([rows, cols], axis=1).astype("<u8").tobytes()
        return xxhash64(buf).to_bytes(8, "little")

    def _lazy_row_ids(self, reader):
        return sorted({k // _CONTAINERS_PER_ROW for k in reader.keys()})

    def _lazy_block_pairs(self, reader, block_id, row_ids=None):
        """(rowIDs, colIDs) for one 100-row block from streamed lazy
        rows — same ascending order and global positions as the
        resident _block_pairs. ``row_ids`` lets _lazy_blocks pass the
        pre-grouped list so the key set isn't re-enumerated per
        block."""
        if row_ids is None:
            lo = block_id * HASH_BLOCK_SIZE
            hi = (block_id + 1) * HASH_BLOCK_SIZE
            row_ids = [r for r in self._lazy_row_ids(reader)
                       if lo <= r < hi]
        rows, cols = [], []
        for row_id in row_ids:
            bits = self._extract_bits(self._lazy_row_full(reader, row_id))
            if len(bits) == 0:
                continue
            rows.append(np.full(len(bits), row_id, dtype=np.uint64))
            cols.append(bits)
        if not rows:
            return np.empty(0, np.uint64), np.empty(0, np.uint64)
        return np.concatenate(rows), np.concatenate(cols)

    def _lazy_blocks(self, reader):
        by_block = {}
        for r in self._lazy_row_ids(reader):
            by_block.setdefault(r // HASH_BLOCK_SIZE, []).append(r)
        out = []
        for block_id in sorted(by_block):
            rows, cols = self._lazy_block_pairs(reader, block_id,
                                                by_block[block_id])
            if len(rows) == 0:
                continue
            out.append((block_id, self._block_checksum(rows, cols)))
        return out

    def digest(self):
        """8-byte CONTENT-TRUE fragment-level anti-entropy digest: a
        multilinear hash Σ word·mix64(global word index) mod 2^64 over
        the fragment's decoded 64-bit words (all-zero content — the
        empty fragment — digests to the canonical zero bytes a replica
        404 maps to).

        Content-deterministic across replicas regardless of on-disk
        encoding, op-log state, or residency: both paths hash the
        DECODED words, never file bytes (two replicas holding identical
        bits can differ physically — one snapshotted, one with pending
        op-log records — so payload-byte hashing would force walks on
        every unsnapshotted fragment). The syncer compares this one
        value per replica and skips the whole per-block checksum walk
        on agreement (ref contrast: syncFragment walks unconditionally,
        fragment.go:1703-1782; Checksum() hash-of-block-hashes,
        fragment.go:1023, is the content-true shape this follows).
        Unlike the earlier (key, cardinality) digest — whose blind spot
        was SYSTEMATIC: any cardinality-preserving divergence passed
        forever, requiring a periodic unconditional walk — a collision
        here needs Σ Δword·c_i = 0 mod 2^64 against fixed pseudorandom
        constants: ~2^-64 for any fixed divergence, no structured
        class, so the skip is exact and unconditional (replicas are
        same-installation peers, not adversaries).

        Version-keyed memo (the _win32_memo pattern): the syncer calls
        this for EVERY fragment each pass; an unchanged fragment —
        resident or evicted — answers from the memo without touching
        its words again.

        Consistency invariant (tested): resident global word index
        row·16384 + w64_base + w equals lazy key·1024 + word-in-
        container for the same bit, because key = row·16 + sub and
        16·1024 = 16384 = WORDS64."""
        memo = self._digest_memo
        if memo is not None and memo[0] == self._version:
            return memo[1]
        version = self._version
        lazy = self._lazy_serve(self._lazy_digest)
        if lazy is not _NOT_LAZY:
            self._digest_memo = (version, lazy)
            return lazy
        with self.mu:
            n = len(self._phys_rows)
            if n == 0:
                val = _EMPTY_DIGEST
            else:
                base = np.uint64(self._w64_base) + np.arange(
                    self._w64, dtype=np.uint64)
                rows = np.asarray(self._phys_rows, dtype=np.uint64)
                total = 0  # Python int: np scalar += warns on wrap
                # Row-chunked: the constants matrix is as large as the
                # matrix slice it multiplies, so bound the transient.
                for i in range(0, n, 256):
                    chunk = self._matrix[i : min(i + 256, n)]
                    gwid = (rows[i : i + len(chunk), None]
                            * np.uint64(WORDS64) + base[None, :])
                    total += int(
                        (chunk * _mix64(gwid)).sum(dtype=np.uint64))
                val = (total & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
            self._digest_memo = (self._version, val)
            return val

    def _lazy_digest(self, reader):
        """Content hash over an evicted fragment without materializing
        8 KB blocks per container (the naive container() loop cost
        ~90 µs/container in per-key numpy overhead — ~19 s for a
        400-fragment identical-replica pass).

        Vectorization identities: for ARRAY containers, distinct bit
        positions within one word sum without carry, so
        word·C = Σ_bits 2^(p&63)·C — the whole fragment's array
        positions batch into ONE (shift, mix, multiply, sum) pass.
        BITMAP containers multiply their mmap'd words directly against
        their constants in chunks. RUN containers and op-touched keys
        (both rare on an evicted snapshot) take the exact container()
        path. All paths feed the same Σ word·mix64(gwid) mod 2^64."""
        wpos = np.arange(codec.BITMAP_N, dtype=np.uint64)
        total = 0  # Python int: np scalar += warns on wrap
        mm = reader._mm

        arr_keys, arr_metas = [], []
        for key in reader.keys():
            meta = reader.metas.get(key)
            if meta is None or key in reader._ops:
                block = reader.container(key)
                if block is None:
                    continue
                gwid = np.uint64(key) * np.uint64(codec.BITMAP_N) + wpos
                total += int((block * _mix64(gwid)).sum(dtype=np.uint64))
                continue
            ctype, n, coff = meta
            if ctype == codec.TYPE_ARRAY:
                arr_keys.append(key)
                arr_metas.append((n, coff))
            elif ctype == codec.TYPE_BITMAP:
                words = np.frombuffer(mm, dtype="<u8",
                                      count=codec.BITMAP_N, offset=coff)
                gwid = np.uint64(key) * np.uint64(codec.BITMAP_N) + wpos
                total += int((words * _mix64(gwid)).sum(dtype=np.uint64))
            else:  # RUN: decode exactly (rare)
                block = reader.container(key)
                gwid = np.uint64(key) * np.uint64(codec.BITMAP_N) + wpos
                total += int((block * _mix64(gwid)).sum(dtype=np.uint64))

        if arr_keys:
            # One batched pass over every array container's positions.
            counts = np.asarray([n for n, _ in arr_metas])
            pos = np.empty(int(counts.sum()), dtype=np.uint16)
            off = 0
            for (n, coff) in arr_metas:
                pos[off:off + n] = np.frombuffer(mm, dtype="<u2",
                                                 count=n, offset=coff)
                off += n
            keys64 = np.repeat(
                np.asarray(arr_keys, dtype=np.uint64), counts)
            p64 = pos.astype(np.uint64)
            gwid = keys64 * np.uint64(codec.BITMAP_N) + (
                p64 >> np.uint64(6))
            vals = np.uint64(1) << (p64 & np.uint64(63))
            total += int((vals * _mix64(gwid)).sum(dtype=np.uint64))
        return (total & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")

    def blocks(self):
        """[(block_id, checksum bytes)] for non-empty 100-row blocks
        (ref: fragment.go:1046-1125). Served container-granularly on
        evicted fragments: the periodic anti-entropy walk must not
        fault a whole cold index's matrices in every pass."""
        lazy = self._lazy_serve(self._lazy_blocks)
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu, tracing.span("fragment.block_pack",
                                   slice=self.slice):
            out = []
            if not self._phys_rows:
                return out
            for block_id in sorted({r // HASH_BLOCK_SIZE for r in self.rows()}):
                rows, cols = self._block_pairs(block_id)
                if len(rows) == 0:
                    continue
                out.append((block_id, self._block_checksum(rows, cols)))
            return out

    def block_data(self, block_id):
        """(rowIDs, columnIDs) in ascending position order
        (ref: fragment.go:1127-1137)."""
        lazy = self._lazy_serve(
            lambda r: self._lazy_block_pairs(r, block_id))
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            return self._block_pairs(block_id)

    def merge_block(self, block_id, pair_sets):
        """Majority-consensus merge (ref: fragment.go:1144-1253).

        ``pair_sets`` is a list of (rowIDs, colIDs) from remote replicas.
        Applies the local diff and returns per-remote (sets, clears)
        lists of (rowIDs, colIDs) needed to bring each remote to
        consensus. Even splits resolve to set.
        """
        with self.mu:
            lo_row = block_id * HASH_BLOCK_SIZE
            hi_row = (block_id + 1) * HASH_BLOCK_SIZE

            def keyset(rows, cols):
                rows = np.asarray(rows, dtype=np.uint64)
                cols = np.asarray(cols, dtype=np.uint64)
                keep = (rows >= lo_row) & (rows < hi_row)
                return set(zip(rows[keep].tolist(), cols[keep].tolist()))

            local_rows, local_cols = self._block_pairs(block_id)
            participants = [keyset(local_rows, local_cols)]
            participants += [keyset(r, c) for r, c in pair_sets]
            majority = (len(participants) + 1) // 2

            all_pairs = set().union(*participants)
            consensus = {
                p for p in all_pairs
                if sum(p in s for s in participants) >= majority
            }

            diffs = []
            for s in participants:
                sets = sorted(consensus - s)
                clears = sorted(s - consensus)
                diffs.append((sets, clears))

            for row_id, col in diffs[0][0]:
                self.set_bit(int(row_id), self.slice * SLICE_WIDTH + int(col))
            for row_id, col in diffs[0][1]:
                self.clear_bit(int(row_id), self.slice * SLICE_WIDTH + int(col))
            return diffs[1:]

    # ----------------------------------------------------------------- BSI

    def _planes(self, depth):
        """jnp uint32[depth+1, W]: planes 0..depth-1 + exists plane
        (full slice width)."""
        return self.planes_win(depth, 0, WORDS_PER_SLICE)

    def planes_win(self, depth, base32, width32, device=None):
        """jnp uint32[depth+1, width32] plane matrix rebased into the
        column window [base32, base32+width32) of uint32 device words
        (base32 must be even — windows are 64-bit-word aligned), on
        ``device`` as ``device_row_win`` places a row.

        On an EVICTED fragment the planes assemble from lazy container
        decodes (BSI plane rows 0..depth) — Sum/Min/Max/Range over a
        cold index never faults matrices in; the memo blocks are
        governor-charged like every lazy read."""
        lazy = self._lazy_serve(
            lambda r: self._lazy_planes(r, depth, base32, width32, device))
        if lazy is not _NOT_LAZY:
            return lazy
        with self.mu:
            key = (depth, base32, width32)
            version = self._version
            cached = self._planes_cache.get(key + (device,))
            if cached and cached[0] == version:
                return cached[1]
            planes = _memo_moved(self._planes_cache, key, version, device,
                                 depth + 1)
            if planes is not None:
                return self._planes_put(key, version, device, planes)
            b64, w64 = base32 // 2, width32 // 2
            mat = np.zeros((depth + 1, w64), dtype=np.uint64)
            lo = max(self._w64_base, b64)
            hi = min(self._w64_base + self._w64, b64 + w64)
            if lo < hi:
                for i in range(depth + 1):
                    phys = self._row_index.get(i)
                    if phys is not None:
                        mat[i, lo - b64 : hi - b64] = self._matrix[
                            phys,
                            lo - self._w64_base : hi - self._w64_base]
            return self._planes_put(
                key, version, device,
                jax.device_put(mat.view(np.uint32), device))

    def set_field_value(self, column_id, bit_depth, value):
        """Write value bits into rows 0..depth-1 + not-null row
        (ref: fragment.go:517-546)."""
        with self.mu:
            changed = False
            for i in range(bit_depth):
                if (value >> i) & 1:
                    changed |= self.set_bit(i, column_id)
                else:
                    changed |= self.clear_bit(i, column_id)
            changed |= self.set_bit(bit_depth, column_id)
            return changed

    def field_value(self, column_id, bit_depth):
        """(value, exists) for one column (ref: fragment.go:493-515)."""
        with self.mu:
            col = column_id % SLICE_WIDTH
            word, mask = col >> 6, np.uint64(1 << (col & 63))

            def bit(row_id):
                phys = self._row_index.get(row_id)
                base = self._w64_base
                if phys is None or not (base <= word < base + self._w64):
                    return False
                return bool(self._matrix[phys, word - base] & mask)

            if not bit(bit_depth):
                return 0, False
            value = 0
            for i in range(bit_depth):
                if bit(i):
                    value |= 1 << i
            return value, True

    def field_sum(self, filter_words, bit_depth):
        """(sum, count) over columns with a value, optionally ∩ filter
        (ref: FieldSum fragment.go:590-618)."""
        planes = self._planes(bit_depth)
        if filter_words is None:
            filt = planes[bit_depth]
        else:
            filt = bitops.bitmap_and(
                planes[bit_depth],
                jnp.asarray(np.ascontiguousarray(filter_words).view(np.uint32)))
        counts = bitops.launch("bsi_plane_counts", bsi_ops.plane_counts,
                               planes[:bit_depth], filt, cut=bitops.NO_CUT)
        total = sum((1 << i) * int(c) for i, c in enumerate(counts))
        return total, int(bitops.count(filt))

    def field_range(self, op, bit_depth, predicate):
        """uint64[WORDS64] bitmap of matching columns
        (ref: FieldRange fragment.go:621-798)."""
        planes = self._planes(bit_depth)
        exists = planes[bit_depth]
        bits = bsi_ops.value_to_bits(predicate, bit_depth)
        fn = {
            "==": bsi_ops.bsi_eq, "!=": bsi_ops.bsi_neq,
            "<": bsi_ops.bsi_lt, "<=": bsi_ops.bsi_lte,
            ">": bsi_ops.bsi_gt, ">=": bsi_ops.bsi_gte,
        }[op]
        out = bitops.launch(fn.__name__, fn, planes[:bit_depth], exists,
                            bits, cut=bitops.NO_CUT)
        return np.ascontiguousarray(out).view(np.uint64)

    def field_range_between(self, bit_depth, lo, hi):
        planes = self._planes(bit_depth)
        out = bitops.launch(
            "bsi_between", bsi_ops.bsi_between, planes[:bit_depth],
            planes[bit_depth], bsi_ops.value_to_bits(lo, bit_depth),
            bsi_ops.value_to_bits(hi, bit_depth), cut=bitops.NO_CUT)
        return np.ascontiguousarray(out).view(np.uint64)

    def field_not_null(self, bit_depth):
        """(ref: FieldNotNull fragment.go:755)."""
        return np.array(self.row_words(bit_depth))

    def field_min_max(self, filter_words, bit_depth, find_max):
        """(value, count). Bit-descent Min/Max over the planes."""
        planes = self._planes(bit_depth)
        filt = planes[bit_depth]
        if filter_words is not None:
            filt = bitops.bitmap_and(
                filt, jnp.asarray(np.ascontiguousarray(filter_words).view(np.uint32)))
        if int(bitops.count(filt)) == 0:
            return 0, 0
        # The indicators come to the host; what remains stays on the
        # device for its count.
        ind, remaining = bitops.launch(
            "bsi_extrema", bsi_ops.bsi_extrema_indicators,
            planes[:bit_depth], filt, cut=bitops.NO_CUT,
            fetch=lambda out: (np.asarray(out[0]), out[1]),
            static={"find_max": find_max})
        value = sum((1 << i) * int(b) for i, b in enumerate(ind))
        return value, int(bitops.count(remaining))

    # ---------------------------------------------------------------- TopN

    def top(self, opt=None):
        """TopN over this fragment (ref: fragment.go:831-963).

        TPU path: one fused popcount over the whole row matrix (optionally
        ∩ src) replaces the reference's ranked-cache walk — counts are
        exact, not cache-approximate. The cache's *candidate* semantics
        are preserved: with no explicit row_ids, only rows present in the
        cache are eligible (ref: topBitmapPairs fragment.go:965), and a
        ``none``-cache frame yields no TopN results, as in the reference.

        The filter bitmap: ``opt.src`` (host words: trimmed to the
        window, uploaded, ``|src|`` popcounted here) or ``opt.src_row``,
        the id of one of this fragment's own rows. Then the scan's
        program gathers the probe from the HBM mirror by its physical
        index (a traced scalar) and takes ``|src|`` from the row
        counts: nothing of the probe crosses to the host. The ``top.src``
        span is tagged ``probe`` = ``mirror`` | ``host`` accordingly.

        Where the selection runs is decided by what the request is:
        with a src, an ``n``, no explicit ``row_ids`` (the phase-2
        re-query is never truncated a slice), no ``filter_row_ids``
        (attribute filters stay on the host) and a bucket of at most
        ``SELECT_MAX_K``, the scan's program selects as well
        (``_top_device``): eligibility, the ``K`` largest masked counts
        and the tie count come back in a kilobyte, and the host orders
        at most ``K`` pairs by ``(-count, id)``. Everything else — no
        src (the cache walk over host row counts), explicit ids,
        filters, more rows tied at the cut than ``K`` holds — takes a
        count a row and ``_top_select``. ``opt.selected`` says which;
        the ``top.select`` span carries it as ``where``.

        The scan's operands have the mirror's shape, not the row
        count's: ``device_matrix()`` as the fragment holds it (``_cap``
        rows, a power of two), ``_cap`` row counts and ``_cap``
        eligibility bits, with nothing between the mirror and the
        program. A device-side ``[:n_phys]`` is a program of its own
        that reads and writes the whole matrix before every scan (2.3x
        the scan's device time at 500,000 rows, PR 33's trace) and a
        new shape to compile for every appended row; this way a scan
        compiles when the mirror doubles, as it is uploaded anew then
        anyway. The padded rows cannot change an answer: a row past
        ``n_phys`` is all zero in ``_matrix`` (``_grow_rows_locked``
        allocates zeros, rows are only ever appended) and has row
        count 0, so its intersection is 0;
        ``tanimoto_keep(0, 0, src_n, T)`` is ``0 > T*src_n``, false;
        the device's selection never names a row with a count of 0 or
        one that is not eligible; and where the counts come back
        ``_cap`` long they are cut to the physical rows on the host (a
        view) before ``_top_select``, whose ``counts > 0`` a 0 fails
        anyway. A probe's ``phys`` is below ``n_phys``, so ``row_at``
        never reads a padded row as the probe.
        """
        from pilosa_tpu.ops import topn as topn_ops
        from pilosa_tpu.storage.cache import NopCache

        opt = opt or TopOptions()
        from_mirror = opt.src_row is not None
        has_src = from_mirror or opt.src is not None
        if not has_src:
            # Src-less TopN is a cache walk + exact counts — both
            # available on an EVICTED fragment (cache sidecar + header
            # cardinalities), so don't fault the matrix in for it.
            out = self._lazy_serve(lambda r: self._lazy_top(r, opt))
            if out is not _NOT_LAZY:
                return out
        with self.mu:
            n_phys = len(self._phys_rows)
            if n_phys == 0:
                return []
            if opt.row_ids is None and isinstance(self.cache, NopCache):
                return []
            if has_src:
                # Only the src-intersection path reads the device
                # matrix; refreshing it for the src-less cache walk
                # cost a device upload per fragment per query for data
                # the counts never touch.
                with tracing.span("top.src", rows=n_phys,
                                  probe="mirror" if from_mirror else "host"):
                    if from_mirror:
                        # A row the fragment lacks is an empty src:
                        # nothing intersects it. A row written a moment
                        # ago reaches the mirror in ``device_matrix``'s
                        # refresh, as the rows the scan reads do.
                        self._note_row_read(opt.src_row, 2 * self._w64)
                        phys = self._row_index.get(opt.src_row)
                        if phys is None:
                            return []
                        probe = np.int32(phys)
                    else:
                        # The matrix may be narrower than the full
                        # slice; bits beyond its width are zero, so
                        # trimming src to the matrix width preserves
                        # every intersection count. The Tanimoto
                        # denominator's |src| must still come from
                        # the FULL src bitmap.
                        src_words = np.ascontiguousarray(opt.src)
                        base = self._w64_base
                        probe = jnp.asarray(np.ascontiguousarray(
                            src_words[base : base + self._w64]
                        ).view(np.uint32))
                    matrix = self.device_matrix()
                querystats.add("topnRowsScanned", n_phys)
                src_n = (None if from_mirror
                         else int(np.bitwise_count(src_words).sum()))
                if (opt.n and opt.row_ids is None
                        and opt.filter_row_ids is None
                        and topn_ops.select_k(opt.n)
                        <= topn_ops.SELECT_MAX_K):
                    pairs = self._top_device(opt, matrix, probe, src_n)
                    if pairs is not None:
                        opt.selected = "device"
                        return pairs
                    opt.selected = "overflow"
                else:
                    opt.selected = "host"
                if not opt.tanimoto_threshold:
                    counts = topn_ops.fetch_counts(
                        bitops.count_and_rows_at if from_mirror
                        else bitops.count_and_rows, matrix, probe)
                elif from_mirror:
                    counts = topn_ops.fetch_counts(
                        topn_ops.tanimoto_masked_counts_at, matrix, probe,
                        self._row_counts_device(),
                        opt.tanimoto_threshold,
                        op="topn_tanimoto_frag_probe")
                else:
                    counts = topn_ops.fetch_counts(
                        topn_ops.tanimoto_masked_counts, matrix, probe,
                        self._row_counts_device(), src_n,
                        opt.tanimoto_threshold, op="topn_tanimoto_frag")
                counts = counts[:n_phys]
            else:
                counts = self._row_counts[:n_phys].copy()

            with tracing.span("top.select", rows=n_phys,
                              where=opt.selected or "host"):
                return self._top_select(opt, counts)

    def _top_device(self, opt, matrix, probe, src_n):
        """A scan whose program selects as well (``ops/topn.py``
        ``_select_top``): the ``k`` largest eligible masked counts come
        back with their physical rows, a kilobyte where ``_top_select``
        is handed a count a row of the mirror, and the host orders at
        most ``k`` pairs by ``(-count, id)`` and cuts at ``n``. None
        where more rows tie at or above the n-th count than ``k``
        holds: the caller then selects over all the counts. Caller
        holds ``mu``; ``src_n`` is None where ``probe`` is a physical
        row of the mirror."""
        from pilosa_tpu.ops import topn as topn_ops

        k = topn_ops.select_k(opt.n)
        scalars = np.array(
            [probe if src_n is None else src_n, opt.tanimoto_threshold,
             min(opt.min_threshold, SLICE_WIDTH + 1), opt.n],
            dtype=np.int32)
        tail = (scalars, self._row_counts_device(), self._elig_device())
        if src_n is None:
            out = topn_ops.fetch_counts(
                topn_ops.tanimoto_select_at, matrix, *tail, k=k,
                op="topn_tanimoto_frag_probe_select")
        else:
            out = topn_ops.fetch_counts(
                topn_ops.tanimoto_select, matrix, probe, *tail, k=k,
                op="topn_tanimoto_frag_select")
        k = len(out) // 2         # a mirror shorter than the bucket: all of it
        if out[-1] > k:
            return None
        counts = out[:k]
        kept = int(np.count_nonzero(counts))   # descending: zeros last
        with tracing.span("top.select", rows=kept, where="device"):
            counts = counts[:kept]
            ids = self._phys_row_ids()[out[k:k + kept]]
            order = np.lexsort((ids, -counts))[:opt.n]
            return list(zip(ids[order].tolist(), counts[order].tolist()))

    def _elig_device(self):
        """bool[cap] device copy of ``_cached_rows_mask()`` (false past
        the last physical row): the rows the device's selection may
        return. Uploaded once and kept while the host mask is the same
        object and the mirror as long: a row appended or a change of
        the cache's membership builds a new mask. Caller holds
        ``mu``."""
        mask = self._cached_rows_mask()
        memo = self._elig_dev
        if (memo is None or memo[0] is not mask
                or memo[1].shape[0] != self._cap):
            padded = np.zeros(self._cap, dtype=bool)
            padded[:len(mask)] = mask
            memo = self._elig_dev = (mask, jnp.asarray(padded))
        return memo[1]

    def _phys_row_ids(self):
        """uint64 array of the physical rows' ids (read only), kept
        while the list is the same list at the same length: rows are
        only ever appended, and a reload starts a new list. Converting
        500,000 Python ints cost every ``top`` 18 of its 30 ms of
        selection (PR 26's spans)."""
        memo = self._phys_arr
        if (memo is None or memo[0] is not self._phys_rows
                or len(memo[1]) != len(self._phys_rows)):
            memo = self._phys_arr = (self._phys_rows, np.asarray(
                self._phys_rows, dtype=np.uint64))
        return memo[1]

    def _cached_rows_mask(self):
        """bool[rows] (read only): the physical row is in the ranked
        cache, TopN's candidate rule. Kept while the rows' id array and
        the cache's (``cache.ids_arr()``) are the same objects: each is
        built anew when its membership changes. The ``isin`` it saves
        sorted a million ids a query, and its 30 MB of temporaries were
        where the selection's slow requests came from (PR 26's spans:
        ``top.select`` 6.7 ms in the median, 22-28 in one of twenty)."""
        ids, cached = self._phys_row_ids(), self.cache.ids_arr()
        memo = self._cache_mask
        if memo is None or memo[0] is not ids or memo[1] is not cached:
            memo = self._cache_mask = (ids, cached, np.isin(ids, cached))
        return memo[2]

    def _top_select(self, opt, counts):
        """Eligibility and selection over one count per physical row,
        on the host (caller holds ``mu``): (row id, count) pairs in
        (-count, id) order."""
        from pilosa_tpu.storage.cache import NopCache

        row_ids = self._phys_row_ids()
        counts_np = np.asarray(counts, dtype=np.int64)
        # Vectorized eligibility + selection: at the chem-500k shape
        # (500k cached rows in one fragment) the per-row Python loop +
        # full sort this replaces was ~300 ms/query — most of the
        # measured TopN latency on an accelerator.
        mask = counts_np > 0
        if opt.min_threshold:
            mask &= counts_np >= opt.min_threshold
        if opt.row_ids is not None:
            mask &= np.isin(row_ids, np.fromiter(
                opt.row_ids, dtype=np.uint64))
        elif not isinstance(self.cache, NopCache):
            mask &= self._cached_rows_mask()
        if opt.filter_row_ids is not None:
            mask &= np.isin(row_ids, np.fromiter(
                opt.filter_row_ids, dtype=np.uint64))
        idx = np.nonzero(mask)[0]
        # Explicit row ids (the TopN phase-2 exact re-query) are
        # never truncated per slice — trimming happens only after
        # the cross-slice merge (ref: fragment.go:835-838
        # "If row ids are provided, we don't want to truncate").
        truncate = bool(opt.n) and opt.row_ids is None
        if truncate and idx.size > opt.n:
            # Exact top-n: nth-largest count bounds the candidate
            # set (count ties straddling the cut stay in and are
            # broken by row id in the final sort).
            c = counts_np[idx]
            nth = c[np.argpartition(-c, opt.n - 1)[opt.n - 1]]
            idx = idx[c >= nth]
        order = np.lexsort((row_ids[idx], -counts_np[idx]))
        sel = idx[order[: opt.n]] if truncate else idx[order]
        return [(int(r), int(c))
                for r, c in zip(row_ids[sel], counts_np[sel])]

    # -------------------------------------------------------------- backup

    def write_to(self, fileobj):
        """Tar archive of data + cache (ref: fragment.go:1476-1560).

        An EVICTED fragment's roaring file (snapshot + op-log tail)
        already IS its current state — readers replay the tail — so
        backup streams the raw file bytes instead of faulting the
        matrix in to re-serialize it: backing up a cold index is file
        copying, not an index-wide decode."""
        import io

        if not self._resident and self._opened:
            done = fresh = False
            self.mu.acquire_raw()
            try:
                if not self._resident and self._opened:
                    fresh = (self._lazy_cache_ids is None
                             and not self._cache_loaded)
                    cache = json.dumps(sorted(
                        self._lazy_cache_ids_locked())).encode()
                    with open(self.path, "rb") as f:
                        # Streamed, not f.read(): a multi-GB cold
                        # fragment must not double-buffer through host
                        # memory — the resource eviction protects.
                        self._write_backup_tar(
                            fileobj, f, os.fstat(f.fileno()).st_size,
                            cache)
                    done = True
            finally:
                self.mu.release_raw()
            if done:
                if fresh and self.governor is not None:
                    self.governor.touch(self)
                    self.governor.update(self, self.host_bytes())
                return

        with self.mu:
            data = codec.serialize_arrays(*self._to_arrays())
            cache = json.dumps(self.cache.ids()).encode()
        self._write_backup_tar(fileobj, io.BytesIO(data), len(data),
                               cache)

    @staticmethod
    def _write_backup_tar(fileobj, data_stream, data_size, cache):
        """The ONE backup-archive layout (data + cache members),
        shared by the cold (raw-file stream) and resident
        (re-serialized) paths so the two formats cannot diverge."""
        import io
        import tarfile

        with tarfile.open(fileobj=fileobj, mode="w") as tar:
            info = tarfile.TarInfo("data")
            info.size = data_size
            tar.addfile(info, data_stream)
            cinfo = tarfile.TarInfo("cache")
            cinfo.size = len(cache)
            tar.addfile(cinfo, io.BytesIO(cache))

    def read_from(self, fileobj):
        """Restore from a backup tar (ref: fragment.go:1562-1648)."""
        import tarfile

        with tarfile.open(fileobj=fileobj, mode="r") as tar:
            for member in tar.getmembers():
                payload = tar.extractfile(member).read()
                if member.name == "data":
                    # Raw lock: restoring over an evicted/untouched
                    # fragment must not fault the soon-discarded old
                    # state in first.
                    self.mu.acquire_raw()
                    try:
                        self._drop_lazy_locked()  # file being replaced
                        blocks, op_n, torn = codec.deserialize(payload)
                        self._reset_storage()
                        self._load_blocks(blocks)
                        with open(self.path, "wb") as f:
                            # A file with no op log is written as it
                            # came, as upstream's ReadFrom does: encoding
                            # the blocks again was a third of a restore.
                            f.write(codec.serialize(blocks)
                                    if op_n or torn else payload)
                        if self._op_file:
                            self._op_file.close()
                            self._op_file = None
                        self.op_n = 0
                        # The rewritten file IS the new snapshot.
                        self._snap_card = int(self._row_counts.sum())
                        # A restore fully replaces both memory and the
                        # on-disk file — exactly the reload the
                        # fail-stop latch waits for — so it clears the
                        # latch: restoring over a fail-stopped
                        # fragment is the operator's repair path, and
                        # leaving writes 503ing after a verified
                        # restore would demand a pointless restart.
                        self._failed = None
                        self._resident = True  # restored state IS current
                        self._mem_changed()
                    finally:
                        self.mu.release_raw()
                elif member.name == "cache":
                    with open(self.cache_path, "wb") as f:
                        f.write(payload)
                    self.cache.clear()
                    self._open_cache()
                    self._cache_loaded = True
                    _bump_epoch(self.index)  # a new ranked membership

    def merge_from(self, fileobj):
        """Union-install a backup tar: every set bit in the snapshot
        is OR-ed into the CURRENT fragment (one vectorized
        import_bits), clearing nothing. The elastic-rebalance install
        path (cluster/rebalancer.py) for bit views: a replacing
        restore would wipe dual writes applied to this replica while
        the snapshot was in flight — the acked-write-loss race — while
        a union can only add bits the source held. The rank cache
        member is ignored (it rebuilds from the merged counts)."""
        import tarfile

        rows_out, cols_out = [], []
        with tarfile.open(fileobj=fileobj, mode="r") as tar:
            for member in tar.getmembers():
                if member.name != "data":
                    continue
                payload = tar.extractfile(member).read()
                blocks, _, _ = codec.deserialize(payload)
                cbits = _WORDS64_PER_CONTAINER * 64
                for key, words in blocks.items():
                    w = np.ascontiguousarray(words, dtype=np.uint64)
                    bits = np.flatnonzero(np.unpackbits(
                        w.view(np.uint8), bitorder="little"))
                    if len(bits) == 0:
                        continue
                    rows_out.append(np.full(len(bits), key
                                            // _CONTAINERS_PER_ROW,
                                            dtype=np.uint64))
                    cols_out.append(
                        bits.astype(np.uint64)
                        + np.uint64((key % _CONTAINERS_PER_ROW) * cbits
                                    + self.slice * SLICE_WIDTH))
        if rows_out:
            self.import_bits(np.concatenate(rows_out),
                             np.concatenate(cols_out))

    def _reset_storage(self):
        self._cap = 0
        self._w64 = _MIN_W64
        self._w64_base = 0
        self._matrix = np.zeros((0, _MIN_W64), dtype=np.uint64)
        self._row_counts = np.zeros(0, dtype=np.int64)
        self._row_index = {}
        self._phys_rows = []
        self.max_row_id = 0
        self._dev = None
        self._dirty.clear()
        self._planes_cache = {}
        self._row_dev = {}
        self._rc_dev = None
        self._elig_dev = None
        self._cont_dev = {}
        self._cont_fmt = {}
        self._version += 1
        _bump_epoch(self.index)
