"""Holder — root registry of all indexes under a data directory
(ref: holder.go:46-70)."""
import logging
import os
import shutil
import threading
import time
import uuid

from pilosa_tpu import errors as perr
from pilosa_tpu import faults
from pilosa_tpu import stats as stats_mod
from pilosa_tpu.storage import fragment as fragment_mod
from pilosa_tpu.storage.index import Index
from pilosa_tpu.storage.memgov import HostMemGovernor
from pilosa_tpu import lockcheck

_LOG = logging.getLogger("pilosa_tpu.storage.holder")


class Holder:
    def __init__(self, path, host_bytes=None):
        self.path = path
        self.mu = lockcheck.register("storage.Holder.mu",
                                     threading.RLock(),
                                     allow_device_sync=True)
        self.indexes = {}
        self.local_id = None
        self.broadcaster = None  # set by Server before open()
        self.stats = stats_mod.NOP
        # Flight recorder (observe.events), server-installed and
        # propagated down the index/frame/view/fragment chain like
        # .stats; None when off.
        self.events = None
        # Host-memory budget for resident fragment matrices (the
        # reference's analog is the OS evicting cold mmap pages). Env
        # override so operators can cap RSS without code changes.
        if host_bytes is None:
            env = os.environ.get("PILOSA_TPU_HOST_BYTES")
            if env:
                try:
                    host_bytes = int(env)
                    if host_bytes <= 0:
                        raise ValueError(env)
                except ValueError:
                    host_bytes = None
        self.governor = HostMemGovernor(host_bytes)
        # Deletion tombstones: ("index", name) / ("frame", idx, name)
        # -> unix deletion time. The heartbeat piggyback's create-only
        # schema union would otherwise RESURRECT deletions — any
        # in-flight or lagging peer's status re-creates the object and
        # re-propagates it cluster-wide every probe round. Tombstones
        # ride the status; an explicit local re-create clears them.
        self._tombstones = {}
        self._status_memo = None  # (monotonic, schema, digest)
        # Bumped (under mu) by EVERY schema-changing path —
        # including Index._create_frame via
        # invalidate_status_memo() — so a memo rebuild that
        # raced a DDL can detect it and decline to install a
        # pre-DDL schema over the invalidation.
        self._status_ver = 0
        # Fired with the index NAME after an index leaves self.indexes
        # by ANY path — explicit delete, heartbeat tombstone merge, or
        # replica resync. The executor hangs its plan-cache release
        # here (plancache.drop_index): the epoch bump alone only
        # invalidates lazily, and a deleted index is never queried
        # again, so its entries and unbounded universe memos would be
        # retained until evicted.
        self.on_index_drop = None

    def open(self):
        """Scan directories and open every index→frame→view→fragment
        (ref: holder.go:87-150)."""
        with self.mu:
            os.makedirs(self.path, exist_ok=True)
            self._acquire_dir_lock()
            try:
                self._set_file_limit()
                for entry in sorted(os.listdir(self.path)):
                    full = os.path.join(self.path, entry)
                    if not os.path.isdir(full) or entry.startswith("."):
                        continue
                    # Partial-boot hardening: one unreadable index must
                    # not fail the whole node (unreadable FRAGMENT
                    # files are quarantined deeper down, at fault-in —
                    # fragment._quarantine_locked; this catches the
                    # structural failures above them: meta JSON rot,
                    # permission errors, the holder.open.partial
                    # failpoint). The skipped index stays on disk for
                    # the operator; everything else serves.
                    try:
                        if faults.ACTIVE.enabled:
                            faults.ACTIVE.fire("holder.open.partial")
                        idx = Index(full, entry)
                        idx.broadcaster = self.broadcaster
                        idx.stats = self.stats.with_tags(f"index:{entry}")
                        idx.governor = self.governor
                        idx.events = self.events
                        idx.holder = self  # tombstone plumbing
                        idx.open()
                    except perr.ErrFragmentLocked:
                        # A held lock is a deliberate REFUSAL — another
                        # process owns this data (mixed-era mutual
                        # exclusion) — not rot to boot around: two
                        # writers would corrupt what a skipped index
                        # merely hides.
                        raise
                    except Exception:  # noqa: BLE001 — boot must survive
                        _LOG.warning(
                            "index %s failed to open; skipping (node "
                            "boots without it)", entry, exc_info=True)
                        self.stats.count("holder_open_errors_total", 1)
                        continue
                    self.indexes[entry] = idx
                self._load_local_id()
                self._load_tombstones_locked()
            except BaseException:
                # A failed open must not leak the dir lock: a retry in
                # this process would hit its own stale fd forever.
                self._release_dir_lock()
                raise
        return self

    def close(self):
        with self.mu:
            try:
                for idx in self.indexes.values():
                    idx.close()
                self.indexes = {}
            finally:
                self._release_dir_lock()

    def _acquire_dir_lock(self):
        """ONE exclusive flock on the data directory instead of one
        per fragment (the same cross-process guard as
        fragment.go:203-205, at 1 fd instead of ~10k at 10B-column
        scale — per-fragment lock fds exhausted RLIMIT_NOFILE on a
        2-node 10B benchmark in one process). Replica holders (worker
        read-only views of a master's files) take no lock."""
        if fragment_mod.REPLICA:
            return
        self._dir_lock = fragment_mod.try_flock(
            os.path.join(self.path, fragment_mod.HOLDER_LOCK_NAME),
            perr.ErrHolderLocked)
        fragment_mod.register_locked_root(self.path)

    def _release_dir_lock(self):
        lock = getattr(self, "_dir_lock", None)
        if lock is not None:
            fragment_mod.unregister_locked_root(self.path)
            try:
                lock.close()
            except OSError:
                pass
            self._dir_lock = None

    def refresh_replica(self):
        """Replica worker resync (server/workers.py): reconcile the
        in-memory tree against the master's on-disk state — new
        indexes open, deleted ones close, survivors re-fault lazily."""
        with self.mu:
            try:
                on_disk = {
                    e for e in os.listdir(self.path)
                    if os.path.isdir(os.path.join(self.path, e))
                    and not e.startswith(".")}
            except FileNotFoundError:
                on_disk = set()
            for entry in sorted(on_disk - self.indexes.keys()):
                full = os.path.join(self.path, entry)
                idx = Index(full, entry)
                idx.broadcaster = self.broadcaster
                idx.stats = self.stats.with_tags(f"index:{entry}")
                idx.governor = self.governor
                idx.events = self.events
                idx.holder = self
                idx.open()
                self.indexes[entry] = idx
            dropped = []
            for entry in list(self.indexes.keys() - on_disk):
                self.indexes.pop(entry).close()
                dropped.append(entry)
            indexes = list(self.indexes.values())
        if self.on_index_drop is not None:
            for entry in dropped:
                self.on_index_drop(entry)
        for idx in indexes:
            idx.refresh_replica()

    @staticmethod
    def _set_file_limit(target=262144):
        """Raise RLIMIT_NOFILE toward ~262k (ref: setFileLimit
        holder.go:385-431): every open fragment holds its data-file and
        lock-file descriptors, so big schemas exhaust the default soft
        limit (often 1024) fast."""
        try:
            import resource

            soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
            if soft == resource.RLIM_INFINITY:  # already unlimited (-1
                return                          # in Python — never lower)
            want = target if hard == resource.RLIM_INFINITY \
                else min(target, hard)
            if soft < want:
                try:
                    resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
                except (ValueError, OSError):
                    # Some kernels (darwin kern.maxfilesperproc) cap below
                    # the reported hard limit; retry with the reference's
                    # darwin fallback (holder.go:418-424).
                    fallback = 10240
                    if soft < fallback:
                        resource.setrlimit(resource.RLIMIT_NOFILE,
                                           (fallback, hard))
        except (ImportError, ValueError, OSError):
            pass  # non-POSIX or insufficient privilege: keep defaults

    def _load_local_id(self):
        """Persist a node UUID at <data>/.id (ref: holder.go:435-453)."""
        id_path = os.path.join(self.path, ".id")
        if os.path.exists(id_path):
            with open(id_path) as f:
                self.local_id = f.read().strip()
        else:
            self.local_id = str(uuid.uuid4())
            with open(id_path, "w") as f:
                f.write(self.local_id)

    # ----------------------------------------------------------- indexes

    def index_path(self, name):
        return os.path.join(self.path, name)

    def index(self, name):
        with self.mu:
            return self.indexes.get(name)

    def indexes_list(self):
        with self.mu:
            return [self.indexes[k] for k in sorted(self.indexes)]

    TOMBSTONE_TTL = 24 * 3600

    def _tombstone_path(self):
        return os.path.join(self.path, ".tombstones")

    def _save_tombstones_locked(self):
        """Persist live tombstones: a node that deletes and then
        restarts must still refuse a lagging peer's resurrection."""
        import json as _json

        now = time.time()
        live = [list(k) + [ts] for k, ts in self._tombstones.items()
                if now - ts < self.TOMBSTONE_TTL]
        try:
            with open(self._tombstone_path(), "w") as f:
                _json.dump(live, f)
        except OSError:
            pass

    def _load_tombstones_locked(self):
        import json as _json

        try:
            with open(self._tombstone_path()) as f:
                entries = _json.load(f)
        except (OSError, ValueError):
            return
        now = time.time()
        for entry in entries:
            *key_parts, ts = entry
            if now - ts < self.TOMBSTONE_TTL:
                self._tombstones[tuple(key_parts)] = ts

    def _record_tombstone(self, key):
        with self.mu:
            self._tombstones[key] = time.time()
            self._invalidate_status_memo_locked()  # schema changed
            self._save_tombstones_locked()

    def _clear_tombstone(self, key):
        with self.mu:
            if self._tombstones.pop(key, None) is not None:
                self._save_tombstones_locked()
            self._invalidate_status_memo_locked()

    def _tombstone_live(self, key):
        ts = self._tombstones.get(key)
        # Tombstone stamps are PERSISTED (.tombstones, heartbeats)
        # and compared against peer/meta createdAt wall stamps —
        # monotonic can't survive a restart or cross a node.
        # pilint: disable=deadline-clock
        return ts is not None and time.time() - ts < self.TOMBSTONE_TTL

    def _admit_tombstoned(self, key, created_at):
        """Schema-merge gate: False when a live deletion tombstone
        blocks this name. An advertised creation NEWER than the
        tombstone is a legitimate re-create — it clears the tombstone
        and is admitted (last-write-wins reconciliation)."""
        if not self._tombstone_live(key):
            return True
        if created_at > self._tombstones.get(key, 0):
            self._clear_tombstone(key)
            return True
        return False

    def create_index(self, name, column_label="", time_quantum=""):
        with self.mu:
            if name in self.indexes:
                raise perr.ErrIndexExists()
            # An explicit local re-create overrides any deletion
            # tombstone (the tombstone only blocks MERGE resurrection).
            self._tombstones.pop(("index", name), None)
            return self._create_index(name, column_label, time_quantum)

    def create_index_if_not_exists(self, name, column_label="", time_quantum=""):
        with self.mu:
            return self.indexes.get(name) or self._create_index(
                name, column_label, time_quantum)

    def _create_index(self, name, column_label, time_quantum):
        """Caller holds self.mu."""
        if not name:
            raise perr.ErrIndexRequired()
        idx = Index(self.index_path(name), name)
        idx.broadcaster = self.broadcaster
        idx.stats = self.stats.with_tags(f"index:{name}")
        idx.governor = self.governor
        idx.events = self.events
        idx.holder = self  # frame create/delete tombstone plumbing
        idx.open()
        if column_label:
            idx.set_column_label(column_label)
        if time_quantum:
            idx.set_time_quantum(time_quantum)
        idx.save_meta()
        self.indexes[name] = idx
        self._invalidate_status_memo_locked()  # schema changed
        # DDL is durable on disk now — let replica workers discover it
        # (the published epoch is their only schema-change signal).
        fragment_mod._bump_epoch(name)
        return idx

    def delete_index(self, name):
        with self.mu:
            idx = self.indexes.pop(name, None)
            if idx is None:
                raise perr.ErrIndexNotFound()
            self._tombstones[("index", name)] = time.time()
            self._invalidate_status_memo_locked()  # schema changed
            self._save_tombstones_locked()
        # close() takes idx.mu — never while holding holder.mu (the
        # frame tombstone path takes the locks in the other order).
        idx.close()
        shutil.rmtree(idx.path, ignore_errors=True)
        fragment_mod._bump_epoch(name)  # replicas drop the index
        if self.on_index_drop is not None:
            self.on_index_drop(name)

    # ------------------------------------------------------------ schema

    def schema(self, include_meta=False):
        """(ref: holder.go:173) — [{name, frames:[{name, views}]}].

        ``include_meta`` adds index/frame options + BSI fields — the
        payload used for rejoin reconciliation, where name-only schema
        would recreate frames with default options."""
        with self.mu:
            out = []
            for idx in self.indexes_list():
                frames = []
                # list() snapshots: holder.mu does not guard idx.frames
                # (idx.mu does) — heartbeat merges mutate them from
                # other threads while this walk runs.
                for fname in sorted(list(idx.frames)):
                    frame = idx.frames.get(fname)
                    if frame is None:
                        continue
                    info = {
                        "name": fname,
                        "views": [{"name": v}
                                  for v in sorted(list(frame.views))],
                    }
                    if include_meta:
                        # Creation stamp lets receivers reconcile a
                        # re-create against their deletion tombstone
                        # (newer creation wins).
                        info["createdAt"] = getattr(
                            frame, "created_at", 0)
                        info["options"] = {
                            "rowLabel": frame.row_label,
                            "inverseEnabled": frame.inverse_enabled,
                            "rangeEnabled": frame.range_enabled,
                            "cacheType": frame.cache_type,
                            "cacheSize": frame.cache_size,
                            "timeQuantum": frame.time_quantum,
                            "fields": [fd.to_dict() for fd in frame.fields],
                        }
                    frames.append(info)
                info = {"name": idx.name, "frames": frames}
                if include_meta:
                    info["createdAt"] = getattr(idx, "created_at", 0)
                    info["options"] = {"columnLabel": idx.column_label,
                                       "timeQuantum": idx.time_quantum}
                out.append(info)
            return out

    def apply_schema(self, schema):
        """Merge a remote schema (ref: Index.MergeSchemas index.go:576).
        Create-only, like the reference — but deletion tombstones are
        honored: a merged schema can never resurrect an object deleted
        locally within the tombstone TTL."""
        from pilosa_tpu.storage.index import FrameOptions

        for idx_info in schema:
            if not self._admit_tombstoned(("index", idx_info["name"]),
                                          idx_info.get("createdAt", 0)):
                continue
            opts = idx_info.get("options", {})
            idx = self.create_index_if_not_exists(
                idx_info["name"],
                column_label=opts.get("columnLabel", ""),
                time_quantum=opts.get("timeQuantum", ""))
            for f_info in idx_info.get("frames", []):
                if not self._admit_tombstoned(
                        ("frame", idx_info["name"], f_info["name"]),
                        f_info.get("createdAt", 0)):
                    continue
                fopts = f_info.get("options")
                frame = idx.create_frame_if_not_exists(
                    f_info["name"],
                    FrameOptions.from_dict(fopts) if fopts else None)
                for v_info in f_info.get("views", []):
                    frame.create_view_if_not_exists(v_info["name"])

    def node_status_compact(self, host):
        """Compact NodeStatus for heartbeat piggyback: full meta schema
        (apply_schema merges it idempotently), a stable schema digest,
        and the max-slice maps. The analog of what memberlist exchanges
        in gossip push/pull (gossip.go LocalState/MergeRemoteState, end
        of file) — schema and slice convergence rides every probe
        instead of waiting for the rejoin push or the 60 s poll.

        Senders strip the ``schema`` field when the other side's digest
        already matches, so steady-state probes stay O(bytes of the
        max-slice map) on the wire, not O(schema)."""
        schema, digest = self._schema_and_digest()
        now = time.time()
        with self.mu:  # snapshot: handler threads mutate under mu
            items = list(self._tombstones.items())
        tombs = [list(k) + [ts] for k, ts in items
                 if now - ts < self.TOMBSTONE_TTL]
        return {
            "host": host,
            "schema": schema,
            "schemaDigest": digest,
            "tombstones": tombs,
            "maxSlices": self.max_slices(),
            "maxInverseSlices": self.max_inverse_slices(),
        }

    def _invalidate_status_memo_locked(self):
        """Drop the schema/digest memo after a schema change. Caller
        holds self.mu. The version bump lets a concurrently-running
        _schema_and_digest rebuild detect that its walk predates this
        change and decline to install — without it, the rebuild's
        re-stamp silently overwrote the invalidation and re-served
        the pre-DDL digest for a full memo TTL (found by pilint's
        guarded-state pass: _status_memo written both under and
        outside mu)."""
        self._status_ver += 1
        self._status_memo = None

    def invalidate_status_memo(self):
        """Cross-class invalidation hook (Index._create_frame runs
        under idx.mu and must take holder.mu to touch the memo —
        idx.mu -> holder.mu is the established frame-path order, see
        Index.create_frame)."""
        with self.mu:
            self._invalidate_status_memo_locked()

    def _schema_and_digest(self):
        """(schema, digest), memoized for 2 s: the status is built per
        probe per peer plus per inbound heartbeat — O(schema) walks +
        hashing every few seconds in steady state otherwise. The short
        TTL means a just-changed schema ships at most one round late.

        The memo is read and installed under mu, versioned against
        concurrent invalidations; the O(schema) walk itself runs
        outside the lock (schema() re-enters the RLock as needed)."""
        import hashlib
        import json as _json

        now = time.monotonic()
        with self.mu:
            memo = self._status_memo
            ver = self._status_ver
        if memo is not None and now - memo[0] < 2.0:
            return memo[1], memo[2]
        schema = self.schema(include_meta=True)

        # Digest the LOGICAL schema only: the meta-level createdAt is
        # node-local (two nodes creating the same object independently
        # — or one via broadcast — stamp different times), and hashing
        # it made such digests stable-but-unequal forever, which both
        # defeated the steady-state schema-strip optimization and
        # tripped the divergence warning on healthy clusters. Strip
        # ONLY the known index/frame meta slots — never recurse into
        # arbitrary values, where a user key happening to be named
        # 'createdAt' must keep counting as real content.
        scrubbed = []
        for idx in schema:
            idx = {k: v for k, v in idx.items() if k != "createdAt"}
            idx["frames"] = [
                {k: v for k, v in fr.items() if k != "createdAt"}
                for fr in idx.get("frames", [])]
            scrubbed.append(idx)
        digest = hashlib.sha1(
            _json.dumps(scrubbed, sort_keys=True)
            .encode()).hexdigest()[:16]
        with self.mu:
            if self._status_ver == ver:
                self._status_memo = (now, schema, digest)
            # else: a DDL landed mid-walk — serve this (still
            # self-consistent) snapshot but leave the memo cold so
            # the next probe rebuilds post-DDL.
        return schema, digest

    def merge_remote_status(self, st):
        """Merge a peer's compact NodeStatus (heartbeat piggyback):
        deletion tombstones first (they gate the union), then the
        create-only schema union and monotonic max-slice maxima — all
        idempotent, so repeated exchanges are free."""
        now = time.time()
        for entry in st.get("tombstones") or []:
            *key_parts, ts = entry
            key = tuple(key_parts)
            if now - ts >= self.TOMBSTONE_TTL:
                continue
            with self.mu:
                if self._tombstones.get(key, 0) < ts:
                    self._tombstones[key] = ts
                    self._invalidate_status_memo_locked()
                    self._save_tombstones_locked()
            # Apply the deletion locally unless our object was created
            # AFTER the tombstone (a legitimate re-create wins). The
            # removal keeps the PEER's original stamp — going through
            # delete_index/delete_frame would re-stamp at local time,
            # inflating the tombstone past legitimate re-creates and
            # deleting them back off the cluster.
            if key[0] == "index" and len(key) == 2:
                with self.mu:
                    idx = self.indexes.get(key[1])
                    if idx is None or getattr(idx, "created_at",
                                              now) > ts:
                        idx = None
                    else:
                        self.indexes.pop(key[1])
                        self._invalidate_status_memo_locked()
                if idx is not None:
                    idx.close()
                    shutil.rmtree(idx.path, ignore_errors=True)
                    if self.on_index_drop is not None:
                        self.on_index_drop(key[1])
            elif key[0] == "frame" and len(key) == 3:
                idx = self.index(key[1])
                if idx is not None:
                    fr = idx.frame(key[2])
                    if fr is not None and getattr(
                            fr, "created_at", now) <= ts:
                        idx.delete_frame(key[2],
                                         record_tombstone=False)
                        with self.mu:
                            self._invalidate_status_memo_locked()
        self.apply_schema(st.get("schema") or [])
        for index, n in (st.get("maxSlices") or {}).items():
            idx = self.index(index)
            if idx is not None:
                idx.set_remote_max_slice(int(n))
        for index, n in (st.get("maxInverseSlices") or {}).items():
            idx = self.index(index)
            if idx is not None:
                idx.set_remote_max_inverse_slice(int(n))

    def fragment(self, index, frame, view, slice_num):
        """Accessor chain (ref: holder.go:196-338)."""
        idx = self.index(index)
        if idx is None:
            return None
        fr = idx.frame(frame)
        if fr is None:
            return None
        v = fr.view(view)
        if v is None:
            return None
        return v.fragment(slice_num)

    def fragments(self, index, frame, view, slices):
        """Bulk accessor: resolve index→frame→view ONCE, then one
        lookup per slice. Batched executors fetch whole slice lists
        (1B columns = 954 fragments per list) and keep each until the
        index's mutation epoch moves (executor._frag_list); the
        per-call chain walk was a measurable slice of query latency."""
        idx = self.index(index)
        fr = idx.frame(frame) if idx is not None else None
        v = fr.view(view) if fr is not None else None
        if v is None:
            return [None] * len(slices)
        return [v.fragment(s) for s in slices]

    def prune_fragments(self, keep_fn):
        """Drop every local fragment whose ``(index_name, slice)``
        fails ``keep_fn`` — the post-rebalance removal pass
        (cluster/rebalancer.py): a committed resize leaves the old
        owners holding verified-elsewhere copies that should stop
        costing disk. Walks snapshots of the inner maps (fragments can
        be created concurrently — those are by definition owned, the
        write path routed them here). Returns fragments removed."""
        removed = 0
        for idx in self.indexes_list():
            for frame in list(idx.frames.values()):
                for v in list(frame.views.values()):
                    with v.mu:
                        slices = list(v.fragments)
                    for s in slices:
                        if not keep_fn(idx.name, s):
                            if v.drop_fragment(s):
                                removed += 1
        return removed

    def max_slices(self):
        """{index: max_slice} (ref: handler /slices/max)."""
        with self.mu:
            return {name: idx.max_slice() for name, idx in self.indexes.items()}

    def max_inverse_slices(self):
        with self.mu:
            return {name: idx.max_inverse_slice()
                    for name, idx in self.indexes.items()}

    # ------------------------------------------------- memory accounting

    _MEM_KEYS = ("hostBytes", "deviceBytes", "lazyBytes", "diskBytes",
                 "cacheEntries")

    def memory_stats(self):
        """Per-index and total memory occupancy — packed block bytes
        resident on host, device (HBM) mirror bytes, evicted-read memo
        bytes, roaring bytes on disk, TopN cache entries — plus the
        governor's view. Serves ``GET /debug/memory`` and the
        ``pilosa_memory_*`` gauges. The fragment walk reads gauges
        lock-free (Fragment.memory_stats); the index list snapshots
        under holder.mu like schema().

        Memoized for 2 s (the _schema_and_digest discipline): the walk
        is O(total fragments) with a stat() syscall each for the disk
        gauge, and a scraped node answers /metrics, /cluster/metrics
        fan-in, and /debug/vars back to back — gauges tolerate 2 s of
        staleness, a 10k-fragment stat storm per surface does not."""
        now = time.monotonic()
        memo = getattr(self, "_mem_memo", None)
        if memo is not None and now - memo[0] < 2.0:
            return memo[1]
        with self.mu:
            indexes = [(name, self.indexes[name])
                       for name in sorted(self.indexes)]
        per_index = {}
        totals = dict.fromkeys(self._MEM_KEYS, 0)
        totals["fragments"] = totals["residentFragments"] = 0
        totals["containers"] = self._empty_container_agg()
        for name, idx in indexes:
            agg = dict.fromkeys(self._MEM_KEYS, 0)
            agg["fragments"] = agg["residentFragments"] = 0
            cagg = self._empty_container_agg()
            for frame in list(idx.frames.values()):
                for view in list(frame.views.values()):
                    for frag in list(view.fragments.values()):
                        m = frag.memory_stats()
                        agg["fragments"] += 1
                        if m["resident"]:
                            agg["residentFragments"] += 1
                        for k in self._MEM_KEYS:
                            agg[k] += m[k]
                        c = m["containers"]
                        for fmt, fv in c["formats"].items():
                            cagg["formats"][fmt]["blocks"] += fv["blocks"]
                            cagg["formats"][fmt]["bytes"] += fv["bytes"]
                        cagg["denseEquivBytes"] += c["denseEquivBytes"]
                        cagg["conversions"] += c["conversions"]
            agg["containers"] = cagg
            per_index[name] = agg
            for k, v in agg.items():
                if k == "containers":
                    for fmt, fv in v["formats"].items():
                        t = totals["containers"]["formats"][fmt]
                        t["blocks"] += fv["blocks"]
                        t["bytes"] += fv["bytes"]
                    totals["containers"]["denseEquivBytes"] += (
                        v["denseEquivBytes"])
                    totals["containers"]["conversions"] += (
                        v["conversions"])
                else:
                    totals[k] += v
        out = {"indexes": per_index, "totals": totals,
               "governor": self.governor.snapshot()}
        self._mem_memo = (now, out)
        return out

    @staticmethod
    def _empty_container_agg():
        """Zeroed per-format container rollup (the /debug/memory and
        pilosa_memory_container_* shape — dense/array/run block counts
        + payload bytes, the dense-tier-equivalent bytes for the same
        blocks, and conversion totals)."""
        return {"formats": {f: {"blocks": 0, "bytes": 0}
                            for f in ("dense", "array", "run")},
                "denseEquivBytes": 0, "conversions": 0}

    def memory_metrics(self):
        """Flat ``name;index:...`` dict for the /metrics ``memory``
        group (pilosa_memory_* series): per-index gauges plus governor
        totals."""
        ms = self.memory_stats()
        out = {}
        for name, agg in ms["indexes"].items():
            out[f"fragment_bytes;index:{name}"] = agg["hostBytes"]
            out[f"device_bytes;index:{name}"] = agg["deviceBytes"]
            out[f"lazy_bytes;index:{name}"] = agg["lazyBytes"]
            out[f"disk_bytes;index:{name}"] = agg["diskBytes"]
            out[f"cache_entries;index:{name}"] = agg["cacheEntries"]
            out[f"resident_fragments;index:{name}"] = agg[
                "residentFragments"]
            # Compressed container tier (ops/containers.py): per-format
            # resident block counts + payload bytes, the dense-tier
            # equivalent for the same blocks, and conversion totals.
            c = agg["containers"]
            for fmt, fv in c["formats"].items():
                out[f"container_blocks;index:{name},format:{fmt}"] = (
                    fv["blocks"])
                out[f"container_bytes;index:{name},format:{fmt}"] = (
                    fv["bytes"])
            out[f"container_dense_equiv_bytes;index:{name}"] = (
                c["denseEquivBytes"])
            out[f"container_conversions_total;index:{name}"] = (
                c["conversions"])
        gov = ms["governor"]
        out["governor_resident_bytes"] = gov["residentBytes"]
        out["governor_budget_bytes"] = gov["budgetBytes"]
        out["governor_evictions_total"] = gov["evictions"]
        out["governor_faults_total"] = gov["faults"]
        return out

    def flush_caches(self):
        """(ref: monitorCacheFlush holder.go:340-376). The inner maps
        are snapshotted: holder.mu guards index creation/deletion, but
        writes create fragments under the frame/view locks, so a bulk
        load mutates ``view.fragments`` mid-walk otherwise."""
        with self.mu:
            for idx in list(self.indexes.values()):
                for frame in list(idx.frames.values()):
                    for view in list(frame.views.values()):
                        for frag in list(view.fragments.values()):
                            frag.flush_cache()

    def recalculate_caches(self):
        """Rebuild every fragment's TopN cache from storage, then
        persist (ref: handleRecalculateCaches handler.go:2016). Holds
        holder.mu for the whole walk, like flush_caches, so concurrent
        index deletion can't pull directories out from under the
        sidecar writes."""
        with self.mu:
            for idx in list(self.indexes.values()):
                for frame in list(idx.frames.values()):
                    for view in list(frame.views.values()):
                        for frag in list(view.fragments.values()):
                            frag.recalculate_cache()
                            frag.flush_cache()
