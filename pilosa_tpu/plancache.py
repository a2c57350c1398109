"""Epoch-validated slice-plan cache — walk-free large-index serving.

At 10B columns an index spans ~9,540 slices, and before this tier
every query re-derived the same per-(index, slice-range) facts on a
pure-Python walk before any device work ran: the slice universe
(``idx.max_slice()`` iterates every view of every frame), the
fragment window layout and device/host residency (``_leaf_frags`` +
``_union_window``), the batched-dispatch plan (``_batched_plan``),
and the owner-host sets — plus O(slices) ``tuple(slices)`` memo keys
whose hashing alone cost ~0.5 ms/query at that scale. The roaring
line (arXiv:1402.6407) wins by computing per-container structural
metadata ONCE and reusing it; this module is the equivalent for the
executor's per-(index, slice-range) plan.

One cache, one validity protocol:

- **Keys** are ``(kind, index, slice-key, ...call shape)`` tuples.
  Kinds are caller-defined and need no registration here — the
  executor's memos ("plan", "row", "bsi", "topn1", ...), its
  per-(frame, view, slice list) fragment facts ("leaf" -> ``FragList``),
  and the adaptive planner's ``("planner", index, ast, slice-key)``
  decision memos (planner.py) share one LRU and show up separately
  in the snapshot's ``entriesByKind``. The slice-key is COMPACT: a verified-contiguous slice list keys as
  ``("#range", first, last)`` (O(1) to hash) instead of a 9,540-int
  tuple; only genuinely ragged lists (failover remap subsets) fall
  back to the exact tuple. ``SliceList`` carries the key it was built
  with so the hot path never re-derives it.
- **Validity** is a per-entry token the CALLER computes, in the same
  shapes the executor's memos already use: the scoped process-local
  mutation epoch (``storage/fragment.py``) for entries derived from
  local fragment state, the cluster topology state for owner sets,
  and PR 5's distributed epoch-vector tokens (``cluster/epochs.py``)
  where an entry covers remote data. A ``None`` token means
  unverifiable — the cache computes without storing: cold, never
  stale (the PR 5 contract). Any write on any node reaches this node
  as a local mutation (client write, relayed write, anti-entropy
  merge, hinted replay) and bumps the scoped epoch; fragment
  fail-stop and ``.corrupt`` quarantine bump it too (storage layer),
  so exactly the affected index's entries drop.
- **Real LRU**, configurable capacity (``[executor]
  plan-cache-entries`` / ``PILOSA_PLAN_CACHE_ENTRIES``; 0 = off —
  every lookup misses and nothing is stored), hit/miss/invalidation
  counters per index, exposed on ``/metrics``
  (``pilosa_plan_cache_*``) and ``GET /debug/plans``.

This subsumes the executor's former ad-hoc tiers: the FIFO 64-entry
``_owner_hosts_cache``, the FIFO ``_prelude_cache``, and the
per-query ``max_slice()`` walk (the slice-universe memo below).
"""
import os
import threading
from collections import OrderedDict

import numpy as np

from pilosa_tpu.storage import fragment as _frag
from pilosa_tpu import lockcheck

# Default entry budget: preludes/owner sets/plans are a few hundred
# host bytes each (stacks live in the byte-budgeted stack cache, NOT
# here), so a few hundred entries cover every realistic dashboard mix
# while bounding shape-churning clients.
DEFAULT_ENTRIES = 512

# Marker for compact contiguous slice keys. A real slices tuple holds
# only ints, so no exact-tuple fallback key can ever collide with
# ("#range", first, last).
RANGE_MARK = "#range"


class SliceList(list):
    """A slice list that remembers its compact cache key, so hot
    paths pay one attribute read instead of an O(n) re-derivation.
    Treated as IMMUTABLE by convention: the executor shares one
    instance across concurrent queries (every consumer copies before
    mutating, as ``_map_reduce`` always has)."""

    __slots__ = ("skey",)


class FragList(list):
    """One (frame, view)'s fragments over a slice list (``None`` where
    a slice has none), with the facts every batched prelude derives
    from them — none depends on the query, none can change without
    the index's mutation epoch moving, so the executor stores the
    list under kind ``"leaf"`` and composes a never-seen query's
    prelude from a few lookups instead of O(slices) walks:

    - ``tokens``: the stack cache's validity tuple, one ``(uid,
      version)`` per fragment. Stacks built from this list are stamped
      with this very object, so revalidating one is an identity check.
    - ``extent``: ``(lo, hi)`` in uint32 device words covering every
      fragment's column window, or None when no fragment holds a row.
    - ``dense``: row ids some fragment of the list serves dense (the
      negative outcome of the compressed-tier probe). Only that
      outcome is kept: dense -> compressed needs an eviction or a
      mutation (both bump the epoch), compressed -> dense does not (a
      fault-in), so a compressed-everywhere row is probed every time.
    - ``members``: the fragments' ranked-cache membership as arrays,
      built by TopN phase 1 on its first pass over the list (None
      until then): every write that reaches a ranked cache moves the
      epoch, and the epoch replaces the list.

    Shared across concurrent queries and immutable by convention, as
    ``SliceList``; ``dense`` only grows, ``members`` is set once, and
    both go with their entry."""

    __slots__ = ("tokens", "extent", "dense", "members")


def slice_key(slices):
    """Compact, exact cache key for a slice list: the precomputed key
    for a ``SliceList``; ``("#range", first, last)`` for a verified
    contiguous run; the exact tuple otherwise. The contiguity check is
    exact (numpy element compare in C) — span/length alone is NOT
    sufficient (e.g. [0, 2, 2] spans like [0, 1, 2])."""
    k = getattr(slices, "skey", None)
    if k is not None:
        return k
    n = len(slices)
    if n > 32 and slices[0] + n - 1 == slices[-1]:
        arr = np.asarray(slices)
        if bool(np.array_equal(arr, np.arange(arr[0], arr[-1] + 1))):
            return (RANGE_MARK, int(slices[0]), int(slices[-1]))
    return tuple(slices)


def as_slice_list(slices):
    """Wrap a plain list as a SliceList with its key computed once.
    The key is derived from the materialized copy, so one-shot
    iterables are safe."""
    out = SliceList(slices)
    out.skey = slice_key(out)
    return out


class PlanCache:
    """LRU of epoch-validated slice-plan entries + the per-index
    slice-universe memo. Thread-safe; every operation is a few dict
    moves under one short lock (token COMPUTATION stays with the
    caller — a cluster vector validation may probe a peer and must
    never run under this lock)."""

    def __init__(self, capacity=None):
        if capacity is None:
            env = os.environ.get("PILOSA_PLAN_CACHE_ENTRIES")
            if env:
                try:
                    capacity = max(0, int(env))
                except ValueError:
                    capacity = DEFAULT_ENTRIES
            else:
                capacity = DEFAULT_ENTRIES
        self.capacity = int(capacity)
        self._mu = lockcheck.register("plancache.PlanCache._mu",
                                      threading.Lock())
        self._entries = OrderedDict()   # key -> (token, value)
        self._universe = {}             # index -> (token, std, inv)
        self.hits = 0
        self.misses = 0
        self.invalidations = 0
        self._by_index = {}             # index -> [hits, misses]

    def set_capacity(self, capacity):
        """Resize (config path); shrinking evicts LRU-first, 0 wipes
        and disables."""
        with self._mu:
            self.capacity = max(0, int(capacity))
            if self.capacity == 0:
                self._entries.clear()
                self._universe.clear()
                return
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)

    # ------------------------------------------------------------ entries

    def _note(self, index, hit):
        """Per-index hit/miss tally. Caller holds self._mu."""
        st = self._by_index.get(index)
        if st is None:
            st = self._by_index[index] = [0, 0]
        st[0 if hit else 1] += 1

    def get(self, key, token, record=True):
        """Value for ``key`` when its stored token equals ``token``
        (LRU-refreshing); None on miss or staleness. A stale entry is
        dropped eagerly — epochs are monotone, it can never validate
        again — and counts as an invalidation. ``token=None`` (caller
        could not verify) is always a miss and never drops: the entry
        may validate once visibility returns. ``record=False`` skips
        the hit/miss counters (invalidations still count) — for
        callers whose lookup only succeeds after a second resolution
        step (prelude memos resolving device stacks), who call
        ``record()`` with the true outcome instead."""
        index = key[1]
        with self._mu:
            ent = self._entries.get(key)
            if ent is None:
                if record:
                    self.misses += 1
                    self._note(index, False)
                return None
            if token is None or ent[0] != token:
                if token is not None:
                    del self._entries[key]
                    self.invalidations += 1
                if record:
                    self.misses += 1
                    self._note(index, False)
                return None
            self._entries.move_to_end(key)
            if record:
                self.hits += 1
                self._note(index, True)
            return ent[1]

    def peek(self, key, token):
        """Pure read: the value for ``key`` when its stored token
        equals ``token``, else None — NO LRU refresh, NO hit/miss
        accounting, NO stale-entry drop. The explain-only surface
        (observe/explain.py) reports plan-cache state through this so
        planning a query without executing it provably mutates
        nothing."""
        with self._mu:
            ent = self._entries.get(key)
            if ent is None or token is None or ent[0] != token:
                return None
            return ent[1]

    def record(self, index, hit):
        """Count a deferred lookup outcome (see ``get(record=False)``)."""
        with self._mu:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
            self._note(index, hit)

    def put(self, key, token, value):
        """Store (no-op when disabled or the token is unverifiable —
        cold, never stale)."""
        if token is None or self.capacity == 0:
            return
        with self._mu:
            # Re-check under the lock: a concurrent set_capacity(0)
            # (live reconfiguration) must not revive entries — and the
            # eviction loop would popitem() an emptied dict (0 >= 0).
            if self.capacity == 0:
                return
            self._entries.pop(key, None)
            while len(self._entries) >= self.capacity and self._entries:
                self._entries.popitem(last=False)
            self._entries[key] = (token, value)

    def entries_view(self, kinds=None):
        """Snapshot mapping of entry key -> stored value (optionally
        filtered by kind = key[0]) — introspection and tests."""
        with self._mu:
            return {k: v[1] for k, v in self._entries.items()
                    if kinds is None or k[0] in kinds}

    # ----------------------------------------------------------- universe

    @staticmethod
    def _fresh_universe(idx):
        """Build the (std, inv) shared SliceLists from a max_slice()
        walk — ONE constructor for both the memoizing and the
        read-only paths, so their universes can never drift."""
        std = SliceList(range(idx.max_slice() + 1))
        std.skey = (RANGE_MARK, 0, len(std) - 1)
        inv = SliceList(range(idx.max_inverse_slice() + 1))
        inv.skey = (RANGE_MARK, 0, len(inv) - 1)
        return std, inv

    def slice_universe(self, index, idx):
        """The index's full (standard, inverse) slice lists as shared
        ``SliceList``s, memoized against the scoped mutation epoch
        plus the peer-reported max slices (``set_remote_max_slice``
        moves without an epoch bump — heartbeats widen the range).
        This replaces the per-query ``max_slice()`` walk over every
        view of every frame (~0.24 ms at 9,540 slices)."""
        token = (_frag.mutation_epoch(index), idx.remote_max_slice,
                 idx.remote_max_inverse_slice)
        if self.capacity != 0:
            with self._mu:
                ent = self._universe.get(index)
                if ent is not None and ent[0] == token:
                    self.hits += 1
                    self._note(index, True)
                    return ent[1], ent[2]
                self.misses += 1
                self._note(index, False)
        std, inv = self._fresh_universe(idx)
        if self.capacity != 0:
            # Token captured BEFORE the max_slice walk: a write landing
            # mid-walk makes the memo stale-on-arrival, never wrong.
            # Capacity re-checked under the lock so a concurrent
            # set_capacity(0) can't be re-populated behind its back.
            with self._mu:
                if self.capacity != 0:
                    self._universe[index] = (token, std, inv)
        return std, inv

    def universe_peek(self, index, idx):
        """(std, inv, memo-hit?) — the read-only twin of
        ``slice_universe``: a memo hit returns the shared lists; a
        miss computes fresh ones WITHOUT storing (and without
        hit/miss accounting). The explain-only surface."""
        token = (_frag.mutation_epoch(index), idx.remote_max_slice,
                 idx.remote_max_inverse_slice)
        with self._mu:
            ent = self._universe.get(index)
            if ent is not None and ent[0] == token:
                return ent[1], ent[2], True
        std, inv = self._fresh_universe(idx)
        return std, inv, False

    def drop_index(self, index):
        """Explicitly drop every entry AND the per-index stats for
        ``index`` (index deletion — the name may never be queried
        again, so lazy epoch invalidation would retain them forever)."""
        with self._mu:
            self._universe.pop(index, None)
            self._by_index.pop(index, None)
            dead = [k for k in self._entries if k[1] == index]
            for k in dead:
                del self._entries[k]
            self.invalidations += len(dead)

    # -------------------------------------------------------------- intro

    def metrics(self):
        """Flat dict for the /metrics ``pilosa_plan_cache_*`` group.
        ``entries`` is LRU occupancy only (comparable to
        ``capacity``); universe memos — one per live index, outside
        the LRU — report separately, and both surfaces (here and
        ``snapshot``) agree on the split."""
        with self._mu:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "invalidations": self.invalidations,
                "entries": len(self._entries),
                "universe_entries": len(self._universe),
                "capacity": self.capacity,
            }

    def snapshot(self):
        """GET /debug/plans payload: totals, per-index hit rates and
        current validity epochs, per-kind entry counts, and the
        universe memo state."""
        with self._mu:
            total = self.hits + self.misses
            kinds = {}
            for k in self._entries:
                kinds[k[0]] = kinds.get(k[0], 0) + 1
            per_index = {}
            for index, (h, m) in self._by_index.items():
                per_index[index] = {
                    "hits": h, "misses": m,
                    "hitRate": round(h / (h + m), 4) if h + m else 0.0,
                    "validityEpoch": _frag.mutation_epoch(index),
                }
            universe = {
                index: {"slices": len(std), "inverseSlices": len(inv),
                        "token": list(tok)}
                for index, (tok, std, inv) in self._universe.items()}
            return {
                "enabled": self.capacity != 0,
                "capacity": self.capacity,
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "invalidations": self.invalidations,
                "hitRate": round(self.hits / total, 4) if total else 0.0,
                "entriesByKind": kinds,
                "perIndex": per_index,
                "universe": universe,
            }
