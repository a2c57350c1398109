"""Compressed device-resident containers — the roaring tier on XLA.

The reference never materializes sparse bitmaps densely: a 2^16-bit
container with ≤4096 set bits is a sorted uint16 position ARRAY, long
runs collapse to (start, length) RUN pairs, and only genuinely dense
data pays the 8 KB bitmap (roaring.go:1011-1024; Chambi et al.,
arXiv:1402.6407; Lemire et al., arXiv:1603.06549). The TPU port's
dense ``uint32`` row vectors (bitops.py) made every resident row cost
its full window width in HBM regardless of sparsity — the memory
ceiling between 10B and 100B columns.

This module is the compressed tier: per-row-block ARRAY and RUN
containers with device kernels for the hot count paths, registered
into ``bitops``'s format-polymorphic dispatch table (the XLA analog of
the reference's ~30-kernel container matrix, roaring.go:1811-3283).

Formats (per row block — one row at one column window):

- **array** — sorted ``int32`` bit positions (window-relative).
  ``count`` is the length: zero device work (ref: array containers'
  ``n`` field). Ops against dense go through gather + bit-test; against
  another array through a sorted-merge membership test (searchsorted).
- **run** — sorted (start, end) half-open bit ranges. ``count`` is the
  summed lengths: zero device work. Ops against dense build the run
  mask by per-position boundary search (O(width) temporaries) fused
  into the popcount.
- **dense** — the existing uint32 word vector, wrapped so it carries
  its (already known) cardinality. Dense×dense dispatch is the exact
  pre-existing fused kernel path.

Count-only fast paths never materialize a dense intermediate: or/xor/
andnot counts derive from |a|, |b| and |a∩b| (exact for two operands —
the identities the reference's count-only paths exploit,
roaring.go:1811-1923), so every (op, format, format) cell reduces to
one intersection kernel plus host integers.

Padding: device kernels are shape-bucketed (positions pad to powers of
two) so jit compilation stays bounded; array sentinels are
out-of-window positions chosen so operand sentinels can never equal
each other or any valid position.
"""
import os
import threading
import time

import numpy as np

from pilosa_tpu.ops import bitops

from pilosa_tpu import lockcheck
from pilosa_tpu.observe import kerneltime as _kt

# Roaring thresholds (roaring.go:40-42): a block with ≤4096 set bits
# is cheaper as sorted positions than as a bitmap; a block whose run
# count is small enough that 2 ints/run beat both encodings is a run
# container.
ARRAY_MAX_BITS = 4096
RUN_MAX_RUNS = 2048

# Global gate ([storage] container-formats / PILOSA_CONTAINER_FORMATS,
# server/server.py): off = every block is dense = today's behavior.

def parse_enabled(value):
    """THE truthiness rule for PILOSA_CONTAINER_FORMATS-style strings
    — config.py calls this too, so the env surface and the module gate
    can never drift."""
    return str(value).lower() not in ("0", "false", "no", "off")


_ENABLED = parse_enabled(os.environ.get("PILOSA_CONTAINER_FORMATS", ""))

# Process-wide conversion counter (pilosa_container_conversions_total
# backstop for bare fragments; per-fragment counters roll up through
# holder.memory_stats).
_conv_mu = lockcheck.register("containers._conv_mu",
                              threading.Lock(),
                              allow_device_sync=True)
_conversions_total = 0


def set_enabled(on):
    global _ENABLED
    _ENABLED = bool(on)


def enabled():
    return _ENABLED


def note_conversion(n=1):
    global _conversions_total
    with _conv_mu:
        _conversions_total += n


def conversions_total():
    return _conversions_total


class Container:
    """One row block in one format. ``count`` is always host-known at
    construction (the density stat that chose the format), so
    cardinality queries cost zero device work in every format."""

    __slots__ = ("fmt", "width32", "count", "words", "positions", "runs",
                 "_pos_dev", "_pos_dev_b", "_runs_dev")

    def __init__(self, fmt, width32, count, words=None, positions=None,
                 runs=None):
        self.fmt = fmt
        self.width32 = int(width32)
        self.count = int(count)
        self.words = words          # dense: uint32[width32] (device or host)
        self.positions = positions  # array: np.int32[count] sorted, host
        self.runs = runs            # run: np.int32[n_runs, 2] (start, end)
        self._pos_dev = None
        self._pos_dev_b = None
        self._runs_dev = None

    # ------------------------------------------------------------ payload

    def nbytes(self):
        """Resident payload bytes in THIS format (device + host copy of
        the compressed payload counted once — the device copy is the
        serving one; the host copy is the build source)."""
        if self.fmt == bitops.FMT_ARRAY:
            return int(self.positions.nbytes)
        if self.fmt == bitops.FMT_RUN:
            return int(self.runs.nbytes)
        return int(getattr(self.words, "nbytes", self.width32 * 4))

    def dense_equiv_bytes(self):
        """What the dense tier would hold resident for this block."""
        return self.width32 * 4

    def device_positions(self, sentinel_off=0):
        """Padded sorted device positions (int32[pow2]) with the
        sentinel ``window limit + sentinel_off`` filling the tail
        (merge kernels give each operand side a distinct offset so
        padding can never compare equal). Both sides memoized."""
        import jax.numpy as jnp

        if sentinel_off:
            if self._pos_dev_b is None:
                self._pos_dev_b = jnp.asarray(pad_positions(
                    self.positions, self.width32 * 32, sentinel_off))
            return self._pos_dev_b
        if self._pos_dev is None:
            self._pos_dev = jnp.asarray(
                pad_positions(self.positions, self.width32 * 32))
        return self._pos_dev

    def device_runs(self):
        """Padded device (starts, ends) int32[pow2] pair; padding runs
        are the empty [limit, limit) — past every real run, so the
        starts stay SORTED (count_array_run bisects them) and the
        range mask of the padding is all-zero."""
        if self._runs_dev is None:
            import jax.numpy as jnp

            s, e = pad_runs(self.runs, self.width32 * 32)
            self._runs_dev = (jnp.asarray(s), jnp.asarray(e))
        return self._runs_dev

    def dense_words(self):
        """Dense uint32[width32] device words — the densify fallback
        every format must provide (bitops.densify). Deliberately NOT
        memoized: a cached full-width dense row per compressed
        container would quietly re-pin the dense-tier HBM footprint
        this tier exists to remove (8192 memoized containers × 128 KB
        ≈ 1 GB, ungoverned); materializing queries rebuild on demand
        and repeats are covered by the result-memo/replay tiers."""
        if self.fmt == bitops.FMT_DENSE:
            return self.words
        if self.fmt == bitops.FMT_ARRAY:
            return _array_to_dense(self.device_positions(), self.width32)
        s, e = self.device_runs()
        return _runs_to_dense(s, e, self.width32)

    def device_bytes(self):
        """HBM bytes this container's materialized device buffers hold
        (padded positions/runs). Dense containers report 0 — their
        words are the fragment's existing device mirrors, already
        charged by memory_stats."""
        if self.fmt == bitops.FMT_DENSE:
            return 0
        total = 0
        for buf in (self._pos_dev, self._pos_dev_b):
            if buf is not None:
                total += int(buf.nbytes)
        if self._runs_dev is not None:
            total += int(self._runs_dev[0].nbytes
                         + self._runs_dev[1].nbytes)
        return total

    def host_words64(self):
        """Host uint64[width32 // 2] reconstruction (tests/tools)."""
        out = np.zeros(self.width32, dtype=np.uint32)
        if self.fmt == bitops.FMT_DENSE:
            return np.asarray(self.words).view(np.uint64)
        if self.fmt == bitops.FMT_ARRAY:
            p = self.positions.astype(np.int64)
            np.bitwise_or.at(out, p >> 5,
                             (np.uint32(1) << (p & 31).astype(np.uint32)))
            return out.view(np.uint64)
        bits = np.zeros(self.width32 * 32, dtype=np.uint8)
        for s, e in self.runs.tolist():
            bits[s:e] = 1
        return np.packbits(bits, bitorder="little").view(np.uint64)


# --------------------------------------------------------- construction

def run_bounds(words64):
    """(starts, ends) half-open bit ranges of the set runs in a host
    uint64 word vector — one vectorized pass (a run starts at a set
    bit whose predecessor is clear; carries cross word boundaries)."""
    x = np.ascontiguousarray(words64, dtype=np.uint64)
    if not len(x):
        return (np.zeros(0, np.int32),) * 2
    prev_carry = np.zeros_like(x)
    prev_carry[1:] = x[:-1] >> np.uint64(63)
    start_mask = x & ~((x << np.uint64(1)) | prev_carry)
    next_carry = np.zeros_like(x)
    next_carry[:-1] = (x[1:] & np.uint64(1)) << np.uint64(63)
    end_mask = x & ~((x >> np.uint64(1)) | next_carry)
    starts = extract_positions(start_mask)
    ends = extract_positions(end_mask) + 1
    return starts.astype(np.int32), ends.astype(np.int32)


def extract_positions(words64):
    """Sorted set-bit positions of a host uint64 vector (int64)."""
    return np.flatnonzero(np.unpackbits(
        np.ascontiguousarray(words64, dtype=np.uint64).view(np.uint8),
        bitorder="little")).astype(np.int64)


def choose_format(count, n_runs):
    """The per-block format rule (density stats → format), the
    roaring thresholds verbatim: run when 2 ints/run undercut both the
    position array and the dense words; else array at ≤4096 set bits;
    else dense. Deterministic, so replicas agree."""
    if count == 0:
        return bitops.FMT_ARRAY
    if n_runs <= RUN_MAX_RUNS and 2 * n_runs < min(count,
                                                   ARRAY_MAX_BITS + 1):
        return bitops.FMT_RUN
    if count <= ARRAY_MAX_BITS:
        return bitops.FMT_ARRAY
    return bitops.FMT_DENSE


def build_container(words64, width32, dense_words=None, count=None,
                    offset=0, dense_fn=None):
    """Classify + build one row block from its host uint64 words.

    ``words64`` may be a WINDOW narrower than the container: ``offset``
    rebases positions/runs to container-global bit coordinates, and
    ``count``/``dense_fn`` let the storage tier supply its precomputed
    cardinality and full-width dense device row (``dense_words``: an
    already-built full-width array) instead of re-deriving them —
    there is ONE copy of the classify-and-build pipeline, shared by
    resident and lazy paths."""
    if count is None:
        count = int(np.bitwise_count(
            np.ascontiguousarray(words64, dtype=np.uint64)).sum())
    cnt = int(count)
    if cnt == 0:
        return empty_container(width32)
    starts, ends = run_bounds(words64)
    fmt = choose_format(cnt, len(starts))
    if fmt == bitops.FMT_RUN:
        runs = np.stack([starts, ends], axis=1)
        if offset:
            runs = runs + np.int32(offset)
        return Container(bitops.FMT_RUN, width32, cnt, runs=runs)
    if fmt == bitops.FMT_ARRAY:
        pos = (extract_positions(words64) + offset).astype(np.int32)
        return Container(bitops.FMT_ARRAY, width32, cnt, positions=pos)
    if dense_fn is not None:
        return dense_container(dense_fn(), width32, cnt)
    if dense_words is None:
        import jax.numpy as jnp

        dense_words = jnp.asarray(np.ascontiguousarray(
            words64, dtype=np.uint64).view(np.uint32))
    return Container(bitops.FMT_DENSE, width32, cnt, words=dense_words)


def dense_container(words32, width32, count):
    """Wrap an existing dense device row (count from the storage
    tier's row stats) — the formats-off path and the dense fallback."""
    return Container(bitops.FMT_DENSE, width32, count, words=words32)


def as_container(x, need_count=True):
    """Normalize any operand to a Container. Raw dense arrays (no
    ``fmt``) wrap with a device popcount for the cardinality the
    or/xor/andnot count identities need — mixed raw×compressed pairs
    reach the registered cells through bitmap algebra (a
    from_host_words segment against a fragment-served container).
    ``need_count=False`` (the ``and`` cell, which never reads it)
    skips that kernel."""
    if isinstance(x, Container):
        return x
    cnt = int(bitops.count(x)) if need_count else 0
    return Container(bitops.FMT_DENSE, int(x.shape[-1]), cnt, words=x)


def empty_container(width32):
    return Container(bitops.FMT_ARRAY, width32, 0,
                     positions=np.zeros(0, np.int32))


def _pad_pow2(n, floor=16):
    p = floor
    while p < n:
        p *= 2
    return p


def pad_positions(positions, limit, sentinel_off=0):
    """Positions padded to a power-of-two bucket with the sentinel
    ``limit + sentinel_off`` (sorted order preserved: every valid
    position < limit). Distinct offsets per operand side keep operand
    sentinels from ever comparing equal in merge kernels."""
    n = len(positions)
    out = np.full(_pad_pow2(max(n, 1)), limit + sentinel_off,
                  dtype=np.int32)
    out[:n] = positions
    return out


def pad_runs(runs, limit):
    """(starts, ends) padded to a power-of-two bucket with empty
    [limit, limit) runs — sorted after every real start (real run
    bounds are < limit), and a range_mask of an empty range is
    all-zero, so padding contributes nothing to any kernel."""
    n = len(runs)
    p = _pad_pow2(max(n, 1))
    starts = np.full(p, limit, dtype=np.int32)
    ends = np.full(p, limit, dtype=np.int32)
    if n:
        starts[:n] = runs[:, 0]
        ends[:n] = runs[:, 1]
    return starts, ends


# ------------------------------------------------------- device kernels
# All jitted module-level so shape-bucketed executables are shared
# process-wide, like the dense kernels in bitops.

def _jit(fn):
    import jax

    return jax.jit(fn)


_kernel_cache = {}


def _jitted(name, builder):
    fn = _kernel_cache.get(name)
    if fn is None:
        fn = _kernel_cache[name] = _jit(builder())
        fn.__name__ = name
    return fn


# Serial-cell observation stride: the per-slice compressed count path
# dispatches one cell PER SLICE, so exact per-call bookkeeping there
# would cost two clock readings and a locked note a slice. 1-in-N
# calls record with weight N (the statsd |@rate idiom — counts/sums scale,
# means stay unbiased); the deterministic tick guarantees a sample
# every N dispatches. Fused LANE cells stay exactly instrumented —
# they launch once per tick, not per slice.
OBS_STRIDE = 16
_obs_tick = 0


def _obs_weight():
    """0 = skip this call's observation; else the weight to scale
    by. Racy GIL-atomic tick (the _co_stats discipline). The serial
    cells keep their own closure ticks (a nonlocal increment beats a
    global-function call on the per-slice path); this module-level
    twin serves any future cell that has no closure to hang one on."""
    global _obs_tick
    _obs_tick += 1
    if _obs_tick % OBS_STRIDE:
        return 0
    return OBS_STRIDE


def _count_array_dense_impl():
    import jax.numpy as jnp

    def fn(pos, words):
        w = words[jnp.clip(pos >> 5, 0, words.shape[0] - 1)]
        bit = (w >> (pos & 31).astype(jnp.uint32)) & jnp.uint32(1)
        valid = pos < words.shape[0] * 32
        return jnp.sum(jnp.where(valid, bit, jnp.uint32(0))
                       .astype(jnp.int32))
    return fn


def count_array_dense(pos, words):
    """|array ∩ dense| via gather + bit-test: one gathered word per
    position, no dense intermediate (ref: intersectArrayBitmap count
    shape, roaring.go:1862-1878)."""
    return _jitted("count_array_dense", _count_array_dense_impl)(
        pos, words)


def _count_array_array_impl():
    import jax.numpy as jnp

    def fn(pos_a, pos_b):
        idx = jnp.clip(jnp.searchsorted(pos_b, pos_a), 0,
                       pos_b.shape[0] - 1)
        return jnp.sum((pos_b[idx] == pos_a).astype(jnp.int32))
    return fn


def count_array_array(pos_a, pos_b):
    """|array ∩ array| as a sorted-merge membership test (searchsorted
    — the vectorized analog of intersectArrayArray's galloping merge,
    roaring.go:1811-1830). Operand sentinels differ by construction
    (pad_positions offsets), so padding can never match."""
    return _jitted("count_array_array", _count_array_array_impl)(
        pos_a, pos_b)


def _count_array_run_impl():
    import jax.numpy as jnp

    def fn(pos, starts, ends):
        idx = jnp.clip(
            jnp.searchsorted(starts, pos, side="right") - 1,
            0, starts.shape[0] - 1)
        inside = (pos >= starts[idx]) & (pos < ends[idx])
        return jnp.sum(inside.astype(jnp.int32))
    return fn


def count_array_run(pos, starts, ends):
    """|array ∩ run|: position-in-interval membership (ref:
    intersectArrayRun, roaring.go:1832-1860). Sentinel positions sit
    at/past the window limit, where no run can cover them (run ends
    are ≤ limit)."""
    return _jitted("count_array_run", _count_array_run_impl)(
        pos, starts, ends)


def _run_mask_impl():
    import jax.numpy as jnp

    def fn(starts, ends, n_words):
        # Membership by sorted boundary search, the count_array_run
        # shape applied to EVERY bit position, then packed 32 bits to
        # a word: O(width) temporaries (~a few MB at full slice
        # width). Vmapping range_mask per run instead materializes a
        # [n_runs_pad, n_words] stack — ~277 MB of XLA temp at the
        # 2048-run cap, dwarfing the payloads this tier serves.
        pos = jnp.arange(n_words * 32, dtype=jnp.int32)
        idx = jnp.clip(jnp.searchsorted(starts, pos, side="right") - 1,
                       0, starts.shape[0] - 1)
        inside = (pos >= starts[idx]) & (pos < ends[idx])
        bits = inside.reshape(n_words, 32).astype(jnp.uint32)
        weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
        return (bits * weights).sum(axis=1, dtype=jnp.uint32)
    return fn


def run_mask(starts, ends, n_words):
    """uint32[n_words] mask covering every run — disjoint sorted
    runs, so per-position membership is one boundary bisect (padding
    runs are empty [limit, limit): no position lands inside)."""
    import jax

    fn = _kernel_cache.get("run_mask")
    if fn is None:
        fn = _kernel_cache["run_mask"] = jax.jit(
            _run_mask_impl(), static_argnums=2)
    return fn(starts, ends, n_words)


def _count_run_dense_impl():
    import jax.numpy as jnp
    from jax import lax

    def fn(starts, ends, words):
        mask = _run_mask_impl()(starts, ends, words.shape[0])
        return jnp.sum(lax.population_count(
            lax.bitwise_and(words, mask)).astype(jnp.int32))
    return fn


def count_run_dense(starts, ends, words):
    """|run ∩ dense| fused: run mask → AND → popcount in one XLA
    program (the count analog of intersectBitmapRun,
    roaring.go:1880-1904) — nothing dense is ever materialized in HBM
    beyond what fusion keeps in registers."""
    return _jitted("count_run_dense", _count_run_dense_impl)(
        starts, ends, words)


def count_run_run(runs_a, runs_b):
    """|run ∩ run| host-side: two sorted disjoint interval lists
    overlap via prefix sums + two searchsorted passes — zero device
    work (run lists are ≤ RUN_MAX_RUNS ints; ref: intersectRunRun
    roaring.go:1906-1923). For a-run [s, e), the overlapping b-runs
    are a contiguous window [lo, hi); only its first run can stick out
    left of s and only its last can stick out right of e (the runs
    between are pinned inside by sortedness + disjointness), so the
    overlap is the window's summed length minus the two edge clips."""
    if not len(runs_a) or not len(runs_b):
        return 0
    a_s = runs_a[:, 0].astype(np.int64)
    a_e = runs_a[:, 1].astype(np.int64)
    b_s = runs_b[:, 0].astype(np.int64)
    b_e = runs_b[:, 1].astype(np.int64)
    pref = np.concatenate(([0], np.cumsum(b_e - b_s)))
    lo = np.searchsorted(b_e, a_s, side="right")
    hi = np.searchsorted(b_s, a_e, side="left")
    has = lo < hi
    if not has.any():
        return 0
    lo_h, hi_h = lo[has], hi[has]
    inner = pref[hi_h] - pref[lo_h]
    inner -= np.maximum(0, a_s[has] - b_s[lo_h])
    inner -= np.maximum(0, b_e[hi_h - 1] - a_e[has])
    return int(inner.sum())


# ------------------------------------------------------- fused lanes
# Query-axis kernels for the cross-query micro-batching tier
# (executor._co_fuse_lanes): the coalescer buckets concurrent counts'
# (query, slice) member pairs by format cell, stacks each side's
# payloads into ONE padded lane, and a vmapped twin of the serial
# kernel above serves the whole lane in a single device launch.
# Lane shapes bucket to powers of two (positions/runs per member AND
# members per lane) so jit executables stay bounded, and padding uses
# the same out-of-window sentinels as the serial cells — filler can
# never intersect anything.

def stack_positions(conts, sentinel_off=0):
    """``int32[N, P]`` position lane for N same-width ARRAY containers:
    every member padded to the shared pow2 bucket ``P`` with the
    sentinel ``limit + sentinel_off`` (the pad_positions rule, so
    operand sides keep distinct sentinels)."""
    import jax.numpy as jnp

    limit = conts[0].width32 * 32
    p = _pad_pow2(max(max(c.count for c in conts), 1))
    out = np.full((len(conts), p), limit + sentinel_off, dtype=np.int32)
    for i, c in enumerate(conts):
        out[i, : len(c.positions)] = c.positions
    return jnp.asarray(out)


def stack_runs(conts):
    """``(int32[N, R] starts, int32[N, R] ends)`` run lanes for N RUN
    containers, padded to the shared pow2 bucket with empty
    ``[limit, limit)`` runs (sorted past every real start, mask-zero —
    the pad_runs rule)."""
    import jax.numpy as jnp

    limit = conts[0].width32 * 32
    r = _pad_pow2(max(max(len(c.runs) for c in conts), 1))
    starts = np.full((len(conts), r), limit, dtype=np.int32)
    ends = np.full((len(conts), r), limit, dtype=np.int32)
    for i, c in enumerate(conts):
        n = len(c.runs)
        if n:
            starts[i, :n] = c.runs[:, 0]
            ends[i, :n] = c.runs[:, 1]
    return jnp.asarray(starts), jnp.asarray(ends)


def stack_dense(conts):
    """``uint32[N, W]`` word lane for N DENSE containers (their words
    are already device-resident mirrors; the stack is an on-device
    op). Callers budget this — it is the one lane whose bytes scale
    with the window, which is why the executor chunks dense cells."""
    import jax.numpy as jnp

    return jnp.stack([c.dense_words() for c in conts])


def fused_lane_bytes(fmt_a, fmt_b, width32):
    """HBM bytes ONE lane member costs at ``width32`` — the executor's
    per-chunk budget unit. Position/run payloads are KBs and don't
    meaningfully bound chunking; dense word rows dominate."""
    per = 0
    if fmt_a == bitops.FMT_DENSE:
        per += width32 * 4
    if fmt_b == bitops.FMT_DENSE:
        per += width32 * 4
    return per


def _vmapped(name, impl_builder):
    """jit(vmap(serial kernel body)) — the fused kernels share their
    math with the serial cells by construction, so the two can never
    diverge."""
    import jax

    def build():
        return jax.vmap(impl_builder())
    fn = _kernel_cache.get(name)
    if fn is None:
        fn = _kernel_cache[name] = _jit(build())
        fn.__name__ = name
    return fn


def fused_count_array_array(pos_a, pos_b):
    """Per-member |array ∩ array| over ``int32[N, Pa]`` × ``int32[N,
    Pb]`` lanes (the count_array_array searchsorted merge vmapped over
    the member axis)."""
    return _vmapped("fused_count_array_array", _count_array_array_impl)(
        pos_a, pos_b)


def fused_count_array_dense(pos, words):
    return _vmapped("fused_count_array_dense", _count_array_dense_impl)(
        pos, words)


def fused_count_array_run(pos, starts, ends):
    return _vmapped("fused_count_array_run", _count_array_run_impl)(
        pos, starts, ends)


def fused_count_run_dense(starts, ends, words):
    return _vmapped("fused_count_run_dense", _count_run_dense_impl)(
        starts, ends, words)


def _fused_count_dense_dense_impl():
    import jax.numpy as jnp
    from jax import lax

    def fn(a, b):
        return jnp.sum(lax.population_count(
            lax.bitwise_and(a, b)).astype(jnp.int32))
    return fn


def fused_count_dense_dense(a, b):
    """Per-member |dense ∩ dense| over ``uint32[N, W]`` lanes — the
    lane-tier dense cell (full-width compressed-tier rows); the
    single-query dense stacks keep their own pre-existing kernels."""
    return _vmapped("fused_count_dense_dense",
                    _fused_count_dense_dense_impl)(a, b)


# CPU-backend lane dispatch (the ops/ingest.py precedent): XLA's
# scan-based searchsorted is O(haystack) PER LOOKUP — fine on a
# vector unit, quadratic-feeling on one host core (measured ~8 ms per
# [640, 512] lane where the serial path's N=1 call is ~40 µs). The
# position/interval lanes therefore run a bit-identical vectorized
# numpy pass on the CPU backend: members concatenate at DISJOINT
# offsets (one ``span`` per member) so a SINGLE C searchsorted serves
# the whole lane, per-member sums fold back via bincount. Dense-word
# lanes stay on the device everywhere — AND+popcount is what XLA-CPU
# is already good at.
_LANE_HOST = None


def _lane_host():
    global _LANE_HOST
    if _LANE_HOST is None:
        import jax

        _LANE_HOST = jax.default_backend() == "cpu"
    return _LANE_HOST


def lane_host_mode():
    """Public probe for the executor: True on the CPU backend, where
    the coalescer's compressed lanes run the vectorized host pass
    (whole-row representations) instead of device lane kernels."""
    return _lane_host()


def _cat_offset(arrays, offs):
    """Concatenate per-member int arrays rebased to disjoint spans."""
    if not arrays:
        return np.zeros(0, np.int64)
    return np.concatenate([a.astype(np.int64) + off
                           for a, off in zip(arrays, offs)])


# The TWO membership idioms every host lane reduces to, shared by the
# per-member cells and the whole-row pair passes so the subtle guards
# (index clipping, the half-open interval test, cross-member safety)
# live in exactly one place each. All inputs are already rebased to
# DISJOINT per-member spans: a previous member's values/intervals end
# below this member's span, so no cross-member hits are possible.

def _pos_hits(pa, pb):
    """Boolean mask over sorted ``pa``: which values appear in sorted
    ``pb`` (one C searchsorted, merge semantics)."""
    if not len(pa) or not len(pb):
        return np.zeros(len(pa), bool)
    idx = np.searchsorted(pb, pa)
    idx_c = np.minimum(idx, len(pb) - 1)
    return (idx < len(pb)) & (pb[idx_c] == pa)


def _interval_hits(pos, starts, ends):
    """Boolean mask over sorted ``pos``: which values fall inside the
    sorted disjoint half-open [starts, ends) intervals.
    ``starts[idx] <= pos`` holds by construction of side="right"."""
    if not len(pos) or not len(starts):
        return np.zeros(len(pos), bool)
    idx = np.searchsorted(starts, pos, side="right") - 1
    ok = idx >= 0
    return ok & (pos < ends[np.maximum(idx, 0)])


def _host_count_array_array(conts_a, conts_b):
    n = len(conts_a)
    span = conts_a[0].width32 * 32 + 1
    offs = np.arange(n, dtype=np.int64) * span
    pa = _cat_offset([c.positions for c in conts_a], offs)
    pb = _cat_offset([c.positions for c in conts_b], offs)
    mid = np.repeat(np.arange(n), [c.count for c in conts_a])
    return np.bincount(mid[_pos_hits(pa, pb)],
                       minlength=n).astype(np.int64)


def _host_count_array_run(conts_a, conts_b):
    n = len(conts_a)
    span = conts_a[0].width32 * 32 + 1
    offs = np.arange(n, dtype=np.int64) * span
    pa = _cat_offset([c.positions for c in conts_a], offs)
    starts = _cat_offset([c.runs[:, 0] for c in conts_b], offs)
    ends = _cat_offset([c.runs[:, 1] for c in conts_b], offs)
    mid = np.repeat(np.arange(n), [c.count for c in conts_a])
    return np.bincount(mid[_interval_hits(pa, starts, ends)],
                       minlength=n).astype(np.int64)


def _host_count_array_dense(conts_a, conts_b):
    out = np.zeros(len(conts_a), np.int64)
    for i, (a, b) in enumerate(zip(conts_a, conts_b)):
        if not a.count:
            continue
        words = np.asarray(b.dense_words())  # zero-copy on CPU
        p = a.positions.astype(np.int64)
        bits = (words[p >> 5] >> (p & 31).astype(np.uint32)) \
            & np.uint32(1)
        out[i] = int(bits.sum())
    return out


# Whole-row host representations: on the CPU backend the coalescer
# collapses a row's per-slice ARRAY/RUN containers into ONE
# global-column (positions, runs) pair (cached executor-side against
# fragment tokens), so a fused group's intersections reduce to a few
# vectorized C passes over concatenated pair lanes instead of
# K×S per-slice members.

def host_row_repr(parts_pos, parts_runs):
    """(positions int64 sorted, runs int64[N,2], count) from a row's
    per-slice container parts already rebased to global columns."""
    pos = (np.concatenate(parts_pos) if parts_pos
           else np.zeros(0, np.int64))
    runs = (np.concatenate(parts_runs) if parts_runs
            else np.zeros((0, 2), np.int64))
    count = int(len(pos) + (runs[:, 1] - runs[:, 0]).sum())
    return pos, runs, count


def host_repr_and_counts(reprs_a, reprs_b, span):
    """``np.int64[n_pairs]`` of |A ∩ B| for whole-row representations.
    Rows decompose into disjoint position and run parts, so the
    intersection is the sum of four exact components — pos∩pos
    (merge via one C searchsorted over pair-offset lanes), pos∈runs
    both ways (interval membership, same trick), and run∩run (the
    host prefix-sum overlap, per pair). ``span`` must exceed every
    global position so pair lanes cannot collide."""
    n = len(reprs_a)
    offs = np.arange(n, dtype=np.int64) * span
    total = np.zeros(n, np.int64)

    def cat_pos(reprs):
        parts = [r[0] + offs[i] for i, r in enumerate(reprs)
                 if len(r[0])]
        mids = np.repeat(np.arange(n), [len(r[0]) for r in reprs])
        return (np.concatenate(parts) if parts
                else np.zeros(0, np.int64)), mids

    def cat_runs(reprs):
        s = [r[1][:, 0] + offs[i] for i, r in enumerate(reprs)
             if len(r[1])]
        e = [r[1][:, 1] + offs[i] for i, r in enumerate(reprs)
             if len(r[1])]
        if not s:
            z = np.zeros(0, np.int64)
            return z, z
        return np.concatenate(s), np.concatenate(e)

    pa, mid_a = cat_pos(reprs_a)
    pb, mid_b = cat_pos(reprs_b)
    sa, ea = cat_runs(reprs_a)
    sb, eb = cat_runs(reprs_b)
    if len(pa) and len(pb):
        total += np.bincount(mid_a[_pos_hits(pa, pb)], minlength=n)
    for pos, mid, starts, ends in ((pa, mid_a, sb, eb),
                                   (pb, mid_b, sa, ea)):
        hits = _interval_hits(pos, starts, ends)
        if len(hits):
            total += np.bincount(mid[hits], minlength=n)
    for i in range(n):
        ra, rb = reprs_a[i][1], reprs_b[i][1]
        if len(ra) and len(rb):
            total[i] += count_run_run(ra, rb)
    return total


def _fused_and_counts(conts_a, conts_b):
    """``np.int64[N]`` of per-member |a ∩ b| for two same-format
    operand lists — one lane launch on accelerators, the vectorized
    host pass for position/interval lanes on the CPU backend (run×run
    stays host-side everywhere: prefix sums over ≤2·RUN_MAX_RUNS ints
    per member beat any transfer)."""
    fa, fb = conts_a[0].fmt, conts_b[0].fmt
    A, R, D = bitops.FMT_ARRAY, bitops.FMT_RUN, bitops.FMT_DENSE
    if fa == D and fb != D:
        return _fused_and_counts(conts_b, conts_a)
    if fa == R and fb == A:
        return _fused_and_counts(conts_b, conts_a)
    if fa == A and fb == A:
        if _lane_host():
            return _host_count_array_array(conts_a, conts_b)
        out = fused_count_array_array(
            stack_positions(conts_a),
            stack_positions(conts_b, sentinel_off=1))
    elif fa == A and fb == D:
        if _lane_host():
            return _host_count_array_dense(conts_a, conts_b)
        out = fused_count_array_dense(stack_positions(conts_a),
                                      stack_dense(conts_b))
    elif fa == A and fb == R:
        if _lane_host():
            return _host_count_array_run(conts_a, conts_b)
        s, e = stack_runs(conts_b)
        out = fused_count_array_run(stack_positions(conts_a), s, e)
    elif fa == R and fb == D:
        s, e = stack_runs(conts_a)
        out = fused_count_run_dense(s, e, stack_dense(conts_b))
    elif fa == R and fb == R:
        return np.array([count_run_run(a.runs, b.runs)
                         for a, b in zip(conts_a, conts_b)],
                        dtype=np.int64)
    elif fa == D and fb == D:
        out = fused_count_dense_dense(stack_dense(conts_a),
                                      stack_dense(conts_b))
    else:
        raise TypeError(f"no fused and-count lane for {fa}x{fb}")
    return np.asarray(out).astype(np.int64)


def _fused_count_cell(op):
    """One (op, fmt, fmt) lane cell: intersection counts from ONE
    launch, then the same or/xor/andnot identities as the serial
    _count_cell applied per member from the host-known cardinalities
    (exact for two operands) — so fused and serial can only agree."""
    def cell(conts_a, conts_b):
        obs = _kt.ACTIVE
        if not obs.enabled:
            inter = _fused_and_counts(conts_a, conts_b)
        else:
            # Fused-lane attribution: one note per lane launch, cell
            # = the member format pair, bucket = the member-count
            # class (the lane tier's cost axis). np.asarray in
            # _fused_and_counts blocks, so samples are device time.
            # Compile separation is the first-sample-of-cell rule
            # (note's compiled=None): a lane cell's first launch at a
            # member-count bucket IS where its vmapped kernel
            # compiles, and a jit-cache walk per launch would tax
            # every tick.
            t0 = time.perf_counter()
            inter = _fused_and_counts(conts_a, conts_b)
            obs.note(f"fused_count_{op}",
                     f"{conts_a[0].fmt}*{conts_b[0].fmt}",
                     _kt.lane_bucket(len(conts_a)),
                     time.perf_counter() - t0,
                     compiled=None, device=True)
        if op == "and":
            return inter
        ca = np.array([c.count for c in conts_a], dtype=np.int64)
        cb = np.array([c.count for c in conts_b], dtype=np.int64)
        if op == "or":
            return ca + cb - inter
        if op == "xor":
            return ca + cb - 2 * inter
        return ca - inter  # andnot
    return cell


def _array_to_dense(pos, width32):
    """Scatter sorted positions into dense words. Positions are
    distinct, so per-word mask ADDs equal ORs (no carry)."""
    def build():
        import jax.numpy as jnp

        def fn(pos, zeros):
            valid = pos < zeros.shape[0] * 32
            word = jnp.where(valid, pos >> 5, 0)
            mask = jnp.where(
                valid, jnp.uint32(1) << (pos & 31).astype(jnp.uint32),
                jnp.uint32(0))
            return zeros.at[word].add(mask)
        return fn

    import jax.numpy as jnp

    return _jitted("array_to_dense", build)(
        pos, jnp.zeros(width32, jnp.uint32))


def _runs_to_dense(starts, ends, width32):
    return run_mask(starts, ends, width32)


# -------------------------------------------------- dispatch registry
# Count cells for every compressed pair. or/xor/andnot derive from
# |a∩b| and the (host-known) cardinalities — exact for two operands —
# so one intersection kernel per pair covers the whole op row; the
# registration below writes all four ops per pair into bitops's table.
# Dense×dense is NOT registered: bitops routes it to the pre-existing
# fused kernels unconditionally (the exact current path).

def _and_count(a, b):
    fa, fb = a.fmt, b.fmt
    A, R, D = bitops.FMT_ARRAY, bitops.FMT_RUN, bitops.FMT_DENSE
    if fa == A and fb == A:
        return int(count_array_array(a.device_positions(),
                                     b.device_positions(sentinel_off=1)))
    if fa == A and fb == D:
        return int(count_array_dense(a.device_positions(),
                                     b.dense_words()))
    if fa == D and fb == A:
        return _and_count(b, a)
    if fa == A and fb == R:
        s, e = b.device_runs()
        return int(count_array_run(a.device_positions(), s, e))
    if fa == R and fb == A:
        return _and_count(b, a)
    if fa == R and fb == D:
        s, e = a.device_runs()
        return int(count_run_dense(s, e, b.dense_words()))
    if fa == D and fb == R:
        return _and_count(b, a)
    if fa == R and fb == R:
        return count_run_run(a.runs, b.runs)
    raise TypeError(f"no and-count cell for {fa}x{fb}")


def _count_cell(op):
    tick = 0

    def cell(a, b):
        need = op != "and"  # |a∩b| alone needs no cardinalities
        a, b = as_container(a, need), as_container(b, need)
        obs = _kt.ACTIVE
        w = 0
        if obs.enabled:
            nonlocal tick
            tick += 1
            if tick % OBS_STRIDE == 0:
                w = OBS_STRIDE
        if not w:
            inter = _and_count(a, b)
        else:
            # Stride-sampled serial-cell attribution: these cells
            # coerce to a host int (the int() in _and_count blocks),
            # so every sample is device time. Compile attribution is
            # the first-sample-of-cell rule (note's compiled=None) —
            # exact jit-cache introspection here, once a slice, would
            # cost more than the cell it watches; the exact probes
            # live on the bitops and fused-lane paths.
            t0 = time.perf_counter()
            inter = _and_count(a, b)
            dt = time.perf_counter() - t0
            obs.note(f"count_{op}", f"{a.fmt}*{b.fmt}",
                     _kt.shape_bucket(a.nbytes() + b.nbytes()), dt,
                     compiled=None, device=True, n=w)
        if op == "and":
            return inter
        if op == "or":
            return a.count + b.count - inter
        if op == "xor":
            return a.count + b.count - 2 * inter
        return a.count - inter  # andnot
    return cell


def _register():
    fmts = (bitops.FMT_ARRAY, bitops.FMT_RUN, bitops.FMT_DENSE)
    for op in ("and", "or", "xor", "andnot"):
        cell = _count_cell(op)
        lane = _fused_count_cell(op)
        for fa in fmts:
            for fb in fmts:
                if fa != bitops.FMT_DENSE or fb != bitops.FMT_DENSE:
                    # dense×dense serial stays the pre-existing fused
                    # kernel path, untouched.
                    bitops.register_count_kernel(op, fa, fb, cell)
                # The LANE registry covers every pair, dense×dense
                # included — a compressed group's dense-format members
                # (full-width compressed-tier rows) batch too instead
                # of falling back to per-member dispatches.
                bitops.register_fused_count_kernel(op, fa, fb, lane)


_register()
