"""Packed-bitmap algebra as fused XLA kernels.

The reference dispatches every binary bitmap op through a matrix of
container-specialized Go kernels (roaring/roaring.go:1811-3283:
``intersectArrayArray``, ``intersectBitmapRun``, ``unionBitmapBitmap``,
``differenceRunArray``, ``xorBitmapBitmap``, ... ~30 kernels) plus
count-only fast paths (``intersectionCount*`` :1811-1923) built on
software popcount loops (``popcountAndSlice`` etc. :3242-3283).

On TPU all of that collapses: a bitmap row is a dense ``uint32[n_words]``
vector in HBM, binary ops are single fused ``lax.bitwise_*`` kernels on
the VPU, and counts are ``lax.population_count`` + reduce — XLA fuses the
bitwise op into the popcount so count-only queries never materialize the
intermediate bitmap (the analog of the reference's count fast paths).

Conventions
-----------
- dtype is always ``jnp.uint32``: TPUs have no native 64-bit integer
  datapath, and 2^20 bits = 32768 uint32 words = a clean (256, 128) tile.
- Kernels are shape-polymorphic pure functions; ``jax.jit`` caches one
  executable per shape. Fragment shapes are bucketed (powers of two) by
  the storage layer so recompilation is bounded.
- Counts are returned as ``int32``. A single slice holds ≤ 2^20 bits so
  any per-row / per-slice count fits; cross-slice totals are summed on
  the host in Python ints (arbitrary precision) or via float64.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu import lockcheck, querystats, tracing
from pilosa_tpu import stats as stats_mod
from pilosa_tpu.observe import devprof as _devprof
from pilosa_tpu.observe import kerneltime as _kt

_U32 = jnp.uint32
# NumPy scalar, NOT jnp: a module-level jnp constant would initialize
# the XLA backend at import time, which breaks multi-host startup
# (jax.distributed.initialize must run before the first device op).
_FULL = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Binary algebra (materializing). Ref semantics: roaring.go Intersect :1925,
# Union :2123, Difference :2415, Xor :2732 — here each is one VPU kernel.
# ---------------------------------------------------------------------------

@jax.jit
def bitmap_and(a, b):
    return lax.bitwise_and(a, b)


@jax.jit
def bitmap_or(a, b):
    return lax.bitwise_or(a, b)


@jax.jit
def bitmap_xor(a, b):
    return lax.bitwise_xor(a, b)


@jax.jit
def bitmap_andnot(a, b):
    """a \\ b (ref: Difference, roaring.go:2415)."""
    return lax.bitwise_and(a, lax.bitwise_not(b))


# ---------------------------------------------------------------------------
# N-ary reductions over stacked rows: uint32[k, n_words] -> uint32[n_words].
# Used by Union/Intersect/Xor over >2 children and by time-quantum view
# merging (executor.go:665-675).
# ---------------------------------------------------------------------------

@jax.jit
def union_reduce(rows):
    return lax.reduce(rows, _U32(0), lax.bitwise_or, (0,))


@jax.jit
def intersect_reduce(rows):
    return lax.reduce(rows, _FULL, lax.bitwise_and, (0,))


@jax.jit
def xor_reduce(rows):
    return lax.reduce(rows, _U32(0), lax.bitwise_xor, (0,))


# ---------------------------------------------------------------------------
# Population counts. Ref: popcount* roaring.go:3242-3283 and the
# count-only fast paths :1811-1923.
# ---------------------------------------------------------------------------

def _popcount_sum(x):
    return jnp.sum(lax.population_count(x).astype(jnp.int32))


# Per-kernel dispatch-time histogram (stats.Histogram), wired by the
# server when [metrics] histograms are on; the module default is the
# shared nop so bare kernel use (tests, benchmarks) pays one attribute
# read. Dispatch time is ENQUEUE wall time — the histogram never calls
# block_until_ready, so async dispatch pipelining is unchanged (the
# traced path below still blocks, as spans must measure device time).
_DISPATCH_HIST = stats_mod.NOP_HISTOGRAM
_HIST_KERNELS = {}

# Steady-state observatory note stride for untraced dispatches
# (compile/device-sampled dispatches always record; racy GIL-atomic
# tick — the containers.OBS_STRIDE discipline).
OBS_STRIDE = 8
_obs_tick = 0


def set_dispatch_histogram(hist):
    """Install the ``kernel_dispatch_seconds`` family (or None/nop to
    disable). Pre-tagged per-kernel children are memoized — with_tags
    per dispatch would take the family lock on every kernel call.

    PROCESS-GLOBAL, like the kernels themselves: when several servers
    share one process (in-process test clusters), the last-installed
    set records every node's dispatches — kernel attribution is
    per-process, not per-node, in that topology. Real deployments run
    one server per process, where the two coincide."""
    global _DISPATCH_HIST, _HIST_KERNELS
    _DISPATCH_HIST = hist or stats_mod.NOP_HISTOGRAM
    _HIST_KERNELS = {}


def _kernel_hist(name):
    child = _HIST_KERNELS.get(name)
    if child is None:
        child = _HIST_KERNELS[name] = _DISPATCH_HIST.with_tags(
            f"kernel:{name}")
    return child


# Operand counts past this share one program name: names stay few.
PROGRAM_NAME_MAX_OPERANDS = 16


def program_name(tier, operands):
    """The name a jitted program of the served path carries in a device
    trace (``jit_<name>`` on the ``XLA Modules`` line): the tier that
    built it and how many operand stacks it reads, as
    ``pilosa_count_batched_k3``. No hash and no row id, so a trace's
    gaps and device times can be put to a query shape by name."""
    k = (f"k{operands}" if operands <= PROGRAM_NAME_MAX_OPERANDS
         else f"k{PROGRAM_NAME_MAX_OPERANDS}p")
    return f"pilosa_{tier}_{k}"


def _traced_dispatch(name, fn, *args):
    """Dispatch a jitted kernel under the active trace span; a plain
    call when no trace is active (one attribute read of overhead).
    Traced dispatches block until the result is ready — the span must
    measure device time, not async-enqueue time — and tag whether this
    call paid an XLA compile (jit cache growth) or hit steady state."""
    if lockcheck.ACTIVE.enabled:
        # A lock held across a kernel dispatch/device sync serializes
        # every thread behind HBM round-trip latency (and behind an
        # XLA compile on the first shape). Locks that by design cover
        # their own device mirrors register allow_across_io=True.
        lockcheck.ACTIVE.io_point("device.dispatch", kind="device")
    qs = querystats.active()
    if qs is not None and name.startswith("count"):
        # bytes-popcounted is the kernel cost unit (arXiv:1611.07612):
        # charge the primary operand's footprint per popcount dispatch.
        nb = getattr(args[0], "nbytes", 0)
        if nb:
            qs.add("bytesPopcounted", int(nb))
    obs = _kt.ACTIVE
    if tracing.active_span() is None:
        h = _DISPATCH_HIST
        if not h.enabled and not obs.enabled:
            return fn(*args)
        if not obs.enabled:
            t0 = time.perf_counter()
            out = fn(*args)
            _kernel_hist(name).observe(time.perf_counter() - t0)
            return out
        # Workload-observatory path (observe/kerneltime.py): the
        # tracing-only first_compile probe promoted to always-on
        # counters — jit cache growth marks this dispatch's time as
        # COMPILE; 1-in-N sampled dispatches additionally block so
        # true device time is measured without stalling the other
        # N-1 calls' async pipelining. Every dispatch pays ONE
        # post-call cache-size probe (the note_jit_cache delta is the
        # compile detector — exact, per kernel); STEADY notes are
        # stride-sampled with scaled weight so the per-slice serial
        # dense loop pays a locked note one dispatch in OBS_STRIDE,
        # while compile and device-sampled dispatches always record.
        sampled = obs.should_sample()
        t0 = time.perf_counter()
        out = fn(*args)
        # Enqueue time captured BEFORE any sampled block: the
        # pre-existing kernel_dispatch_seconds histogram keeps its
        # enqueue-time semantics on this path even when sampling
        # blocks 1-in-N dispatches for the observatory.
        enqueue_dt = time.perf_counter() - t0
        if sampled:
            try:
                out.block_until_ready()
            except AttributeError:
                pass  # abstract value: inside another jit trace
        dt = time.perf_counter() - t0
        compiled = False
        try:
            compiled = obs.note_jit_cache(name, fn._cache_size())
        except Exception:  # noqa: BLE001 — jit internals vary; pilint: disable=swallow
            pass  # jit cache introspection is best-effort
        global _obs_tick
        _obs_tick += 1
        if compiled or sampled:
            bucket = _kt.shape_bucket(getattr(args[0], "nbytes", 0))
            obs.note(name, FMT_DENSE, bucket, dt, compiled=compiled,
                     device=sampled)
            if compiled and _devprof.ACTIVE.enabled:
                # This dispatch already paid the XLA compile — the
                # analytic flops/bytes capture (one extra lowering,
                # once per cell) rides it, never steady state.
                _devprof.ACTIVE.note_compile(name, FMT_DENSE, bucket,
                                             fn, args)
        elif _obs_tick % OBS_STRIDE == 0:
            obs.note(name, FMT_DENSE,
                     _kt.shape_bucket(getattr(args[0], "nbytes", 0)),
                     dt, n=OBS_STRIDE)
        if h.enabled:
            _kernel_hist(name).observe(enqueue_dt)
        return out
    try:
        pre = fn._cache_size()
    except Exception:  # noqa: BLE001 — jit internals vary by version; pilint: disable=swallow
        pre = None
    t0 = time.perf_counter()
    compiled = False
    with tracing.span(f"kernel:{name}") as sp:
        out = fn(*args)
        try:
            out.block_until_ready()
        except AttributeError:
            pass  # abstract value: dispatched inside another jit trace
        if pre is not None:
            try:
                post = fn._cache_size()
                compiled = post > pre
                sp.tag(first_compile=compiled)
                if obs.enabled:
                    obs.note_jit_cache(name, post)
            except Exception:  # noqa: BLE001; pilint: disable=swallow
                pass  # jit cache introspection is best-effort
    dt = time.perf_counter() - t0
    if obs.enabled:
        # Traced dispatches block, so this sample IS device time.
        bucket = _kt.shape_bucket(getattr(args[0], "nbytes", 0))
        obs.note(name, FMT_DENSE, bucket, dt,
                 compiled=compiled, device=True)
        if compiled and _devprof.ACTIVE.enabled:
            _devprof.ACTIVE.note_compile(name, FMT_DENSE, bucket,
                                         fn, args)
    if _DISPATCH_HIST.enabled:
        # Traced dispatches block, so this sample is device time — a
        # superset of the untraced enqueue time, but losing kernel
        # samples whenever tracing is on would be worse.
        _kernel_hist(name).observe(dt)
    return out


@jax.jit
def _count_impl(a):
    return _popcount_sum(a)


def count(a):
    """Total set bits. Ref: Bitmap.Count (roaring.go:185)."""
    return _traced_dispatch("count", _count_impl, a)


@jax.jit
def _count_rows_impl(m):
    return jnp.sum(lax.population_count(m).astype(jnp.int32), axis=-1)


def count_rows(m):
    """Per-row set bits over the trailing axis: uint32[..., W] -> int32[...].

    The workhorse of TopN (fragment.go:831) and cache recalculation —
    one fused popcount+reduce over the whole row matrix.
    """
    return _traced_dispatch("count_rows", _count_rows_impl, m)


@jax.jit
def _count_and_impl(a, b):
    return _popcount_sum(lax.bitwise_and(a, b))


def count_and(a, b):
    """|a ∩ b| without materializing. Ref: intersectionCount* :1811-1923."""
    return _traced_dispatch("count_and", _count_and_impl, a, b)


@jax.jit
def _count_or_impl(a, b):
    return _popcount_sum(lax.bitwise_or(a, b))


def count_or(a, b):
    return _traced_dispatch("count_or", _count_or_impl, a, b)


@jax.jit
def _count_xor_impl(a, b):
    return _popcount_sum(lax.bitwise_xor(a, b))


def count_xor(a, b):
    return _traced_dispatch("count_xor", _count_xor_impl, a, b)


@jax.jit
def _count_andnot_impl(a, b):
    return _popcount_sum(lax.bitwise_and(a, lax.bitwise_not(b)))


def count_andnot(a, b):
    return _traced_dispatch("count_andnot", _count_andnot_impl, a, b)


@jax.jit
def _count_and_rows_impl(m, filt):
    return jnp.sum(
        lax.population_count(lax.bitwise_and(m, filt[None, :])).astype(jnp.int32),
        axis=-1,
    )


def count_and_rows(m, filt):
    """Per-row intersection counts vs one filter row:
    uint32[R, W], uint32[W] -> int32[R]. TopN's Src-intersection path
    (fragment.go:886-906) as a single broadcasted kernel.
    """
    return _traced_dispatch("count_and_rows", _count_and_rows_impl, m, filt)


def row_at(m, i):
    """Row ``i`` of ``m``, read inside the program that scans ``m``:
    ``i`` is a traced int32 scalar, so one executable serves every
    row. Where a scan's filter is a row of the matrix it scans (TopN's
    probe, a ``Bitmap`` of the same fragment) this is the whole of its
    staging: no device slice, no trip to the host and back."""
    return lax.dynamic_index_in_dim(m, i, axis=0, keepdims=False)


@jax.jit
def _count_and_rows_at_impl(m, i):
    return _count_and_rows_impl(m, row_at(m, i))


def count_and_rows_at(m, i):
    """``count_and_rows`` with row ``i`` of ``m`` itself as the filter:
    uint32[R, W], int32 scalar -> int32[R]."""
    return _traced_dispatch("count_and_rows_at", _count_and_rows_at_impl,
                            m, i)


# ---------------------------------------------------------------------------
# Bit-range masking. Ref: CountRange (roaring.go:214-285) walks containers;
# here a mask vector is built from iota and fused into the popcount.
# start/end are traced scalars so one executable serves all ranges.
# ---------------------------------------------------------------------------

def _range_mask_impl(n_words, start, end):
    word_lo = jnp.arange(n_words, dtype=jnp.int32) * 32
    lo = jnp.clip(jnp.int32(start) - word_lo, 0, 32)
    hi = jnp.clip(jnp.int32(end) - word_lo, 0, 32)
    nbits = jnp.maximum(hi - lo, 0)
    ones = jnp.where(
        nbits >= 32, _FULL, (_U32(1) << nbits.astype(_U32)) - _U32(1)
    )
    return jnp.where(nbits > 0, ones << lo.astype(_U32), _U32(0))


@jax.jit
def range_mask(words, start, end):
    """uint32[n_words] mask with bits [start, end) set (bit positions
    within this word vector)."""
    return _range_mask_impl(words.shape[-1], start, end)


@jax.jit
def count_range(a, start, end):
    """Set bits within bit positions [start, end). Ref: CountRange
    (roaring.go:214) — used for cache restoration (fragment.go:250-289)."""
    mask = _range_mask_impl(a.shape[-1], start, end)
    return _popcount_sum(lax.bitwise_and(a, mask))


@jax.jit
def apply_mask(a, start, end):
    """Zero all bits outside [start, end)."""
    return lax.bitwise_and(a, _range_mask_impl(a.shape[-1], start, end))


# ---------------------------------------------------------------------------
# Range mutation. Ref: Flip (roaring.go:800-832) and the word-level
# kernels bitmapSetRange / bitmapXorRange / bitmapZeroRange
# (roaring.go:2292-2360). Dense blocks need no per-container dispatch:
# each is one fused mask + bitwise op.
# ---------------------------------------------------------------------------

@jax.jit
def set_range(a, start, end):
    """Set all bits in [start, end). Ref: bitmapSetRange roaring.go:2292."""
    return lax.bitwise_or(a, _range_mask_impl(a.shape[-1], start, end))


@jax.jit
def flip_range(a, start, end):
    """Toggle all bits in [start, end). Ref: Flip roaring.go:800 /
    bitmapXorRange roaring.go:2320."""
    return lax.bitwise_xor(a, _range_mask_impl(a.shape[-1], start, end))


@jax.jit
def zero_range(a, start, end):
    """Clear all bits in [start, end). Ref: bitmapZeroRange
    roaring.go:2340."""
    return lax.bitwise_and(
        a, lax.bitwise_not(_range_mask_impl(a.shape[-1], start, end)))


# ---------------------------------------------------------------------------
# Format-polymorphic dispatch. The reference's container matrix
# (roaring.go:1811-3283) is ~30 Go kernels selected by the (type_a,
# type_b) pair of each operand; this is its registry shape: an operand
# carries a format descriptor (``fmt`` attribute — raw device/host
# arrays are implicitly "dense"), a kernel table maps (op, fmt_a,
# fmt_b) to the specialized kernel, and any uncovered pair densifies
# both sides and falls back to the fused dense kernels above —
# bit-exact always. Adding a format means registering descriptors and
# kernels here (ops/containers.py does exactly that at import); no
# executor or storage dispatch code changes.
# ---------------------------------------------------------------------------

FMT_DENSE = "dense"
FMT_ARRAY = "array"
FMT_RUN = "run"

# (op, fmt_a, fmt_b) -> kernel.  op ∈ {"and", "or", "xor", "andnot"}.
# Count kernels return a host/device int (|a OP b|); pair kernels
# return dense uint32 words (materializing ops stay dense — results
# feed Bitmap segments, which are dense device arrays by design).
_COUNT_KERNELS = {}

_DENSE_COUNT = {}   # op -> fused dense kernel (bound below)
_DENSE_PAIR = {}


def operand_format(x):
    """Format descriptor of an operand: its ``fmt`` attribute, or
    dense for raw arrays (today's operands are all dense, so the
    pre-format call sites behave identically)."""
    return getattr(x, "fmt", FMT_DENSE)


def register_count_kernel(op, fmt_a, fmt_b, fn):
    """Install the count kernel for one (op, format, format) cell.
    Last registration wins (tests swap in probes)."""
    _COUNT_KERNELS[(op, fmt_a, fmt_b)] = fn


def count_kernel(op, fmt_a, fmt_b):
    """The registered kernel for a cell, or None (callers then take
    the densify fallback)."""
    return _COUNT_KERNELS.get((op, fmt_a, fmt_b))


def densify(x):
    """Dense uint32 words for any operand: raw arrays pass through;
    formatted containers provide ``dense_words()``. The fallback
    contract every format must honor."""
    fn = getattr(x, "dense_words", None)
    if fn is None:
        return x
    return fn()


# Fused (query-axis) count cells: the cross-query micro-batching
# tier's analog of _COUNT_KERNELS. A cell takes two SAME-FORMAT
# operand lists (containers for the (q, slice) members the coalescer
# bucketed into this (fmt_a, fmt_b) lane) and returns the per-member
# |a OP b| counts as one host int array — ONE vmapped device launch
# per lane instead of one dispatch per member (arXiv:1611.07612's
# word-level batching applied across queries). ops/containers.py
# registers the lane cells at import, exactly like the serial cells.
_FUSED_COUNT_KERNELS = {}


def register_fused_count_kernel(op, fmt_a, fmt_b, fn):
    """Install the fused lane cell for one (op, format, format) pair.
    Last registration wins (tests swap in probes)."""
    _FUSED_COUNT_KERNELS[(op, fmt_a, fmt_b)] = fn


def fused_count_kernel(op, fmt_a, fmt_b):
    """The registered lane cell, or None (callers then fall back to
    per-member dispatch_count — bit-exact, just one dispatch each)."""
    return _FUSED_COUNT_KERNELS.get((op, fmt_a, fmt_b))


def dispatch_count(op, a, b):
    """|a OP b| with per-operand format dispatch. Dense×dense is the
    EXACT current fused path (the jitted kernels above, same traced
    dispatch); a registered (op, fmt_a, fmt_b) cell runs its
    specialized kernel; anything else densifies both operands and
    falls back — bit-exact by construction."""
    fa, fb = operand_format(a), operand_format(b)
    if fa == FMT_DENSE and fb == FMT_DENSE:
        return _DENSE_COUNT[op](densify(a), densify(b))
    fn = _COUNT_KERNELS.get((op, fa, fb))
    if fn is not None:
        return fn(a, b)
    return _DENSE_COUNT[op](densify(a), densify(b))


def dispatch_pair(op, a, b):
    """a OP b materialized as dense uint32 words. Compressed operands
    densify first (materialized results feed dense Bitmap segments);
    dense×dense is the exact current fused kernel."""
    return _DENSE_PAIR[op](densify(a), densify(b))


def _bind_dense():
    """Dense×dense cells bind to the fused kernels defined above —
    the current hot path, unchanged."""
    _DENSE_COUNT.update(
        {"and": count_and, "or": count_or, "xor": count_xor,
         "andnot": count_andnot})
    _DENSE_PAIR.update(
        {"and": bitmap_and, "or": bitmap_or, "xor": bitmap_xor,
         "andnot": bitmap_andnot})


_bind_dense()


# ---------------------------------------------------------------------------
# Ingest dispatch registry. The write-path analog of the count-kernel
# table above: the streaming bulk-ingest pipeline (ingest/pipeline.py)
# resolves its device pack/classify pass and its per-format container
# builders through named cells here, and ops/ingest.py registers the
# implementations at import — adding an ingest format (or swapping the
# pack kernel for a hardware-specialized one, the arXiv:1803.11207
# offload shape) means registering cells, not editing the pipeline.
# ---------------------------------------------------------------------------

_INGEST_KERNELS = {}


def register_ingest_kernel(name, fn):
    """Install one ingest cell: ``pack_classify`` (the fused device
    scatter/pack/classify pass) or ``build.<fmt>`` (host positions ->
    compressed Container for one classified row). Last registration
    wins (tests swap in probes)."""
    _INGEST_KERNELS[name] = fn


def ingest_kernel(name):
    """The registered ingest cell, or None (callers then decline the
    device path and fall back to the legacy import pipeline)."""
    return _INGEST_KERNELS.get(name)
