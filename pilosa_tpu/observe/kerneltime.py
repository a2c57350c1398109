"""Kernel-cost attribution: per-(op, format-cell, shape-bucket)
counters and duration accounting with compile-time separated from
steady state.

The AVX2 popcount line (arXiv:1611.07612) shows word-level kernel
cost is shape-bucketed, and the roaring library line (arXiv:1709.07821)
shows intersection cost is dominated by the format pairing — so both
dimensions are MEASURED per cell here, not guessed: every dispatch
records into a ``(op, cell, bucket)`` accumulator, where ``cell`` is
the operand-format pair ("dense*dense", "array*run", a fused-lane
cell, an ingest pass, ...) and ``bucket`` is the power-of-two class of
the primary operand's payload (bytes for word vectors, lane members
for fused lanes).

Three cost populations per cell:

- **compile**: dispatches whose jit executable cache grew — the XLA
  compile the width warmer pre-pays off the serving path. Promoted
  from the tracing-only ``first_compile`` span tag to always-on
  counters (a chip window must explain its numbers without re-running
  under the tracer).
- **steady**: everything else, noted one launch in ``STRIDE`` with
  weight ``STRIDE``. A launch that does not wait for the device
  records ENQUEUE wall time — cheap and pipelining-neutral.
- **device-sampled**: 1-in-N dispatches (``[observe]
  kernel-sample-rate``) additionally ``block_until_ready`` so TRUE
  device time is measured without stalling the other N-1 calls.

Updates are GIL-atomic list increments (the ``_co_stats`` discipline):
no lock on the dispatch path; a lost update under extreme contention
costs one sample, never corruption. The disabled path is the shared
``NOP`` whose ``enabled`` attribute is the only thing hot paths read.
"""
import functools
import time

# Cells are a small closed product (ops x format pairs x buckets) in
# practice; the cap is a backstop against a pathological caller
# minting unbounded bucket labels, not a working limit.
MAX_CELLS = 4096

# Steady-state launches are noted one in STRIDE, each note standing
# for STRIDE launches (the statsd |@rate idiom): counts and sums
# scale, means stay true. Compiles and device-sampled launches always
# record.
STRIDE = 8

# Slot layout of one cell accumulator (a plain list: GIL-atomic
# increments, no per-call allocation).
_CALLS, _SECONDS, _COMPILES, _COMPILE_SECONDS, _DEV_CALLS, _DEV_SECONDS \
    = range(6)


@functools.lru_cache(maxsize=4096)
def shape_bucket(nbytes):
    """Power-of-two byte-size class label for a kernel operand:
    "<=4KB", "<=64KB", ... — one executable per jit shape bucket, one
    cost row per size class. Memoized: dispatch paths call this per
    note, and the label f-string is the allocation."""
    n = int(nbytes)
    if n <= 0:
        return "0B"
    b = 1 << max((n - 1).bit_length(), 0)
    if b >= 1 << 20:
        return f"<={b >> 20}MB"
    if b >= 1 << 10:
        return f"<={b >> 10}KB"
    return f"<={b}B"


def lane_bucket(members):
    """Power-of-two lane-size class for fused (query, slice) lanes —
    the cost axis there is member count, not operand bytes."""
    n = max(int(members), 1)
    return f"k<={1 << (n - 1).bit_length()}"


class KernelObservatory:
    """One process-wide cost table. ``note`` is the single write path;
    everything else is a read surface."""

    enabled = True

    def __init__(self, sample_rate=0, _clock=time.perf_counter):
        # 1-in-N block_until_ready sampling; 0 = never block (enqueue
        # time only — async dispatch pipelining untouched).
        self.sample_rate = max(0, int(sample_rate))
        self._clock = _clock
        self._cells = {}       # (op, cell, bucket) -> [6 slots]
        self._jit_cache = {}   # kernel name -> last seen cache size
        self._overflow = 0
        self._tick = 0         # kernel-sample-rate
        self._steady = 0       # STRIDE
        # Device-transfer rollup (host<->HBM), fed from the existing
        # querystats seams in storage/fragment.py.
        self._transfers = [0, 0, 0.0]  # count, bytes, seconds

    def clock(self):
        return self._clock()

    def should_sample(self):
        """True on the 1-in-N dispatches that measure device time.
        The tick is a GIL-atomic racy increment — exact periodicity is
        not the contract, the sampling RATE is."""
        n = self.sample_rate
        if n <= 0:
            return False
        self._tick += 1
        return self._tick % n == 0

    def steady_weight(self):
        """STRIDE on the one steady-state launch in STRIDE that is
        noted, else 0. One racy GIL-atomic tick for every launch."""
        self._steady += 1
        return 0 if self._steady % STRIDE else STRIDE

    def note(self, op, cell, bucket, seconds, compiled=False,
             device=False, n=1):
        """Record a dispatch into its (op, cell, bucket) cost cell.
        ``compiled`` marks a dispatch whose jit cache grew (its time is
        compile, not steady state). ``device`` marks a dispatch that
        blocked until the result was ready. ``n > 1`` is the statsd-|@rate
        idiom for stride-sampled launches: this observation stands for
        ``n`` calls of ~``seconds`` each, so counts and sums scale while
        means stay unbiased. A compile is always ONE event regardless
        of n."""
        key = (op, cell, bucket)
        acc = self._cells.get(key)
        if acc is None:
            if len(self._cells) >= MAX_CELLS:
                self._overflow += 1
                return
            acc = self._cells.setdefault(key, [0, 0.0, 0, 0.0, 0, 0.0])
        acc[_CALLS] += n
        acc[_SECONDS] += seconds * n
        if compiled:
            acc[_COMPILES] += 1
            acc[_COMPILE_SECONDS] += seconds
        if device:
            acc[_DEV_CALLS] += n
            acc[_DEV_SECONDS] += seconds * n

    def note_jit_cache(self, name, size):
        """Record a kernel's jit executable-cache size (the
        ``jitCacheSizes`` gauge; a launch that compiled stamps it)."""
        self._jit_cache[name] = size

    def note_transfer(self, nbytes, seconds=0.0):
        """One host->device (or device->host) transfer, from the
        querystats seams."""
        t = self._transfers
        t[0] += 1
        t[1] += int(nbytes)
        t[2] += seconds

    # ------------------------------------------------- read surfaces

    def cell_mean(self, op, cell=None):
        """Aggregate measured per-call seconds for ``op`` (optionally
        one format ``cell``) across shape buckets — the cost model's
        lookup. Device-sampled means win when present (true device
        time); steady-state enqueue means otherwise; None when the
        table holds no matching steady samples yet (callers fall back
        to their static default)."""
        dev_calls = steady_calls = 0
        dev_secs = steady_secs = 0.0
        for (o, c, _bucket), acc in list(self._cells.items()):
            if o != op or (cell is not None and c != cell):
                continue
            dev_calls += acc[_DEV_CALLS]
            dev_secs += acc[_DEV_SECONDS]
            steady_calls += acc[_CALLS] - acc[_COMPILES]
            steady_secs += acc[_SECONDS] - acc[_COMPILE_SECONDS]
        if dev_calls:
            return dev_secs / dev_calls
        if steady_calls > 0 and steady_secs > 0:
            return steady_secs / steady_calls
        return None

    def snapshot(self):
        """/debug/kernels: the cost table, most expensive cells first
        — a ready-made per-(op, format-cell, shape-bucket) cost model
        for the planner (steady-state mean is the number to plan on;
        compile mean is the first-shape tax the warmer can pre-pay).
        Cells carry the devprof tier's analytic flops/bytes/intensity
        where XLA cost_analysis was captured at their first compile."""
        from pilosa_tpu.observe import devprof as devprof_mod

        rows = []
        for (op, cell, bucket), acc in sorted(list(
                self._cells.items())):
            calls, secs, compiles, csecs, dcalls, dsecs = acc
            steady_calls = calls - compiles
            steady_secs = secs - csecs
            row = {
                "op": op, "cell": cell, "bucket": bucket,
                "calls": calls,
                "totalMs": round(secs * 1e3, 3),
                "compileCalls": compiles,
                "compileMs": round(csecs * 1e3, 3),
                "steadyCalls": steady_calls,
                "steadyMeanUs": (round(steady_secs / steady_calls * 1e6,
                                       3) if steady_calls else None),
                "deviceSampledCalls": dcalls,
                "deviceMeanUs": (round(dsecs / dcalls * 1e6, 3)
                                 if dcalls else None),
            }
            rows.append(row)
        rows.sort(key=lambda r: -r["totalMs"])
        dp = devprof_mod.ACTIVE
        if dp.enabled:
            dp.fold(rows)
        t = self._transfers
        return {
            "enabled": True,
            "sampleRate": self.sample_rate,
            "analytic": dp.summary(),
            "cells": rows,
            "cellOverflow": self._overflow,
            "jitCacheSizes": dict(sorted(list(
                self._jit_cache.items()))),
            "transfers": {"count": t[0], "bytes": t[1],
                          "seconds": round(t[2], 6)},
        }

    def metrics(self):
        """Flat ``name;tag:v`` map for the ``pilosa_kernel_*``
        exposition group."""
        out = {}
        # list() copies before iterating: lock-free writers insert
        # new cells concurrently, and a plain dict iteration would
        # raise RuntimeError mid-scrape (the _HeatTable.top
        # discipline).
        for (op, cell, bucket), acc in list(self._cells.items()):
            tags = f"op:{op},cell:{cell},bucket:{bucket}"
            out[f"calls_total;{tags}"] = acc[_CALLS]
            out[f"seconds_total;{tags}"] = round(acc[_SECONDS], 9)
            out[f"compile_total;{tags}"] = acc[_COMPILES]
            out[f"compile_seconds_total;{tags}"] = round(
                acc[_COMPILE_SECONDS], 9)
            out[f"device_sampled_total;{tags}"] = acc[_DEV_CALLS]
            out[f"device_seconds_total;{tags}"] = round(
                acc[_DEV_SECONDS], 9)
        for name, size in list(self._jit_cache.items()):
            out[f"jit_cache_size;kernel:{name}"] = size
        t = self._transfers
        out["transfers_total"] = t[0]
        out["transfer_bytes_total"] = t[1]
        out["transfer_seconds_total"] = round(t[2], 9)
        out["cell_overflow_total"] = self._overflow
        return out


class NopKernelObservatory:
    """Disabled tier: hot paths read ``.enabled`` (one attribute) and
    skip; every surface still answers."""

    enabled = False
    sample_rate = 0

    def should_sample(self):
        return False

    def steady_weight(self):
        return 0

    def note(self, op, cell, bucket, seconds, compiled=False,
             device=False, n=1):
        pass

    def note_jit_cache(self, name, size):
        pass

    def note_transfer(self, nbytes, seconds=0.0):
        pass

    def cell_mean(self, op, cell=None):
        return None

    def snapshot(self):
        return {"enabled": False}

    def metrics(self):
        return {}


NOP = NopKernelObservatory()
ACTIVE = NOP


def enable(sample_rate=0):
    """Install a fresh process-global observatory (server wiring).
    Installed only FOR a real enable — a later observe-disabled server
    in the same process never downgrades an enabled one (the
    set_dispatch_histogram discipline)."""
    global ACTIVE
    ACTIVE = KernelObservatory(sample_rate=sample_rate)
    return ACTIVE


def disable():
    """Restore the nop (tests only — servers never downgrade)."""
    global ACTIVE
    ACTIVE = NOP
