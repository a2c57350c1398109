"""Measured query-cost model with production accuracy tracking.

ROADMAP item 5 (cost-based planning, per the roaring line
arXiv:1402.6407 / arXiv:1611.07612) needs a cost estimator over
``container_stats`` × the PR 13 ``/debug/kernels`` measured cells —
and an estimator nobody can validate against reality is a planner bug
factory. This module is the estimator PLUS its truth serum:

- ``estimate_count`` predicts a Count's serving cost **per tier**
  (serial compressed kernels, batched dense program, coalesced lane,
  mesh collective) by combining the kerneltime tier's measured
  per-cell means with operand formats/cardinalities probed read-only
  from the fragments (``row_format_probe``), plus per-tier dispatch
  overheads the model LEARNS from its own samples.
- after execution the executor records predicted-vs-measured for the
  tier that actually served (``record_count``): ratio histograms ride
  ``pilosa_cost_model_error`` (by op × format-cell × tier), medians
  ride ``GET /debug/costmodel`` — the calibration surface
  ``make explaincheck`` gates (median |error| ≤ 2× warm) and the
  future planner consumes.

Sampling discipline: estimation costs a few dict lookups on the memo
hit path but real fragment probes on a miss, so un-inspected queries
record 1-in-``STRIDE``; profiled/explained queries (an active
querystats accumulator) always record — they are exactly the queries
someone is inspecting. Updates are GIL-atomic dict/list writes (the
kerneltime discipline): no lock on the serving path, a lost update
under extreme contention costs one sample, never corruption. The
disabled path is the shared ``NOP`` whose ``enabled`` attribute is
the only thing hot paths read.
"""
import math
import time

# Serving-path sampling stride for un-inspected queries: at 27k q/s a
# warm dashboard still calibrates ~400 samples/s, while one query in
# STRIDE pays the estimate's memo-miss cost.
STRIDE = 64

# Ring of recent predicted/measured ratios per tier — the median
# window /debug/costmodel reports (bounded, recency-weighted truth).
RING = 256

# Bounded estimate memo (dashboards repeat query strings; the memo
# turns a sampled estimate into two dict reads).
MEMO_MAX = 512

# Measured-history table cap — a backstop against a shape-churning
# caller, not a working limit.
MAX_HISTORY_KEYS = 1024

# Static fallbacks when the kerneltime table has no matching cell yet
# (fresh process, first shapes): a host popcount sweep is ~10 GB/s on
# one core, and a Python-level kernel dispatch is ~20 µs.
FALLBACK_BYTES_PER_SEC = 10e9
FALLBACK_DISPATCH_S = 20e-6

# Per-tier overhead learning is the MEDIAN over a bounded ring of
# recent residuals: a rolling minimum (the path-model idiom) predicts
# the best case and systematically undershoots the typical serve on a
# noisy shared core, while a mean lets one compile-laden 100 ms
# residual bake in forever — the median is robust to both and tracks
# a regime change within ~half the ring.
OVERHEAD_RING = 64

# kerneltime op names per tier (the cells the estimator reads).
_SERIAL_OPS = {"and": "count_and", "or": "count_or",
               "xor": "count_xor", "andnot": "count_andnot"}

# Slot layout of one (tier, op, cell) accumulator.
_N, _ABS_LOG2_SUM, _RATIO_SUM = range(3)


class CostModel:
    """One process-wide calibrated cost model. ``estimate_count`` is
    the read path the executor samples and EXPLAIN renders;
    ``record_count`` is the single write path."""

    enabled = True

    def __init__(self, kernels=None, _clock=time.perf_counter):
        # The kerneltime observatory to read measured cells from;
        # resolved lazily against the module ACTIVE so a later
        # kerneltime enable()/disable() is always honored.
        self._kernels = kernels
        self._clock = _clock
        self._tick = 0
        self._cells = {}      # (tier, op, cell) -> [n, |log2|sum, ratio sum]
        self._rings = {}      # tier -> bounded list of ratios
        self._oh_rings = {}   # tier -> bounded list of residuals
        self._overhead = {}   # tier -> median per-unit overhead seconds
        # Measured-history rings per (tier, op, cell, slice-bucket):
        # once a shape class has real samples, its median IS the
        # prediction — the kernel-cell arithmetic is the cold-start
        # prior, measured reality is the calibrated model (medians
        # are robust: predicted = median(history) makes the median
        # predicted/actual ratio 1 by construction on a stationary
        # workload, whatever the per-sample variance).
        self._measured = {}
        self._memo = {}       # (index, call str, slice key) -> (token, est)
        self._hist = None     # stats.Histogram family (cost_model_error)
        self.samples = 0
        self.estimates = 0
        # Bumped by every recorded sample: estimate-memo tokens fold
        # it in, so a memoized prediction never outlives the learning
        # that would have changed it (a frozen first estimate would
        # freeze calibration forever).
        self._version = 0

    def set_histogram(self, hist):
        """Install the ``cost_model_error`` ratio-histogram family
        (server wiring; children tagged per tier/cell)."""
        self._hist = hist

    def _kt(self):
        if self._kernels is not None:
            return self._kernels
        from pilosa_tpu.observe import kerneltime

        return kerneltime.ACTIVE

    # -------------------------------------------------------- sampling

    def should_record(self):
        """True on the dispatches that should pay the estimate: every
        inspected query (an active querystats accumulator — profile,
        explain, or a collecting coordinator), else 1-in-STRIDE. The
        tick is a GIL-atomic racy increment; the RATE is the
        contract, not exact periodicity."""
        from pilosa_tpu import querystats

        if querystats.active() is not None:
            return True
        self._tick += 1
        return self._tick % STRIDE == 1

    # ------------------------------------------------------ estimation

    def estimate_count(self, ex, index, child, slices, plan=None,
                       leaves=None, store=True):
        """Per-tier cost estimate for ``Count(child)`` over
        ``slices``: ``{"op", "cell", "units", "tiers": {tier:
        seconds}, "cells": [...]}`` or None (unplannable/errored —
        estimation must never fail a query). ``store=False`` is the
        explain-only mode: planning reads through the plan cache
        without writing (``plan_readonly``)."""
        try:
            return self._estimate_count(ex, index, child, slices,
                                        plan, leaves, store)
        except Exception:  # noqa: BLE001 — estimator errors never surface
            return None

    def _estimate_count(self, ex, index, child, slices, plan, leaves,
                        store):
        from pilosa_tpu.plancache import slice_key
        from pilosa_tpu.storage import fragment as _frag

        # The learning version is BUCKETED (>>4): predictions refresh
        # every ~16 recorded samples — enough for calibration to
        # converge through the median rings, while a steady sampled
        # workload keeps the memo's two-dict-read amortization (a
        # per-record bump made every sampled estimate a miss).
        token = (_frag.mutation_epoch(index), self._version >> 4)
        mkey = (index, str(child), slice_key(slices))
        hit = self._memo.get(mkey)
        if hit is not None and hit[0] == token:
            return hit[1]
        if plan is None:
            if store:
                plan, leaves = ex._plan_memoized(index, child)
            else:
                from pilosa_tpu.observe.explain import plan_readonly

                plan, leaves = plan_readonly(ex, index, child)
        if plan is None:
            return None
        self.estimates += 1
        est = self._estimate_plan(ex, index, plan, leaves, slices)
        if store:  # explain-only keeps even THIS memo untouched
            if len(self._memo) >= MEMO_MAX:
                self._memo.clear()
            self._memo[mkey] = (token, est)
        return est

    def _leaf_info(self, ex, index, spec, slices):
        """(format, payload bytes/slice) for one row leaf, probed
        read-only on a couple of sample fragments (the _co_tick_route
        economy — never a full fragment walk per estimate)."""
        from pilosa_tpu import WORDS_PER_SLICE

        if spec[0] != "row":
            # BSI planes are dense by design; full window charged.
            return "dense", WORDS_PER_SLICE * 4
        _, fname, rid, view = spec
        fmt = "dense"
        nbytes = WORDS_PER_SLICE * 4
        for s in (slices[0], slices[len(slices) // 2]):
            frag = ex.holder.fragment(index, fname, view, s)
            if frag is None:
                continue
            fmt = frag.row_format_probe(rid)
            if fmt == "array":
                nbytes = max(4 * int(frag.row_count(rid)), 64)
            elif fmt == "run":
                nbytes = 1024  # run payloads are interval pairs — tiny
            break
        return fmt, nbytes

    def _cell_mean(self, op, cell, default):
        m = self._kt().cell_mean(op, cell)
        return default if m is None else m

    def _analytic_default(self, op, cell, default):
        """Cold-start fallback upgraded by the devprof tier: when the
        kerneltime table has no measured cell yet but XLA cost
        analysis captured the executable's analytic bytes, a roofline
        estimate over the COMPILER's byte count beats the operand-size
        guess (padding, fusion, and layout all change what actually
        moves). ``default`` stands when nothing is captured."""
        from pilosa_tpu.observe import devprof as devprof_mod

        dp = devprof_mod.ACTIVE
        if not dp.enabled:
            return default
        a = dp.analytic(op, cell)
        if a and a["bytes"]:
            return (a["bytes"] / FALLBACK_BYTES_PER_SEC
                    + FALLBACK_DISPATCH_S)
        return default

    def _overhead_s(self, tier, default):
        return self._overhead.get(tier, default)

    def _estimate_plan(self, ex, index, plan, leaves, slices):
        """The per-tier arithmetic: measured per-cell means × dispatch
        counts + learned per-tier overheads."""
        n = max(len(slices), 1)
        # Dominant cell: a 2-operand boolean node over row leaves (the
        # Count fast path); anything deeper charges every leaf's
        # payload through the generic tree cells.
        shape = ex._lane_plan_shape(plan)
        infos = [self._leaf_info(ex, index, sp, slices)
                 for sp in leaves]
        total_bytes = sum(b for _f, b in infos) * n
        cells = []
        if shape is not None and shape[0] != "count":
            op = shape[0]
            fa = infos[shape[1]][0]
            fb = infos[shape[2]][0]
            pair_bytes = infos[shape[1]][1] + infos[shape[2]][1]
            cell = ("dense" if fa == fb == "dense" else f"{fa}*{fb}")
            op_name = _SERIAL_OPS[op]
            serial_cell = self._cell_mean(
                op_name, cell,
                self._analytic_default(
                    op_name, cell,
                    pair_bytes / FALLBACK_BYTES_PER_SEC
                    + FALLBACK_DISPATCH_S))
            cells.append({"op": op_name, "cell": cell,
                          "perCallUs": round(serial_cell * 1e6, 3),
                          "calls": n})
            lane_cell = self._cell_mean(
                f"fused_count_{op}", None, serial_cell)
        else:
            op_name, cell = "count", "dense"
            serial_cell = self._cell_mean(
                "count", "dense",
                (total_bytes / n) / FALLBACK_BYTES_PER_SEC
                + FALLBACK_DISPATCH_S) * max(len(leaves), 1)
            cells.append({"op": "count", "cell": "dense",
                          "perCallUs": round(serial_cell * 1e6, 3),
                          "calls": n})
            lane_cell = serial_cell
        batched = self._cell_mean(
            "count_batched", None,
            self._analytic_default(
                "count_batched", None,
                total_bytes / FALLBACK_BYTES_PER_SEC
                + FALLBACK_DISPATCH_S))
        mesh = self._cell_mean("mesh_count", None, batched)
        co_dense = self._cell_mean("coalesce_count_fused", None, batched)
        tiers = {
            "serial": n * (serial_cell
                           + self._overhead_s("serial", 20e-6)),
            "batched": batched + self._overhead_s("batched", 100e-6),
            "coalesced_dense": co_dense
            + self._overhead_s("coalesced_dense", 100e-6),
            "coalesced_lane": lane_cell
            + self._overhead_s("coalesced_lane", 100e-6),
            "mesh": mesh + self._overhead_s("mesh", 200e-6),
        }
        bucket = n.bit_length()
        measured = []
        for tier in list(tiers):
            hist = self._measured.get((tier, op_name, cell, bucket))
            if hist and len(hist) >= 4:
                tiers[tier] = self._median(list(hist))
                measured.append(tier)
        return {"op": op_name, "cell": cell, "units": n,
                "bucket": bucket, "bytes": total_bytes,
                "cells": cells, "measured": measured,
                "kernel": {"serial": n * serial_cell,
                           "batched": batched},
                "tiers": tiers}

    def estimate_tiers(self, ex, index, child, slices, candidates,
                       plan=None, leaves=None, store=True):
        """Per-tier estimates for a CANDIDATE SET in one call: one
        feature derivation (probes, cells, overheads — all behind the
        estimate memo), the ``tiers`` dict restricted to the tiers
        the caller can actually serve with. Callers used to re-derive
        the full estimate per tier they compared; the planner's tier
        selector and explain's trimmed per-tier block both read this.
        ``measured`` lists the candidates whose figure is a
        measured-history median rather than the cold kernel-cell
        arithmetic."""
        est = self.estimate_count(ex, index, child, slices, plan=plan,
                                  leaves=leaves, store=store)
        if est is None:
            return None
        out = dict(est)
        out["tiers"] = {t: est["tiers"][t] for t in candidates
                        if t in est["tiers"]}
        out["measured"] = [t for t in est.get("measured", ())
                           if t in out["tiers"]]
        return out

    # ------------------------------------------------------- recording

    def record_count(self, est, tier, measured_s):
        """One predicted-vs-measured sample for the tier that actually
        served. Prediction is OUT-OF-SAMPLE (read before this update
        touches the overhead EWMA); tiers the model doesn't predict
        (memo replays, http fan-outs) are skipped."""
        if est is None or tier is None or measured_s <= 0:
            return
        predicted = est["tiers"].get(tier)
        if predicted is None or predicted <= 0:
            return
        ratio = predicted / measured_s
        key = (tier, est["op"], est["cell"])
        acc = self._cells.get(key)
        if acc is None:
            acc = self._cells.setdefault(key, [0, 0.0, 0.0])
        acc[_N] += 1
        acc[_ABS_LOG2_SUM] += abs(math.log2(ratio))
        acc[_RATIO_SUM] += ratio
        ring = self._rings.get(tier)
        if ring is None:
            ring = self._rings.setdefault(tier, [])
        ring.append(ratio)
        if len(ring) > RING:
            del ring[: len(ring) - RING]
        self.samples += 1
        # Learn the tier's dispatch overhead from the residual over
        # the kernel estimate — AFTER recording, so the next
        # prediction improves without flattering this one. Median of
        # a bounded residual ring: a compile-laden first sample's
        # 100 ms residual must not become the "overhead" every warm
        # prediction then overshoots by, and the noisy-core jitter a
        # minimum would undershoot averages out.
        units = est["units"] if tier == "serial" else 1
        kern = est["kernel"]["serial" if tier == "serial"
                             else "batched"]
        resid = max(measured_s - kern, 0.0) / max(units, 1)
        oh = self._oh_rings.get(tier)
        if oh is None:
            oh = self._oh_rings.setdefault(tier, [])
        oh.append(resid)
        if len(oh) > OVERHEAD_RING:
            del oh[: len(oh) - OVERHEAD_RING]
        self._overhead[tier] = self._median(list(oh))
        # Measured history AFTER the ratio above — prediction stays
        # out-of-sample. Bounded table: shape classes are a small
        # closed product in practice (the kerneltime cap discipline).
        hkey = (tier, est["op"], est["cell"],
                est.get("bucket", est["units"].bit_length()))
        hist = self._measured.get(hkey)
        if hist is None:
            if len(self._measured) >= MAX_HISTORY_KEYS:
                self._measured.clear()
            hist = self._measured.setdefault(hkey, [])
        hist.append(measured_s)
        if len(hist) > OVERHEAD_RING:
            del hist[: len(hist) - OVERHEAD_RING]
        self._version += 1
        h = self._hist
        if h is not None and h.enabled:
            h.with_tags(f"tier:{tier}", f"op:{est['op']}",
                        f"cell:{est['cell']}").observe(ratio)

    # --------------------------------------------------- read surfaces

    @staticmethod
    def _median(values):
        if not values:
            return None
        s = sorted(values)
        return s[len(s) // 2]

    def snapshot(self):
        """GET /debug/costmodel: per-tier calibration state (median
        predicted/actual ratio over the recent ring, median |log2
        error| as a factor, within-2× fraction, learned overheads)
        and the per-(tier, op, cell) sample table. The harness that
        the ROADMAP-5 planner calibration consumes."""
        tiers = {}
        for tier, ring in list(self._rings.items()):
            r = list(ring)
            med = self._median(r)
            within = (sum(1 for x in r if 0.5 <= x <= 2.0) / len(r)
                      if r else None)
            tiers[tier] = {
                "samples": len(r),
                "medianRatio": round(med, 4) if med else None,
                "medianErrorFactor": (round(2 ** abs(math.log2(med)), 4)
                                      if med else None),
                "withinTwoX": round(within, 4) if within is not None
                else None,
                "overheadUs": round(
                    self._overhead.get(tier, 0.0) * 1e6, 3),
            }
        cells = {}
        for (tier, op, cell), acc in sorted(list(self._cells.items())):
            n = acc[_N]
            cells[f"{tier}/{op}/{cell}"] = {
                "samples": n,
                "meanRatio": round(acc[_RATIO_SUM] / n, 4) if n else None,
                "meanAbsLog2": round(acc[_ABS_LOG2_SUM] / n, 4)
                if n else None,
            }
        return {"enabled": True, "samples": self.samples,
                "estimates": self.estimates, "stride": STRIDE,
                "tiers": tiers, "cells": cells}

    def metrics(self):
        """Flat ``name;tag:v`` map for the ``pilosa_cost_model_*``
        exposition group — untagged totals always present (zeroed on
        an idle server, the plan_cache discipline) so the families
        exist from boot; per-(tier, op, cell) children appear with
        their first sample. The error-ratio distribution rides the
        separate ``cost_model_error`` histogram family."""
        out = {"samples_total": self.samples,
               "estimates_total": self.estimates}
        for (tier, op, cell), acc in sorted(list(self._cells.items())):
            tags = f"tier:{tier},op:{op},cell:{cell}"
            out[f"samples_total;{tags}"] = acc[_N]
            out[f"abs_log2_error_sum;{tags}"] = round(
                acc[_ABS_LOG2_SUM], 6)
            out[f"ratio_sum;{tags}"] = round(acc[_RATIO_SUM], 6)
        for tier, ring in sorted(list(self._rings.items())):
            med = self._median(list(ring))
            if med is not None:
                out[f"median_ratio;tier:{tier}"] = round(med, 6)
        return out


class NopCostModel:
    """Disabled tier: hot paths read ``.enabled`` (one attribute) and
    skip; every surface still answers."""

    enabled = False

    def set_histogram(self, hist):
        pass

    def should_record(self):
        return False

    def estimate_count(self, ex, index, child, slices, plan=None,
                       leaves=None, store=True):
        return None

    def estimate_tiers(self, ex, index, child, slices, candidates,
                       plan=None, leaves=None, store=True):
        return None

    def record_count(self, est, tier, measured_s):
        pass

    def snapshot(self):
        return {"enabled": False}

    def metrics(self):
        return {}


NOP = NopCostModel()
ACTIVE = NOP


def enable(kernels=None):
    """Install a fresh process-global cost model (server wiring, next
    to the kerneltime enable — the observatory IS its measurement
    source). Installed only FOR a real enable; a later
    observe-disabled server in the same process never downgrades an
    enabled one."""
    global ACTIVE
    ACTIVE = CostModel(kernels=kernels)
    return ACTIVE


def disable():
    """Restore the nop (tests only — servers never downgrade)."""
    global ACTIVE
    ACTIVE = NOP
