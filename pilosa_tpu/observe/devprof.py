"""Analytic device-kernel cost attribution: XLA ``cost_analysis()``
flops/bytes per (op, format-cell, shape-bucket), captured once at
first compile.

The kerneltime observatory MEASURES kernel cost; this module captures
what the cost analytically IS — the compiler's own flop and
bytes-accessed counts for the exact executable each cell dispatches.
The pair is the backend-portable cost signal the roaring line
predicts query cost from (arXiv:1709.07821: intersection cost follows
analytic operation counts; arXiv:1611.07612: popcount kernels are
characterizable by flops/bytes alone): analytic flops/bytes transfer
across backends while measured means do not, so the PR 15 cost model
can carry a calibrated prior onto a chip it has never timed.

Capture discipline: one ``fn.lower(*args).compile().cost_analysis()``
per (op, cell, bucket), claimed GIL-atomically so a racing dispatch
never pays twice, and only on dispatches that already paid an XLA
compile — steady state never re-lowers. Backends without cost
analysis (or older jax) degrade to NOP after the first
NotImplementedError; any other analysis failure is counted and that
cell simply stays unannotated. The disabled path is the shared
``NOP`` whose ``enabled`` attribute is the only thing dispatch seams
read.

Also owns the on-demand bounded device trace capture behind
``POST /debug/profile/device`` (``jax.profiler.start_trace`` armed
with a watchdog that stops it after ``seconds``), its state behind
``GET /debug/profile/device``, and the stop a shutdown needs.
"""
import glob
import os
import threading
import time

from pilosa_tpu import lockcheck, tracing

# (op, cell, bucket) capture cap — the same closed product as the
# kerneltime cell table; a backstop, not a working limit.
MAX_ENTRIES = 1024

# Device-capture bounds: one trace at a time, hard-capped duration.
MAX_CAPTURE_SECONDS = 30.0
# How long a shutdown waits for the profiler to write its file.
FINISH_TIMEOUT = 120.0
# What the state route answers before the first capture.
IDLE_STATE = {"state": "idle", "dir": None, "file": None}


class Unsupported(RuntimeError):
    """The backend (or jax build) cannot serve this request — the
    handler maps it to 501."""


class DevProfiler:
    """One process-wide analytic cost table. ``note_compile`` is the
    single write path (bitops/executor dispatch seams); ``fold`` and
    ``analytic`` are the read surfaces kerneltime and costmodel
    consume."""

    enabled = True

    def __init__(self):
        self._cells = {}       # (op, cell, bucket) -> {flops, bytes} | None
        self._failed = 0
        self._unsupported = False
        self._capture_mu = lockcheck.register(
            "devprof.DevProfiler._capture_mu", threading.Lock())
        self._capture = None   # the capture in flight (armed/stopping)
        self._last = None      # {"dir", "id", "file"} of the last one done
        self.captures = 0

    # ------------------------------------------------------ write path

    def note_compile(self, op, cell, bucket, fn, args, static=None):
        """Capture XLA cost_analysis for a kernel cell's first
        compile. Called by ``bitops.launch`` ONLY when this launch
        already paid a compile (jit-cache growth), so the extra
        lowering never rides steady state. ``static`` holds the
        launch's keyword arguments."""
        if self._unsupported:
            return
        key = (op, cell, bucket)
        if key in self._cells or len(self._cells) >= MAX_ENTRIES:
            return
        # GIL-atomic claim: a concurrently-compiling racer sees the
        # key and skips; a failed analysis leaves None (never retried
        # — the compile that could explain it already happened).
        self._cells[key] = None
        try:
            ca = fn.lower(*args, **(static or {})).compile() \
                .cost_analysis()
        except NotImplementedError:
            self._unsupported = True
            return
        except Exception:  # noqa: BLE001 — analysis must never fail a dispatch
            self._failed += 1
            return
        try:
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0) or 0.0)
            nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        except (AttributeError, TypeError, ValueError):
            self._failed += 1
            return
        if flops <= 0 and nbytes <= 0:
            self._failed += 1
            return
        self._cells[key] = {"flops": flops, "bytes": nbytes}

    # ----------------------------------------------------- read surfaces

    def lookup(self, op, cell, bucket):
        """{"flops", "bytes"} for one cell, or None."""
        return self._cells.get((op, cell, bucket))

    def analytic(self, op, cell=None):
        """{"flops", "bytes", "intensity"} for ``op`` (optionally one
        format ``cell``): the largest-bytes entry across shape buckets
        — the serving-shape executable, the cost-model feature. None
        when nothing is captured yet."""
        best = None
        for (o, c, _b), v in list(self._cells.items()):
            if v is None or o != op or (cell is not None and c != cell):
                continue
            if best is None or v["bytes"] > best["bytes"]:
                best = v
        if best is None:
            return None
        return {"flops": best["flops"], "bytes": best["bytes"],
                "intensity": (round(best["flops"] / best["bytes"], 4)
                              if best["bytes"] else None)}

    def fold(self, rows):
        """Annotate /debug/kernels cell rows in place with
        ``analyticFlops``/``analyticBytes``/``arithmeticIntensity``
        where a captured entry matches (op, cell, bucket)."""
        for row in rows:
            v = self._cells.get((row.get("op"), row.get("cell"),
                                 row.get("bucket")))
            if v is None:
                continue
            row["analyticFlops"] = v["flops"]
            row["analyticBytes"] = v["bytes"]
            row["arithmeticIntensity"] = (
                round(v["flops"] / v["bytes"], 4) if v["bytes"]
                else None)

    def summary(self):
        """Compact rollup for the /debug/kernels payload."""
        captured = sum(1 for v in list(self._cells.values())
                       if v is not None)
        return {"enabled": True, "captured": captured,
                "failed": self._failed,
                "unsupported": self._unsupported}

    # ------------------------------------------------- device capture

    def device_capture(self, trace_dir, seconds):
        """Arm a BOUNDED jax.profiler trace to ``trace_dir``: started
        now, stopped by a watchdog after ``seconds`` (hard cap
        MAX_CAPTURE_SECONDS). The one place that starts a device
        trace. The Python tracer is off (with it on, the served path
        ran at a ninth of its rate and the capture measured the
        profiler); the host tracer stays on, because the span mirror
        (tracing.arm_capture) writes through it. One at a time; raises
        Unsupported where the backend/jax build cannot trace (handler
        answers 501) and RuntimeError while a capture is armed or
        still being written (409)."""
        seconds = min(max(float(seconds), 0.1), MAX_CAPTURE_SECONDS)
        try:
            import jax
        except Exception as e:  # noqa: BLE001 — gated dep
            raise Unsupported(f"jax unavailable: {e}")
        with self._capture_mu:
            if self._capture is not None:
                raise RuntimeError("device capture already armed: "
                                   f"{self._public(self._capture)}")
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
            except Exception as e:  # noqa: BLE001 — backend-dependent
                raise Unsupported(f"device trace unsupported: {e}")
            self.captures += 1
            # Operator-facing "until" stamp (409 body / capture
            # state): wall clock is the point — the watchdog itself
            # waits the duration.
            cap = {"dir": trace_dir, "seconds": seconds,
                   "id": self.captures, "state": "armed",
                   # pilint: disable=deadline-clock
                   "until": time.time() + seconds,
                   "wake": threading.Event(), "done": threading.Event()}
            self._capture = cap
            tracing.arm_capture(jax.profiler.TraceAnnotation,
                                {"dir": trace_dir, "id": cap["id"]})

        def _watchdog():
            cap["wake"].wait(seconds)
            self._stop(cap, jax.profiler.stop_trace)

        threading.Thread(target=_watchdog, daemon=True,
                         name="devprof-capture-watchdog").start()
        return {"dir": trace_dir, "seconds": seconds, "id": cap["id"]}

    def _stop(self, cap, stop_trace):
        """armed -> stopping -> done. The profiler writes its file
        inside ``stop_trace``; the lock is not held meanwhile, so the
        state route answers "stopping" and a new capture is refused
        until the file is whole."""
        with self._capture_mu:
            cap["state"] = "stopping"
        tracing.disarm_capture()
        try:
            stop_trace()
        except Exception:  # noqa: BLE001; pilint: disable=swallow
            pass  # backend torn down: there is no file to wait for
        found = glob.glob(os.path.join(
            cap["dir"], "plugins", "profile", "*", "*.xplane.pb"))
        with self._capture_mu:
            self._last = {"dir": cap["dir"], "id": cap["id"],
                          "file": (max(found, key=os.path.getmtime)
                                   if found else None)}
            self._capture = None
        cap["done"].set()

    @staticmethod
    def _public(cap):
        return {k: cap[k] for k in ("dir", "seconds", "id", "state",
                                    "until")}

    def capture_state(self):
        """{"state": "idle" | "armed" | "stopping" | "done", "dir",
        "file"}: ``file`` is the .xplane.pb of the last finished
        capture, whole once the state says "done"."""
        with self._capture_mu:
            cap, last = self._capture, self._last
            if cap is not None:
                return dict(self._public(cap), file=None)
            if last is not None:
                return dict(last, state="done")
            return dict(IDLE_STATE)

    def finish_capture(self, timeout=FINISH_TIMEOUT):
        """The shutdown path: stop an armed capture now and wait for a
        stopping one, so the interpreter never goes while the profiler
        is tearing down (that aborted the process). True when no
        capture is left in flight."""
        with self._capture_mu:
            cap = self._capture
        if cap is None:
            return True
        cap["wake"].set()
        return cap["done"].wait(timeout)


class NopDevProfiler:
    """Disabled tier: dispatch seams read ``.enabled`` (one attribute)
    and skip; every surface still answers. Device capture is refused
    as unsupported — a disabled tier must not start traces."""

    enabled = False

    def note_compile(self, op, cell, bucket, fn, args, static=None):
        pass

    def lookup(self, op, cell, bucket):
        return None

    def analytic(self, op, cell=None):
        return None

    def fold(self, rows):
        pass

    def summary(self):
        return {"enabled": False}

    def device_capture(self, trace_dir, seconds):
        raise Unsupported("device profiling disabled")

    def capture_state(self):
        return IDLE_STATE

    def finish_capture(self, timeout=0.0):
        return True


NOP = NopDevProfiler()
ACTIVE = NOP


def enable():
    """Install a fresh process-global analytic profiler (server
    wiring, next to the kerneltime enable — its cells annotate that
    table). Installed only FOR a real enable; a later observe-disabled
    server in the same process never downgrades an enabled one."""
    global ACTIVE
    ACTIVE = DevProfiler()
    return ACTIVE


def disable():
    """Restore the nop (tests only — servers never downgrade)."""
    global ACTIVE
    ACTIVE = NOP
