"""End-to-end distributed query tracing.

The reference's only ops surfaces are expvar counters and statsd
timings (stats.go:34-252) — aggregates that can say a query WAS slow
but never WHERE the time went (parse, plan, per-slice kernel execute,
XLA compile, remote fan-out, reduce). This module adds spans:

- ``Span``/``Trace``: monotonic timings, tags, parent links. Finished
  traces land in a bounded in-memory ring; traces slower than a
  configurable threshold additionally land in a dedicated slow-query
  ring and increment ``pilosa_slow_queries_total`` plus cumulative
  latency buckets on the stats client (rendered on ``/metrics``).
- Trace-context propagation: the coordinator stamps
  ``X-Pilosa-Trace-Id``/``X-Pilosa-Span-Id`` on internal fan-out
  requests (cluster/client.py); the remote handler adopts them so the
  remote node's spans carry the same trace id and a parent link into
  the coordinator's fan-out span. ``stitch()`` reassembles the pieces
  (one ``to_dict()`` payload per node) into a single tree.
- A module-level ACTIVE-SPAN slot (thread-local): instrumentation
  points anywhere in the codebase call ``tracing.span(name, **tags)``,
  which is a shared no-op context manager unless a trace is active on
  the calling thread — the NopStatsClient pattern, so disabled tracing
  costs one call + attribute read per instrumentation point (per-slice
  hot loops hoist even that behind an ``active_span()`` check).

Roots are opened by whoever owns a Tracer (the HTTP handler, tests);
everything below nests automatically. Fan-out threads adopt their
parent explicitly via ``child_of`` (thread-locals don't cross
``threading.Thread``).

While a device capture is armed (observe/devprof.py calls
``arm_capture``) every span is also written into the profiler's own
trace as a ``pilosa:<name>`` annotation and keeps its start on the
monotonic clock (``startNs``); one ``pilosa:anchor:<id>:<ns>``
annotation, written as the capture starts, maps that clock onto the
trace's. With no capture armed this is one module-level read in
``Span.__enter__``.
"""
import random
import threading
import time
from collections import deque

from pilosa_tpu import lockcheck

TRACE_HEADER = "X-Pilosa-Trace-Id"
SPAN_HEADER = "X-Pilosa-Span-Id"

DEFAULT_SLOW_THRESHOLD = 0.25   # seconds
DEFAULT_RING_SIZE = 128
DEFAULT_SLOW_RING_SIZE = 64

# Cumulative histogram bucket bounds (seconds) for the /metrics
# latency exposition. The +Inf bucket is emitted explicitly —
# histogram_quantile() returns NaN without it.
LATENCY_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, float("inf"))

_ACTIVE = threading.local()

ANNOTATION_PREFIX = "pilosa:"
ANCHOR_PREFIX = ANNOTATION_PREFIX + "anchor:"

# (annotation class, {"dir", "id"}) while a device capture is armed,
# else None: the one thing Span.__enter__ reads for the mirror.
_CAPTURE = None


def arm_capture(annotation_cls, info):
    """Mirror spans into the profiler trace that was just started.
    ``annotation_cls`` is ``jax.profiler.TraceAnnotation`` (handed in:
    this module never imports jax). The anchor's name carries the
    monotonic clock reading taken as it opens, so a reader that finds
    it in the host plane knows the offset between the two clocks."""
    global _CAPTURE
    anchor = annotation_cls(
        f"{ANCHOR_PREFIX}{info['id']}:{time.perf_counter_ns()}")
    anchor.__enter__()
    anchor.__exit__(None, None, None)
    _CAPTURE = (annotation_cls, info)


def disarm_capture():
    global _CAPTURE
    _CAPTURE = None


def mark_arrival():
    """Stamp the moment a request's first line was read (the HTTP
    server's ``parse_request``); the query route turns it into the
    root span's ``httpParseMs``."""
    _ACTIVE.arrived = time.perf_counter()


def take_arrival():
    """The calling thread's stamp, cleared: a request that did not
    come through the HTTP server must not inherit the last one's."""
    t = getattr(_ACTIVE, "arrived", None)
    _ACTIVE.arrived = None
    return t


def _new_id():
    """16 hex digits from the ``random`` module's generator (seeded
    from the OS at import and again in a forked child), not
    ``os.urandom``: that is a system call a span, 6.4 us each on a
    sandboxed host where this takes 0.33 (PERF.md section 3)."""
    return "%016x" % random.getrandbits(64)


def active_span():
    """The span currently active on this thread, or None."""
    return getattr(_ACTIVE, "span", None)


class _NopCM:
    """Shared, stateless no-op span: ``with`` it from any thread."""

    __slots__ = ()
    tags = None  # sentinel — instrumentation must not write into it

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **kw):
        pass


NOP_SPAN = _NopCM()


def span(name, **tags):
    """Child span of the thread's active span; a shared no-op when no
    trace is active (the common, disabled-tracing case)."""
    parent = getattr(_ACTIVE, "span", None)
    if parent is None:
        return NOP_SPAN
    return Span(parent.trace, name, parent_id=parent.span_id, tags=tags)


def child_of(parent, name, **tags):
    """Explicit-parent span for work handed to another thread (the
    executor's fan-out): capture ``active_span()`` before spawning,
    open the child inside the thread."""
    if parent is None or parent is NOP_SPAN:
        return NOP_SPAN
    return Span(parent.trace, name, parent_id=parent.span_id, tags=tags)


def trace_headers():
    """Outbound propagation headers for the active trace context, or
    None when no trace is active."""
    sp = getattr(_ACTIVE, "span", None)
    if sp is None:
        return None
    return {TRACE_HEADER: sp.trace.trace_id, SPAN_HEADER: sp.span_id}


class Span:
    """One timed operation. A context manager: entering activates it on
    the current thread, exiting records duration, appends it to its
    trace, and restores the previous active span."""

    __slots__ = ("trace", "name", "span_id", "parent_id", "tags",
                 "start", "duration", "_t0", "_prev", "_mirror")

    def __init__(self, trace, name, parent_id=None, tags=None):
        self.trace = trace
        self.name = name
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.tags = dict(tags) if tags else {}
        self.start = None
        self.duration = None
        self._t0 = None
        self._prev = None
        self._mirror = None

    def tag(self, **kw):
        self.tags.update(kw)

    def __enter__(self):
        self._prev = getattr(_ACTIVE, "span", None)
        _ACTIVE.span = self
        self._t0 = time.perf_counter()
        # Wall-clock anchor derived from the trace's epoch pair so all
        # of one process's spans share a consistent clock.
        self.start = self.trace.epoch0 + (self._t0 - self.trace.perf0)
        cap = _CAPTURE
        if cap is not None:
            self.trace.capture = cap[1]
            self._mirror = cap[0](ANNOTATION_PREFIX + self.name)
            self._mirror.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
        self.duration = time.perf_counter() - self._t0
        if exc is not None:
            self.tags["error"] = f"{type(exc).__name__}: {exc}"[:200]
        self.trace.add(self)
        _ACTIVE.span = self._prev
        if self is self.trace.root:
            self.trace.tracer._finish(self.trace)
        return False

    def to_dict(self):
        out = {
            "name": self.name,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "start": self.start,
            "durationMs": (round(self.duration * 1000, 3)
                           if self.duration is not None else None),
            "tags": dict(self.tags),
        }
        if self._mirror is not None:
            # The clock the capture's anchor names (perf_counter and
            # perf_counter_ns read the same one).
            out["startNs"] = int(self._t0 * 1e9)
        return out


class Trace:
    """A collection of spans sharing one trace id. Spans append on
    exit (children exit before parents), so the list is complete when
    the root exits."""

    def __init__(self, tracer, trace_id=None):
        self.tracer = tracer
        self.trace_id = trace_id or _new_id()
        self.epoch0 = time.time()
        self.perf0 = time.perf_counter()
        self.spans = []
        # NOT lockcheck-registered: a Trace is per-request — registering
        # would grow the checker's instance registry on every query
        # (lockcheck instruments long-lived locks only).
        self._mu = threading.Lock()
        self.root = None
        self.dropped = 0  # folded into the tracer's total at finish
        self.capture = None  # {"dir", "id"} once a span was mirrored

    def add(self, sp):
        with self._mu:
            if len(self.spans) < self.tracer.max_spans:
                self.spans.append(sp)
            else:
                self.dropped += 1

    def to_dict(self):
        with self._mu:
            spans = [s.to_dict() for s in self.spans]
        out = {
            "traceId": self.trace_id,
            "durationMs": (round(self.root.duration * 1000, 3)
                           if self.root and self.root.duration is not None
                           else None),
            "spans": spans,
            "roots": _build_tree(spans),
        }
        # Per-query resource counts (querystats.py), attached by the
        # handler after the root closes — rendered next to the span
        # tree in ?profile=true responses and the slow-query ring.
        resources = getattr(self, "resources", None)
        if resources:
            out["resources"] = resources
        # Top profiler stacks sampled during this trace's window
        # (observe/profiler.py), attached by the tracer when a slow
        # trace lands in the ring — the "what was the process doing
        # while this was slow" answer, inline with the trace.
        profile = getattr(self, "profile", None)
        if profile:
            out["profile"] = profile
        if self.capture:
            # Which device trace holds this trace's spans as
            # annotations (a reader follows ``dir`` to the .xplane.pb).
            out["capture"] = dict(self.capture)
        return out


def _build_tree(span_dicts):
    """Nest flat span dicts by parent links. Spans whose parent is not
    in the set (trace roots; remote fragments whose parent lives on
    the coordinator) become roots, ordered by start time."""
    nodes = {}
    for s in span_dicts:
        n = dict(s)
        n["children"] = []
        nodes[s["spanId"]] = n
    roots = []
    for n in nodes.values():
        parent = nodes.get(n["parentId"]) if n["parentId"] else None
        if parent is not None and parent is not n:
            parent["children"].append(n)
        else:
            roots.append(n)
    key = lambda n: n["start"] or 0  # noqa: E731
    for n in nodes.values():
        n["children"].sort(key=key)
    roots.sort(key=key)
    return roots


def stitch(trace_dicts):
    """Merge ``Trace.to_dict()`` payloads — typically one per cluster
    node, gathered from each node's ``/debug/traces`` — into one span
    tree. All payloads must share one trace id (propagated via
    ``X-Pilosa-Trace-Id``); remote roots resolve under the
    coordinator's fan-out span through their propagated parent id."""
    if not trace_dicts:
        return None
    tids = {t["traceId"] for t in trace_dicts}
    if len(tids) != 1:
        raise ValueError(f"cannot stitch distinct trace ids: {sorted(tids)}")
    spans, seen = [], set()
    for t in trace_dicts:
        for s in t["spans"]:
            if s["spanId"] not in seen:
                seen.add(s["spanId"])
                spans.append(s)
    durations = [t["durationMs"] for t in trace_dicts
                 if t.get("durationMs") is not None]
    return {
        "traceId": tids.pop(),
        "durationMs": max(durations) if durations else None,
        "spans": spans,
        "roots": _build_tree(spans),
    }


class Tracer:
    """Recording tracer: bounded ring of recent traces, slow-query
    ring, and (optionally) slow-query / latency-bucket counters on a
    stats client so ``/metrics`` exposes them."""

    enabled = True

    def __init__(self, ring_size=DEFAULT_RING_SIZE,
                 slow_threshold=DEFAULT_SLOW_THRESHOLD,
                 slow_ring_size=DEFAULT_SLOW_RING_SIZE,
                 stats=None, max_spans=4096):
        self.slow_threshold = slow_threshold
        self.max_spans = max_spans
        self._ring = deque(maxlen=max(int(ring_size), 1))
        self._slow_ring = deque(maxlen=max(int(slow_ring_size), 1))
        self._latencies = deque(maxlen=512)
        self._mu = lockcheck.register("tracing.Tracer._mu",
                                      threading.Lock())
        self._finished = 0
        self._slow = 0
        self._dropped = 0
        self.stats = stats
        # Pre-tagged bucket clients: with_tags per finish would allocate
        # a client per bucket per query.
        self._buckets = ([(le, stats.with_tags(
                              "le:+Inf" if le == float("inf")
                              else f"le:{le}"))
                          for le in LATENCY_BUCKETS] if stats else [])

    # ------------------------------------------------------------ record

    def start(self, name, trace_id=None, parent_id=None, **tags):
        """Open a root span (a new trace). ``trace_id``/``parent_id``
        from propagated headers stitch this trace under a remote
        parent."""
        trace = Trace(self, trace_id=trace_id)
        root = Span(trace, name, parent_id=parent_id, tags=tags)
        trace.root = root
        return root

    def span(self, name, **tags):
        """Child of the thread's active span, or a fresh root when no
        trace is active (direct executor use in tests)."""
        parent = getattr(_ACTIVE, "span", None)
        if parent is not None:
            return Span(parent.trace, name, parent_id=parent.span_id,
                        tags=tags)
        return self.start(name, **tags)

    def _finish(self, trace):
        dur = trace.root.duration
        slow = dur is not None and dur >= self.slow_threshold
        with self._mu:
            self._ring.append(trace)
            self._finished += 1
            self._dropped += trace.dropped
            if dur is not None:
                self._latencies.append(dur)
            if slow:
                self._slow += 1
                self._slow_ring.append(trace)
        if slow:
            # Slow-query linkage: stamp the trace with the top stacks
            # the continuous profiler sampled during its window.
            # Lazy import (tracing must not import observe at module
            # load); one `.enabled` attribute read when disabled.
            from pilosa_tpu.observe import profiler as profiler_mod

            prof = profiler_mod.ACTIVE
            if prof.enabled:
                # Anchor on the ROOT SPAN's own clock, not trace.perf0:
                # the trace is constructed before the root enters, so
                # a perf0-based window ends early and drops samples
                # taken in the query's final microseconds.
                t0 = (trace.root._t0 if trace.root._t0 is not None
                      else trace.perf0)
                trace.profile = prof.window_top(t0, t0 + dur, k=5)
        st = self.stats
        if st is not None and dur is not None:
            if slow:
                st.count("slow_queries_total", 1)
            st.count("query_latency_seconds_count", 1)
            st.count("query_latency_seconds_sum", dur)
            for le, client in self._buckets:
                if dur <= le:
                    client.count("query_latency_seconds_bucket", 1)

    # ------------------------------------------------------------- read

    def recent(self, n=32, slow=False, trace_id=None):
        """Newest-first trace dicts from the requested ring."""
        with self._mu:
            ring = list(self._slow_ring if slow else self._ring)
        out = []
        for trace in reversed(ring):
            if trace_id and trace.trace_id != trace_id:
                continue
            out.append(trace.to_dict())
            if len(out) >= n:
                break
        return out

    def ring_len(self, slow=False):
        with self._mu:
            return len(self._slow_ring if slow else self._ring)

    def summary(self):
        """Compact stats for diagnostics reports: totals plus p50/p99
        over the recent-latency window."""
        with self._mu:
            lats = sorted(self._latencies)
            out = {"traces": self._finished, "slowQueries": self._slow,
                   "droppedSpans": self._dropped}
        if lats:
            out["p50Ms"] = round(lats[len(lats) // 2] * 1000, 3)
            out["p99Ms"] = round(
                lats[min(len(lats) - 1, (len(lats) * 99) // 100)] * 1000, 3)
        return out


class NopTracer:
    """Disabled tracing: every surface answers, nothing records —
    the ``NopStatsClient`` pattern."""

    enabled = False
    slow_threshold = DEFAULT_SLOW_THRESHOLD

    def start(self, name, trace_id=None, parent_id=None, **tags):
        return NOP_SPAN

    def span(self, name, **tags):
        return NOP_SPAN

    def recent(self, n=32, slow=False, trace_id=None):
        return []

    def ring_len(self, slow=False):
        return 0

    def summary(self):
        return {}


NOP = NopTracer()
