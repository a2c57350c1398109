"""Continuous sampling wall-clock profiler: where the process's time
actually goes, always on, dependency-free.

The observatory (kerneltime) attributes DEVICE cost and the tracer
attributes PER-QUERY cost, but neither answers "which Python frames is
this process burning wall-clock in right now" — the question every
perf regression postmortem starts with. This module answers it the way
production profilers do (py-spy, the Go pprof CPU profile): a sampler
thread walks ``sys._current_frames()`` at ``[profile] sample-hz``
(default 19 — a prime, so the sampler cannot phase-lock with periodic
work at round frequencies) and aggregates each thread's stack into a
bounded frame-stack trie.

Per-sample work happens ON THE SAMPLER THREAD: the threads being
profiled pay nothing beyond the GIL handoff the interpreter already
imposes. The disabled tier is the shared ``NOP`` whose ``enabled``
attribute is the only thing integration seams read (the kerneltime
discipline). The sampler skips itself.

Aggregation:

- **Trie**: one node per (subsystem, frame-path prefix), bounded at
  ``MAX_NODES`` — a sample that would mint a node past the cap is
  attributed to the deepest existing prefix and counted in
  ``overflow`` (never dropped, never unbounded).
- **Two-generation decay**: each node keeps ``(current, previous)``
  sample counts. Every ``GEN_SECONDS`` the generations rotate
  (``previous = current; current = 0``) and dead nodes are pruned, so
  the profile always reflects the last one-to-two generations instead
  of averaging a week-old workload into the present. Lifetime
  per-subsystem counters stay monotonic for /metrics.
- **Ring**: the newest ``RING`` samples as (timestamp, folded stack),
  so a bounded window query can answer "what ran during THIS slow
  query" — the slow-query-ring linkage in tracing._finish.

Subsystem classification walks the stack leaf-first against module
seams (a serving thread inside a kernel dispatch is device-dispatch
time — that is the point), then falls back to the thread-naming seams
(fanpool-worker, bg-<monitor>, process_request_thread), then to
``background``.

Served as ``GET /debug/profile?seconds=&format=json|folded`` (folded =
flamegraph-consumable ``subsystem;frame;frame count`` lines) and the
``pilosa_profile_*`` exposition group.
"""
import os
import sys
import threading
import time
from collections import deque

from pilosa_tpu import lockcheck

DEFAULT_HZ = 19.0   # prime: cannot phase-lock with 1 s/100 ms tickers
MAX_NODES = 8192    # trie node cap (overflow counted, not dropped)
MAX_DEPTH = 24      # leaf-most frames kept per stack
RING = 8192         # recent-sample ring (slow-query window linkage)
GEN_SECONDS = 60.0  # generation rotation period (two-generation decay)

SUBSYSTEMS = ("serving", "coalescer", "fan-out", "device-dispatch",
              "ingest", "rebalance", "background")

# Stack-module seams, matched LEAF-FIRST (innermost frame wins): the
# most specific activity claims the sample, so a serving thread deep
# in a kernel dispatch is device-dispatch time, and a fan-out worker
# coalescing is coalescer time. Each entry: (path fragment | callable
# over (filename, funcname), subsystem).
_DEVICE_FILES = (f"{os.sep}ops{os.sep}", f"{os.sep}jax{os.sep}",
                 f"{os.sep}jaxlib{os.sep}", f"{os.sep}jax_graft{os.sep}")
_STACK_SEAMS = (
    (lambda fn, fu: fu.startswith("_co_"), "coalescer"),
    (lambda fn, fu: any(p in fn for p in _DEVICE_FILES),
     "device-dispatch"),
    (lambda fn, fu: fn.endswith("fanpool.py"), "fan-out"),
    (lambda fn, fu: f"{os.sep}ingest{os.sep}" in fn, "ingest"),
    (lambda fn, fu: fn.endswith("rebalancer.py"), "rebalance"),
    (lambda fn, fu: fn.endswith(("handler.py", "respcache.py"))
     or fn.endswith(f"http{os.sep}server.py")
     or fn.endswith("socketserver.py"), "serving"),
)

# Thread-name seams (the fallback when no stack frame is specific):
# substring -> subsystem. fanpool names its workers and spill threads;
# Server._spawn names monitors bg-<name>; ThreadingHTTPServer threads
# carry "(process_request_thread)" on py3.10+.
_NAME_SEAMS = (
    ("fanpool", "fan-out"),
    ("process_request_thread", "serving"),
    ("http-serve", "serving"),
    ("ingest", "ingest"),
    ("rebalance", "rebalance"),
    ("bg-", "background"),
)


def classify(thread_name, frames):
    """Subsystem for one sampled stack. ``frames`` is a sequence of
    (filename, funcname) ordered ROOT-FIRST; matching walks leaf-first
    so the innermost recognizable activity claims the sample."""
    for fn, fu in reversed(frames):
        for probe, subsystem in _STACK_SEAMS:
            if probe(fn, fu):
                return subsystem
    name = thread_name or ""
    for fragment, subsystem in _NAME_SEAMS:
        if fragment in name:
            return subsystem
    return "background"


def frame_label(filename, funcname):
    """``module:function`` — compact, stable across checkouts (no
    paths), the folded-stack vocabulary."""
    base = os.path.basename(filename)
    if base.endswith(".py"):
        base = base[:-3]
    return f"{base}:{funcname}"


class _Node:
    """One trie node: children by frame label, two-generation sample
    counts for stacks that END here."""

    __slots__ = ("children", "cur", "prev")

    def __init__(self):
        self.children = {}
        self.cur = 0
        self.prev = 0


class Profiler:
    """One process-wide sampling profiler. ``_ingest`` is the single
    write path (called by the sampler thread — and directly by tests
    with synthetic stacks); everything else is a read surface."""

    enabled = True

    def __init__(self, sample_hz=DEFAULT_HZ, _clock=time.perf_counter,
                 max_nodes=MAX_NODES, gen_seconds=GEN_SECONDS):
        self.sample_hz = float(sample_hz)
        self._clock = _clock
        self.max_nodes = int(max_nodes)
        self.gen_seconds = float(gen_seconds)
        self._root = {}            # subsystem -> _Node
        self._nodes = 0
        self._gen_started = _clock()
        self.generations = 0
        self.samples = 0           # lifetime, monotonic
        self.overflow = 0
        self._by_subsystem = {}    # subsystem -> lifetime sample count
        self._ring = deque(maxlen=RING)  # (t, folded "sub;f1;f2")
        self._threads_seen = 0     # thread count at the last sample
        self._stop = threading.Event()
        self._thread = None
        # The trie is written only by the sampler thread; readers
        # (handler, diagnostics) take this lock around full walks so a
        # rotation cannot prune nodes mid-render. Writes stay
        # lock-free except rotation (sampler-local, rare).
        self._mu = lockcheck.register("profiler.Profiler._mu",
                                      threading.Lock())

    # ------------------------------------------------------- sampling

    def start(self):
        if self._thread is not None or self.sample_hz <= 0:
            return self
        t = threading.Thread(target=self._run, daemon=True,
                             name="profiler-sampler")
        self._thread = t
        t.start()
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
        self._thread = None

    def _run(self):
        interval = 1.0 / max(self.sample_hz, 1e-3)
        while not self._stop.wait(interval):
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 — the sampler must not die; pilint: disable=swallow
                pass  # a torn frame during interpreter churn loses
                # one sample, never the profiler

    def sample_once(self):
        """One sweep over every live thread's current stack (the
        sampler's own excluded)."""
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()
        t = self._clock()
        self._threads_seen = len(frames) - 1
        for tid, frame in frames.items():
            if tid == me:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < MAX_DEPTH:
                code = f.f_code
                stack.append((code.co_filename, code.co_name))
                f = f.f_back
            stack.reverse()  # root-first
            subsystem = classify(names.get(tid), stack)
            labels = tuple(frame_label(fn, fu) for fn, fu in
                           stack[-MAX_DEPTH:])
            self._ingest(subsystem, labels, t)

    def _ingest(self, subsystem, labels, t=None):
        """Record one sampled stack (root-first frame labels) into the
        trie, the ring, and the lifetime counters."""
        if t is None:
            t = self._clock()
        if t - self._gen_started >= self.gen_seconds:
            self._rotate(t)
        # Trie mutation under _mu: readers (_walk) iterate children
        # dicts under the lock, and an unlocked insert here could
        # resize a dict mid-iteration: one uncontended acquire a
        # sample, at ~19 Hz.
        with self._mu:
            node = self._root.get(subsystem)
            if node is None:
                node = self._root.setdefault(subsystem, _Node())
                self._nodes += 1
            for label in labels:
                child = node.children.get(label)
                if child is None:
                    if self._nodes >= self.max_nodes:
                        # Cap hit: attribute to the deepest existing
                        # prefix — conserved, just less precise.
                        self.overflow += 1
                        break
                    child = node.children.setdefault(label, _Node())
                    self._nodes += 1
                node = child
            node.cur += 1
            self.samples += 1
            self._by_subsystem[subsystem] = \
                self._by_subsystem.get(subsystem, 0) + 1
        self._ring.append((t, ";".join((subsystem,) + labels)))

    def _rotate(self, t):
        """Two-generation decay: previous <- current, dead nodes
        pruned. Readers hold _mu around walks, so prune under it."""
        with self._mu:
            self._gen_started = t
            self.generations += 1

            def visit(node):
                node.prev = node.cur
                node.cur = 0
                dead = [k for k, c in node.children.items()
                        if not visit(c)]
                for k in dead:
                    del node.children[k]
                    self._nodes -= 1
                return node.prev > 0 or bool(node.children)

            for sub in list(self._root):
                if not visit(self._root[sub]):
                    del self._root[sub]
                    self._nodes -= 1

    # -------------------------------------------------- read surfaces

    def _walk(self):
        """[(subsystem, (label, ...), count)] for every stack with a
        nonzero two-generation count, heaviest first."""
        out = []
        with self._mu:
            for sub, root in list(self._root.items()):
                stack = [(root, ())]
                while stack:
                    node, path = stack.pop()
                    total = node.cur + node.prev
                    if total:
                        out.append((sub, path, total))
                    # list() copies before iterating: the sampler
                    # inserts children concurrently (the _HeatTable
                    # .top discipline).
                    for label, child in list(node.children.items()):
                        stack.append((child, path + (label,)))
        out.sort(key=lambda e: -e[2])
        return out

    def folded(self, limit=None):
        """Flamegraph-consumable folded stacks: one
        ``subsystem;frame;frame count`` line per sampled stack,
        heaviest first."""
        rows = self._walk()
        if limit is not None:
            rows = rows[:limit]
        return "\n".join(
            ";".join((sub,) + path) + f" {count}"
            for sub, path, count in rows)

    def snapshot(self, top=40):
        """GET /debug/profile (format=json): config, lifetime totals,
        per-subsystem sample shares, and the top stacks by
        two-generation weight."""
        rows = self._walk()
        window = sum(c for _s, _p, c in rows)
        by_sub = {}
        for sub, _path, count in rows:
            by_sub[sub] = by_sub.get(sub, 0) + count
        return {
            "enabled": True,
            "sampleHz": self.sample_hz,
            "samples": self.samples,
            "windowSamples": window,
            "generations": self.generations,
            "generationSeconds": self.gen_seconds,
            "threads": self._threads_seen,
            "trieNodes": self._nodes,
            "overflow": self.overflow,
            "subsystems": {
                sub: {"samples": self._by_subsystem.get(sub, 0),
                      "windowSamples": by_sub.get(sub, 0),
                      "windowShare": (round(by_sub.get(sub, 0) / window,
                                            4) if window else 0.0)}
                for sub in sorted(set(self._by_subsystem) | set(by_sub))},
            "topStacks": [
                {"stack": ";".join((sub,) + path), "samples": count,
                 "share": round(count / window, 4) if window else 0.0}
                for sub, path, count in rows[:top]],
        }

    def window_top(self, t0, t1, k=5):
        """Top-k folded stacks sampled in the [t0, t1] perf-clock
        window (the slow-query-ring linkage): [{"stack", "samples"}].
        Bounded by the ring — an old window answers empty."""
        counts = {}
        for t, folded in list(self._ring):
            if t0 <= t <= t1:
                counts[folded] = counts.get(folded, 0) + 1
        top = sorted(counts.items(), key=lambda e: (-e[1], e[0]))[:k]
        return [{"stack": s, "samples": n} for s, n in top]

    def digest(self, k=10):
        """Compact diagnostics block: top-k folded stacks with their
        subsystem and window share, plus per-subsystem shares."""
        snap = self.snapshot(top=k)
        return {"samples": snap["samples"],
                "sampleHz": snap["sampleHz"],
                "subsystems": {s: v["windowShare"]
                               for s, v in snap["subsystems"].items()},
                "topStacks": snap["topStacks"]}

    def collect(self, seconds, k=40):
        """Bounded on-demand window: wait ``seconds`` (the sampler
        keeps running), then aggregate exactly the ring samples from
        the window — GET /debug/profile?seconds=N. Capped small by the
        handler; the wait runs on the serving thread by design (the
        jax.profiler.start_trace precedent)."""
        t0 = self._clock()
        self._stop.wait(min(float(seconds), 30.0))
        t1 = self._clock()
        stacks = self.window_top(t0, t1, k=k)
        total = sum(s["samples"] for s in stacks)
        return {"enabled": True, "seconds": round(t1 - t0, 3),
                "sampleHz": self.sample_hz, "windowSamples": total,
                "topStacks": stacks}

    def metrics(self):
        """Flat ``name;tag:v`` map for the ``pilosa_profile_*``
        exposition group — lifetime monotonic counters plus small
        gauges (bounded cardinality: one series per subsystem)."""
        out = {"samples_total": self.samples,
               "overflow_total": self.overflow,
               "generations_total": self.generations,
               "trie_nodes": self._nodes,
               "threads": self._threads_seen,
               "sample_hz": self.sample_hz}
        for sub, n in sorted(self._by_subsystem.items()):
            out[f"samples_total;subsystem:{sub}"] = n
        return out


class NopProfiler:
    """Disabled tier: integration seams read ``.enabled`` (one
    attribute) and skip; every surface still answers."""

    enabled = False
    sample_hz = 0.0

    def start(self):
        return self

    def stop(self):
        pass

    def sample_once(self):
        pass

    def folded(self, limit=None):
        return ""

    def snapshot(self, top=40):
        return {"enabled": False}

    def window_top(self, t0, t1, k=5):
        return []

    def digest(self, k=10):
        return {"enabled": False}

    def collect(self, seconds, k=40):
        return {"enabled": False}

    def metrics(self):
        return {}


NOP = NopProfiler()
ACTIVE = NOP


def enable(sample_hz=DEFAULT_HZ):
    """Install (and start) a fresh process-global profiler (server
    wiring). PROCESS-GLOBAL like kerneltime — ``sys._current_frames``
    sees every thread in the process — and installed only FOR a real
    enable: a later profile-disabled server in the same process never
    downgrades an enabled one (the set_dispatch_histogram discipline).
    The previous sampler is stopped first so exactly one sampler
    thread exists at a time."""
    global ACTIVE
    if sample_hz <= 0:
        return ACTIVE
    prev = ACTIVE
    if prev.enabled:
        prev.stop()
    ACTIVE = Profiler(sample_hz=sample_hz).start()
    return ACTIVE


def disable():
    """Stop the sampler and restore the nop (tests only — servers
    never downgrade)."""
    global ACTIVE
    if ACTIVE.enabled:
        ACTIVE.stop()
    ACTIVE = NOP
