"""Worker-local read execution (phase 2 of server/workers.py).

A relay-only worker still funnels every query through the master's
GIL; with N workers EXECUTING reads themselves, count-shaped serving
scales with worker count the way the reference scales with goroutines
across cores (ref: server.go:205-217). Each worker holds a READ-ONLY
replica of the holder over the master's own data files
(`PILOSA_TPU_READ_ONLY=1` — no flock, no repair snapshots, no sidecar
writes; storage/fragment.py REPLICA gates) and re-faults it when the
master's published mutation epoch moves.

Consistency: a write relays to the master, which bumps the mmap'd
epoch counter BEFORE its HTTP response; any later read finds the
counter moved and, until the replica's resync catches up, RELAYS to
the always-current master — so every read is correct, every time.
Resyncs are throttled (REFRESH_MIN_S): an every-write full-tree
resync per worker collapsed write-heavy serving.

What MAY serve locally: query trees whose ROOT is scalar-shaped
(Count / Sum / Min / Max / Average) and whose every node is a pure
bitmap-read call. Everything else relays: TopN (rank caches are
master-maintained and only sidecar-flushed periodically),
Bitmap-rooted trees (their responses can carry row attrs from the
master's attr store), writes, protobuf bodies, and every non-query
route.

Whether an ELIGIBLE query actually serves locally is a learned
per-(call shape, slice-count bucket) COST decision (RelayCostModel):
the worker replica executes on the host CPU, while the master may own
an accelerator — a wide-window Count is 100×+ faster through the
master's device stacks than through a worker's CPU popcount, but a
narrow or host-cached read is faster served right here without the
extra hop. The model mirrors the executor's adaptive path model
(aged rolling minima per arm, exploration, periodic re-measure of the
loser — the mapperLocal-never-loses invariant, ref:
executor.go:1537): no shape is ever permanently parked on a losing
path. ``PILOSA_TPU_WORKER_PATH=local|relay`` pins the choice (tests,
operators).
"""
import os
import re
import threading
import time
from pilosa_tpu import lockcheck

_READ_CALLS = frozenset({
    "Count", "Bitmap", "Intersect", "Union", "Difference", "Xor",
    "Range", "Sum", "Min", "Max", "Average"})
_SCALAR_ROOTS = frozenset({"Count", "Sum", "Min", "Max", "Average"})
_QUERY_RE = re.compile(r"^/index/([^/]+)/query$")


def _all_read_calls(call):
    if call.name not in _READ_CALLS:
        return False
    return all(_all_read_calls(c) for c in call.children)


class RelayCostModel:
    """Learned local-CPU vs relay-to-master choice per (call shape,
    slice-count bucket).

    Samples are WALL TIMES of complete serves: the local arm times the
    replica handler dispatch; the relay arm times the full unix-socket
    round trip (master queue + device execution + transport). Each arm
    keeps an aged rolling MINIMUM (one-off costs — replica cache
    fills, master-side XLA compiles — must not bake into the
    steady-state estimate; 1%/query inflation lets a stale minimum
    decay). The loser is re-measured periodically so neither arm is
    ever permanently lost (executor.go:1537's mapperLocal invariant);
    a local probe that loses CATASTROPHICALLY (>5× the relay minimum —
    the CPU-walk-of-a-device-window case) backs its re-measure
    interval off geometrically, bounding probe overhead to a vanishing
    fraction of serving."""

    EXPLORE_N = 10
    REMEASURE_EVERY = 64
    REMEASURE_MAX = 4096
    AGE = 1.01
    HYSTERESIS = 0.98
    CATASTROPHIC = 5.0

    def __init__(self, force=None):
        self._mu = lockcheck.register("worker_exec.RelayCostModel._mu",
                                      threading.Lock())
        self._stats = {}
        if force is not None and force not in ("local", "relay"):
            # A typo'd pin ('Relay', 'remote') must not silently park
            # the worker on the possibly-100x-catastrophic local arm.
            import sys

            print(f"warning: PILOSA_TPU_WORKER_PATH={force!r} is not "
                  "'local'|'relay'; ignoring (adaptive)",
                  file=sys.stderr)
            force = None
        self.force = force  # "local" | "relay" | None
        self.choices = {"local": 0, "relay_cost": 0, "relay_forced": 0}

    def choose(self, key):
        """-> 'local' | 'relay' for one eligible query."""
        if self.force is not None:
            with self._mu:
                self.choices["local" if self.force == "local"
                             else "relay_cost"] += 1
            return self.force
        with self._mu:
            st = self._stats.setdefault(key, {"n": 0})
            n = st["n"]
            st["n"] = n + 1
            for p in ("l", "r"):
                if p in st:
                    st[p] *= self.AGE
            loc, rel = st.get("l"), st.get("r")
            if rel is None:
                # Relay first: always-correct, cheap to sample (the
                # master's own adaptive paths bound it); the possibly-
                # catastrophic local probe waits for a baseline.
                choice = "relay"
            elif loc is None:
                choice = "local"
            elif n < self.EXPLORE_N:
                # Alternate so both minima hold several samples before
                # the steady-state pick — one noisy sample must not
                # park the model on the wrong path.
                choice = "local" if n % 2 else "relay"
            elif n % st.get("every", self.REMEASURE_EVERY) == 0:
                choice = "local" if loc >= rel else "relay"  # loser
            else:
                choice = ("local" if loc < self.HYSTERESIS * rel
                          else "relay")
            self.choices["local" if choice == "local"
                         else "relay_cost"] += 1
            return choice

    REGIME_SAMPLES = 8

    def record(self, key, arm, elapsed):
        """Record a completed serve's wall time for one arm
        ('l' local / 'r' relay)."""
        with self._mu:
            st = self._stats.setdefault(key, {"n": 0})
            prev = st.get(arm)
            if (arm == "r" and prev is not None
                    and elapsed > 2.0 * prev):
                # A rolling minimum can only fall; REGIME_SAMPLES
                # consecutive relay serves at >2x the minimum mean the
                # master's cost regime changed (device lost, overload)
                # — resync the minimum to reality and re-arm local
                # probing, instead of waiting out the 1%/query aging.
                st["r_hi"] = st.get("r_hi", 0) + 1
                if st["r_hi"] >= self.REGIME_SAMPLES:
                    st["r"] = elapsed
                    st["r_hi"] = 0
                    st.pop("every", None)
                return
            if arm == "r":
                st["r_hi"] = 0
            st[arm] = elapsed if prev is None else min(prev, elapsed)
            if arm == "l":
                rel = st.get("r")
                if rel is not None and elapsed > self.CATASTROPHIC * rel:
                    st["every"] = min(
                        st.get("every", self.REMEASURE_EVERY) * 4,
                        self.REMEASURE_MAX)
                elif elapsed < (rel or float("inf")):
                    st.pop("every", None)  # local competitive again

    def snapshot(self):
        """Choice counters + per-key arm minima for /debug/worker."""
        with self._mu:
            keys = {}
            for (sig, bucket), st in self._stats.items():
                keys[f"{sig}/2^{bucket}slices"] = {
                    "queries": st.get("n", 0),
                    "localMs": (round(st["l"] * 1000, 3)
                                if "l" in st else None),
                    "relayMs": (round(st["r"] * 1000, 3)
                                if "r" in st else None),
                    "remeasureEvery": st.get("every",
                                             self.REMEASURE_EVERY),
                }
            return {"choices": dict(self.choices), "keys": keys,
                    "forced": self.force}


class WorkerExecutor:
    def __init__(self, data_dir):
        from pilosa_tpu.utils import compilecache

        compilecache.enable()
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.server.handler import Handler
        from pilosa_tpu.storage import fragment as fragment_mod
        from pilosa_tpu.storage.holder import Holder

        assert fragment_mod.REPLICA, \
            "worker exec requires PILOSA_TPU_READ_ONLY=1 (WorkerPool sets it)"
        self._fragment_mod = fragment_mod
        self.holder = Holder(data_dir)
        self.holder.open()
        self.executor = Executor(self.holder)
        # Warm-start the replica executor's batched-vs-serial model
        # from the master's persisted minima (read-only — REPLICA mode
        # forbids sidecar writes, and the master owns the file):
        # workers respawn with every master boot and would otherwise
        # pay the exploration probes per shape per worker.
        try:
            import json as _json

            with open(os.path.join(data_dir, ".path_model.json")) as f:
                self.executor.load_path_model(_json.load(f))
        except (OSError, ValueError):
            pass
        self.handler = Handler(self.holder, self.executor)
        self._epoch = fragment_mod.open_published_epochs(
            os.path.join(data_dir, ".mutation_epoch"))
        self._seen = self._epoch()
        self._refresh_mu = lockcheck.register(
            "worker_exec.WorkerExecutor._refresh_mu", threading.Lock())
        self._last_refresh = 0.0
        self.cost = RelayCostModel(
            force=os.environ.get("PILOSA_TPU_WORKER_PATH") or None)
        self._tl = threading.local()

    # ------------------------------------------------------------ dispatch

    @staticmethod
    def _sig(call):
        if not call.children:
            return call.name
        return (f"{call.name}("
                f"{','.join(WorkerExecutor._sig(c) for c in call.children)})")

    def dispatch(self, method, path, qp, body, headers):
        """Serve locally when safe AND predicted cheaper; None = relay
        to master (the caller reports the relay's wall time back via
        relay_observed so the cost model sees both arms)."""
        self._tl.pending = None
        if method != "POST":
            return None
        m = _QUERY_RE.match(path)
        if m is None:
            return None
        if headers.get("Content-Type") == "application/x-protobuf" or \
                headers.get("Accept") == "application/x-protobuf":
            return None  # internal/cluster traffic stays on the master
        if ("profile" in qp or "explain" in qp
                or headers.get("X-Pilosa-Trace-Id")
                or headers.get("X-Pilosa-Collect-Stats")):
            # Traced/profiled/explained/stat-collected queries relay:
            # the MASTER owns the tracer, the querystats accumulator,
            # and the query inspector's tier/plan state — a worker
            # replica serving one locally would record nothing and
            # return no profile tree / explain block / stats footer.
            return None
        try:
            # The executor's bounded parse memo — the same tree this
            # worker's handler.dispatch will use moments later.
            calls = self.executor._parse_memo(body.decode()).calls
        except Exception:  # noqa: BLE001 — let the master shape the error
            return None
        if not calls or not all(
                c.name in _SCALAR_ROOTS and _all_read_calls(c)
                for c in calls):
            return None
        # Schema presence: a replica can trail a concurrent create by
        # one request — relay rather than answer 404 for an index the
        # master already has. (No cost sample: the key needs the
        # index's slice count.)
        idx = self.holder.index(m.group(1))
        if idx is None:
            return None
        key = ("\n".join(self._sig(c) for c in calls),
               max(idx.max_slice() + 1, 1).bit_length())
        if self.cost.choose(key) == "relay":
            # Model-driven relay (the master may own an accelerator
            # that beats this worker's CPU popcount 100×+ on wide
            # windows): time the full round trip as the relay arm.
            self._tl.pending = (key, time.perf_counter(), "r")
            return None
        if not self._fresh():
            # Stale replica: RELAY instead of refreshing inline. The
            # master is always current, so correctness never depends
            # on the refresh — and under a write-heavy load an
            # every-write refresh (full tree resync + executor cache
            # loss per worker per write) collapsed mixed serving
            # (measured 1,878 -> 95 q/s from 8 to 32 clients on one
            # core). Refreshes run at most every REFRESH_MIN_S. The
            # round trip still samples the relay arm — it measures the
            # same master path a cost relay would. The choose() above
            # counted this request as 'local'; re-book it as forced.
            with self.cost._mu:
                self.cost.choices["local"] -= 1
                self.cost.choices["relay_forced"] += 1
            self._tl.pending = (key, time.perf_counter(), "r")
            return None
        t0 = time.perf_counter()
        status, ctype, payload = self.handler.dispatch(
            method, path, qp, body, headers)
        if status in (400, 404):
            # Missing frame / stale-schema shapes: let the master (the
            # schema authority) produce the answer or the error. The
            # wasted local attempt PLUS the relay that follows is the
            # true cost of choosing local for this key — book the
            # whole round trip to the LOCAL arm so a persistently
            # erroring local path converges to relay instead of
            # parking on local unsampled.
            self._tl.pending = (key, t0, "l")
            return None
        self.cost.record(key, "l", time.perf_counter() - t0)
        # Fourth element: extra response headers — lets tests and
        # operators see which process answered.
        return status, ctype, payload, {"X-Pilosa-Served-By": "worker"}

    def relay_observed(self, resp):
        """Called by the worker loop after a relay completes: close the
        timing sample for the arm dispatch stashed ('r' for model/
        forced relays; 'l' for a failed local attempt whose true cost
        includes the relay that repaired it)."""
        pending = getattr(self._tl, "pending", None)
        self._tl.pending = None
        if pending is None:
            return
        key, t0, arm = pending
        if resp and resp[0] < 500:  # a 503 master outage is not a sample
            self.cost.record(key, arm, time.perf_counter() - t0)

    REFRESH_MIN_S = 0.25

    def _fresh(self):
        """True when the replica may serve this read. On epoch
        movement, refresh at most every REFRESH_MIN_S (the caller
        relays meanwhile — reads stay correct through the master)."""
        cur = self._epoch()
        if cur == self._seen:
            return True
        if not self._refresh_mu.acquire(blocking=False):
            return False  # someone is refreshing; relay
        try:
            cur = self._epoch()
            if cur == self._seen:
                return True
            now = time.monotonic()
            if now - self._last_refresh < self.REFRESH_MIN_S:
                return False
            # Stamp BEFORE the resync so a failing refresh is also
            # throttled — and a failure means RELAY (return False),
            # never an error: correctness never depends on the
            # refresh (e.g. the master deleting an index mid-scan
            # can race the replica walk).
            self._last_refresh = now
            try:
                # Read the counter BEFORE refreshing: a bump landing
                # mid-refresh stays unseen and triggers the next one.
                self.holder.refresh_replica()
            except Exception:  # noqa: BLE001 — relay until next try
                return False
            self._seen = cur
            return True
        finally:
            self._refresh_mu.release()
