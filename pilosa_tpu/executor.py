"""Query executor — PQL AST → per-slice device kernels + cluster
map/reduce (ref: executor.go).

Per-slice compute runs as XLA kernels on device arrays; cross-slice
reduction is associative (Count→sum, Bitmap→disjoint segment merge,
TopN→candidate merge + exact re-query, Sum→SumCount add). Across nodes
the coordinator fans out over HTTP exactly like the reference
(executor.go:1444-1575), including mid-query failover: when a node
errors, its slices are re-mapped onto remaining replicas.

Within one host, Count, Sum, compound bitmap materialization
(Union/Intersect/Difference/Xor — the result stays one device stack,
segments materialize via a single deferred bulk fetch), and the TopN
phase-2 exact re-query all take a batched mesh fast path: the whole expression tree (and, for
Sum, the BSI plane stack) compiles to ONE fused XLA program over
``uint32[n_slices, ...]`` stacks sharded across every local device
(stacks are cached, byte-bounded LRU, version-invalidated). Time
Ranges batch (view-cover expansion) and BSI conditions batch (vmapped
plane descents); TopN batches both phases incl. the Tanimoto variant
(fused intersect/row/src popcounts and the integer gate); inverse
orientation batches through inverse-view leaf stacks. In multi-node
map/reduce each node — coordinator included — runs its own slice set
through the batched path (the TPU answer to the reference's
goroutine-per-slice mapperLocal) while remote nodes fan out over HTTP;
the serial per-slice path remains the fallback wherever batching is
ineligible.
"""
import logging
import re
import threading
import time

import numpy as np
from collections import deque, namedtuple
from datetime import datetime

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu import errors as perr
from pilosa_tpu import faults
from pilosa_tpu import lockcheck
from pilosa_tpu import qos
from pilosa_tpu import querystats
from pilosa_tpu import stats as stats_mod
from pilosa_tpu import time_quantum as tq
from pilosa_tpu import tracing
from pilosa_tpu.bitmap import Bitmap
from pilosa_tpu.cluster import hedge as hedge_mod
from pilosa_tpu.observe import costmodel as costmodel_mod
from pilosa_tpu.observe import devprof as devprof_mod
from pilosa_tpu.observe import heatmap as heatmap_mod
from pilosa_tpu.observe import kerneltime as kerneltime_mod
from pilosa_tpu.ops import containers as containers_mod
from pilosa_tpu.ops.bitops import program_name
from pilosa_tpu.plancache import (FragList, PlanCache, as_slice_list,
                                  slice_key)
from pilosa_tpu import planner as planner_mod
from pilosa_tpu.pql import Condition, Query
from pilosa_tpu.utils import fanpool as fanpool_mod
from pilosa_tpu.storage.fragment import TopOptions
from pilosa_tpu.storage.view import VIEW_INVERSE, VIEW_STANDARD, view_field_name

DEFAULT_FRAME = "general"        # ref: executor.go:31
MIN_THRESHOLD = 1                # ref: executor.go:33-35
TIME_FORMAT = "%Y-%m-%dT%H:%M"   # ref: TimeFormat "2006-01-02T15:04"

SumCount = namedtuple("SumCount", ["sum", "count"])

KNOWN_CALLS = frozenset({
    "SetBit", "ClearBit", "SetFieldValue", "SetRowAttrs", "SetColumnAttrs",
    "Count", "TopN", "Sum", "Average", "Min", "Max",
    "Bitmap", "Union", "Intersect", "Difference", "Xor", "Range",
})

logger = logging.getLogger("pilosa_tpu.executor")


def _plan_operands(node):
    """How many operand stacks a batched plan reads: one past its
    highest leaf slot (0 for no plan, as an unfiltered Sum has)."""
    if node is None or node[0] == "empty":
        return 0
    if node[0] == "leaf":
        return node[1] + 1
    if node[0] == "bsi":
        return max(node[1], *node[2]) + 1
    return max((_plan_operands(kid) for kid in node[1]), default=0)


def _run_count(fn, stacks):
    """The batched Count call as the served path makes it: enqueue,
    device wait and device-to-host copy in one expression."""
    return np.asarray(fn(*stacks))


def _run_count_split(fn, stacks):
    """The same call under a trace, cut where the time can hide: the
    jitted call until it returns (enqueue, tagged ``program`` = the
    name the launch carries in a device trace after ``jit_``), the wait
    for the device, and the copy to the host."""
    with tracing.span("kernel.dispatch", program=fn.__name__):
        out = fn(*stacks)
    with tracing.span("kernel.wait"):
        out.block_until_ready()
    with tracing.span("kernel.fetch"):
        return np.asarray(out)


def _run_outputs(fn, stacks):
    """A batched program with several outputs (Sum's plane and filter
    counts), as the served path calls it: enqueue, device wait and the
    copies to the host in one expression."""
    return [np.asarray(o) for o in fn(*stacks)]


def _run_outputs_split(fn, stacks):
    """The same call under a trace, cut as ``_run_count_split`` cuts a
    Count."""
    with tracing.span("kernel.dispatch", program=fn.__name__):
        outs = fn(*stacks)
    with tracing.span("kernel.wait"):
        for o in outs:
            o.block_until_ready()
    with tracing.span("kernel.fetch"):
        return [np.asarray(o) for o in outs]


def _popcounts(x):
    """int32 popcount over the last (word) axis."""
    import jax.numpy as jnp
    from jax import lax

    return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=-1)


def _per_candidate(f, src, rows, gathered):
    """int32[R, S] of ``f(rows, src)`` a TopN candidate, inside a
    jitted program. ``rows`` holds the one gathered ``[S, R, W]``
    operand (``_rows_stack``), or a ``[S, W]`` leaf stack a candidate:
    those are reduced one by one, because stacking them inside the
    program materialises the stack (2 GB at 954 slices x 16 rows)."""
    import jax.numpy as jnp

    if gathered:
        return f(rows[0], None if src is None else src[:, None, :]).T
    return jnp.stack([f(r, src) for r in rows])


# Sentinel a batch_fn returns for "ran, and the answer is empty" — as
# opposed to None, which means "ineligible, use the serial path".
# _map_reduce absorbs it (empty overall result / skipped partial);
# reduce_fns never see it.
BATCH_EMPTY = object()

# Sentinel for "eligible, but this slice list exceeds the device stack
# budget" — _windowed_batch halves and retries on it; everything else
# (structural ineligibility) stays None and stops the recursion.
BATCH_OVER_BUDGET = object()

# Sentinel _try_batch returns when the batched path died on an
# UNEXPECTED error (jit failure, transient device OOM): the caller
# falls back to serial for this query but must NOT treat the shape as
# structurally ineligible — the next query retries the batched path.
BATCH_TRANSIENT = object()

# Sentinel _serial_exec returns when a deadline-bounded serial PROBE
# exceeded its budget: the probe already proved serial the loser, so
# the caller abandons it (reads are side-effect free) and serves the
# query batched. Bounds the cost-model exploration phase on backends
# where a per-slice dispatch is expensive: a serial probe costs one
# dispatch per slice, and unbounded alternation would make cold-start
# serving pay several full serial passes per query shape.
SERIAL_ABORT = object()

# Write-burst shapes (`bench set-bit` / bulk clients emit these):
# recognized with one regex pass so storms skip the full
# tokenizer+parser; anything else falls back to pql.parse. Three
# key=value args in ANY order — exactly one must be frame="..."
# (clients differ on arg order; str(Call) sorts alphabetically).
_BURST_ARG = (r'([^\W\d][\w-]*)\s*=\s*("[A-Za-z][\w-]*"|-?\d+)')
_SETBIT_CALL_RE = re.compile(
    r'\s*SetBit\(\s*' + _BURST_ARG + r'\s*,\s*' + _BURST_ARG
    + r'\s*,\s*' + _BURST_ARG + r'\s*\)\s*')
_CLEARBIT_CALL_RE = re.compile(
    r'\s*ClearBit\(\s*' + _BURST_ARG + r'\s*,\s*' + _BURST_ARG
    + r'\s*,\s*' + _BURST_ARG + r'\s*\)\s*')
_SETFIELD_CALL_RE = re.compile(
    r'\s*SetFieldValue\(\s*' + _BURST_ARG + r'\s*,\s*' + _BURST_ARG
    + r'\s*,\s*' + _BURST_ARG + r'\s*\)\s*')


def _parse_write_burst(s, call_re):
    """[(frame, key1, val1, key2, val2) str tuples] when the ENTIRE
    string is burst-shaped calls, else None (parser path). Values
    val1/val2 are integer literal strings (possibly negative)."""
    pos, out = 0, []
    for m in call_re.finditer(s):
        if m.start() != pos:
            return None
        pos = m.end()
        g = m.groups()
        frame = None
        rest = []
        for k, v in zip(g[0::2], g[1::2]):
            if v.startswith('"'):
                if k != "frame" or frame is not None:
                    return None
                frame = v[1:-1]
            else:
                rest.append((k, v))
        if frame is None or len(rest) != 2:
            return None
        out.append((frame, rest[0][0], rest[0][1], rest[1][0], rest[1][1]))
    if pos != len(s) or not out:
        return None
    return out


class ExecOptions:
    def __init__(self, remote=False, exclude_attrs=False, exclude_bits=False):
        self.remote = remote
        self.exclude_attrs = exclude_attrs
        self.exclude_bits = exclude_bits


class SliceUnavailableError(Exception):
    pass


def pairs_add(a, b):
    """Merge pair lists, summing counts per id (ref: Pairs.Add
    cache.go:302-427)."""
    counts = {}
    for rid, cnt in (a or []):
        counts[rid] = counts.get(rid, 0) + cnt
    for rid, cnt in (b or []):
        counts[rid] = counts.get(rid, 0) + cnt
    return sorted(counts.items(), key=lambda rc: (-rc[1], rc[0]))


class Executor:
    # Device-memory budget for cached leaf stacks (uint32[n_slices, W]
    # arrays live in HBM): ~1/8 of a v5e chip's 16 GB.
    STACK_CACHE_BYTES = 2 << 30
    # Compiled tree evaluators are small but each novel shape costs a
    # JIT compile; bound the table so shape-churning clients can't grow
    # it without limit.
    BATCHED_FN_CACHE_MAX = 128

    def __init__(self, holder, cluster=None, host=None, client=None,
                 max_writes_per_request=5000):
        self.holder = holder
        self.cluster = cluster
        self.host = host
        self.client = client   # InternalClient for remote exec
        self.max_writes_per_request = max_writes_per_request
        # Distributed mutation-epoch registry (cluster/epochs.py),
        # wired by the server on multi-node deployments: whole-result
        # memos key their validity on the epoch VECTOR of the owning
        # nodes. None (single-node, bare construction) keeps the
        # process-local epoch rules unchanged.
        self.epochs = None
        # Collective data plane (cluster/meshplane.py), wired by the
        # server when [mesh] is enabled: _map_reduce consults it
        # BEFORE the HTTP fan-out — a query whose owner slices are all
        # mesh-resident compiles to one shard_map + psum program. None
        # (the default) keeps the fan-out path byte-identical.
        self.meshplane = None
        # Tail-tolerant read tier (cluster/hedge.py), wired by the
        # server when [cluster] hedge-reads / replica-routing is on:
        # replica-aware slice→owner routing and deadline-budgeted
        # hedged fan-out legs. None (the default) keeps the
        # preferred-owner fan-out byte-identical.
        self.hedger = None
        # Per-request hedge session (request-thread-local; fan-out
        # pool threads receive it explicitly through the run closure).
        self._hedge_tls = threading.local()
        # Epoch-validated slice-plan cache (plancache.py): the one
        # LRU tier behind the slice-universe memo, the batched-plan
        # memo, the prelude memos, and the owner-host sets — capacity
        # via [executor] plan-cache-entries / PILOSA_PLAN_CACHE_ENTRIES
        # (0 = off, every lookup recomputes).
        self.plans = PlanCache()
        # Adaptive cost-based query planner (planner.py): selectivity
        # reordering, short-circuiting, and learned tier selection
        # between parse and execution. Default ON; [planner] config /
        # PILOSA_PLANNER_* env switch each pass off (everything off =
        # byte-identical pre-planner behavior). Plans memoize in the
        # plan cache below under the ("planner", ...) kind.
        self.planner = planner_mod.Planner()
        # Index removals happen at the HOLDER layer by three paths
        # (explicit delete, heartbeat tombstone merge, replica
        # resync); all must release the plan cache's per-index state,
        # not just the route handlers — hang the release on the
        # holder's hook so every path shares it.
        holder.on_index_drop = self.plans.drop_index
        # Persistent fan-out pool: map/reduce node threads and the
        # TopN discovery overlap thread draw from here instead of
        # paying thread create/join per query (see utils/fanpool.py).
        # No threads exist until the first multi-node fan-out.
        from pilosa_tpu.utils.fanpool import FanoutPool

        self._fan_pool = FanoutPool()
        # Device-stack budget: overridable per deployment (chips differ
        # in HBM; oversized slice lists window through it).
        import os as _os

        env = _os.environ.get("PILOSA_TPU_STACK_BYTES")
        if env:
            try:
                val = int(env)
                if val <= 0:
                    raise ValueError(env)
                self.STACK_CACHE_BYTES = val
            except ValueError:
                logger.warning("ignoring PILOSA_TPU_STACK_BYTES=%r "
                               "(want a positive byte count)", env)
        self._fixed_full_window = _os.environ.get(
            "PILOSA_TPU_FULL_WIN", "").lower() in ("1", "true", "yes")
        self._result_memo_off = _os.environ.get(
            "PILOSA_TPU_RESULT_MEMO", "").lower() in ("0", "false", "no")
        # Background width warming: wider-bucket programs compile off
        # the serving path (accelerator backends; see _warm_wider).
        self._warm_mu = lockcheck.register("executor.Executor._warm_mu",
                                           threading.Lock())
        self._warm_inflight = set()
        self._warm_q = []
        self._warm_thread = None
        self._warm_stats = {"compiled": 0, "failed": 0}
        # Batched dispatches the device refused for memory
        # (RESOURCE_EXHAUSTED) and that fell to the per-slice path:
        # /debug/vars ``oomFallbacks``, beside the per-query key.
        self.oom_fallbacks = 0
        # Fragment lists served from the plan cache's "leaf" entries
        # against lists walked (_frag_list), process lifetime, under
        # _cache_mu: /debug/vars, beside the per-query keys.
        self.leaf_memo = {"leafMemoHits": 0, "leafMemoMisses": 0}
        # Per-fragment TopN scans with a src, by where the probe came
        # from (_execute_topn_slice), process lifetime, under
        # _cache_mu: /debug/vars, beside the per-query keys.
        self.topn_probe = {"topnProbeFromMirror": 0, "topnProbeFromHost": 0}
        # The same scans by where their selection ran (the fragment
        # says: ``TopOptions.selected``), process lifetime, under
        # _cache_mu: /debug/vars, beside the per-query keys.
        self.topn_select = {"topnSelectDevice": 0, "topnSelectHost": 0,
                            "topnSelectOverflow": 0}
        # Lookups of a BSI aggregate's prelude memo by outcome
        # (_prelude_record), process lifetime, under _cache_mu:
        # /debug/vars, beside the per-query keys.
        self.bsi_prelude = {"bsiPreludeHits": 0, "bsiPreludeMisses": 0}
        # Views the covers of planned time Ranges asked for, and the
        # operands they were bucketed to (_batched_plan), process
        # lifetime, under _cache_mu: /debug/vars, beside the per-query
        # keys.
        self.range_cover = {"rangeCoverViews": 0, "rangeCoverOperands": 0}
        # Hinted handoff: writes skipped because a replica was DOWN,
        # keyed by host, replayed on rejoin (anti-entropy remains the
        # backstop for hints lost to a coordinator restart).
        self._hints = {}
        self._hints_dropped = 0
        # Cross-query micro-batching (tick-based group commit):
        # concurrent count-shaped dispatches fuse into ONE device
        # program per tick — dense plans as [K, S, W] query-axis
        # stacks, compressed plans as format-bucketed container lanes
        # (_co_fuse_lanes). Admission is QoS-priority-ordered and
        # deadline-bounded; knobs via [executor] coalesce-* /
        # PILOSA_COALESCE_* (set_coalesce_config).
        self._co_mu = lockcheck.register("executor.Executor._co_mu",
                                         threading.Lock())
        self._co_cv = threading.Condition(self._co_mu)
        self._co_pending = []
        self._co_leader = False
        self._co_tick_waiting = False
        self._co_route_all = False
        # Observability: ticks dispatched, queries served fused (by
        # tier), lane launches, declines by reason, deadline expiries
        # during batch wait, and the largest fused group — surfaced in
        # /debug/vars (countCoalescer) and the pilosa_coalesce_*
        # /metrics group (coalesce_metrics).
        self._co_stats = {"rounds": 0, "fused_queries": 0,
                          "max_group": 0, "compressed_fused": 0,
                          "lane_launches": 0,
                          "densified_blocks": 0,
                          "declined": {}}
        # Deadline expiries during batch wait: incremented by
        # arbitrary PARKED threads (not just the leader), so unlike
        # _co_stats it is guarded by _co_mu.
        self._co_expired = 0
        self._hints_mu = lockcheck.register("executor.Executor._hints_mu",
                                            threading.Lock())
        # Batched-count caches (guarded by one lock: handler threads
        # query concurrently). Stack cache is BYTE-bounded — stacks are
        # device-resident and scale with slice count.
        self._stack_cache = {}
        self._stack_cache_bytes = 0
        # Whole-row host representations for the CPU lane tier
        # (_lane_row_repr): byte-bounded, token-validated.
        self._lane_rows = {}
        self._lane_rows_bytes = 0
        self._result_memo = {}    # epoch-validated host result arrays
        self._result_memo_bytes = 0
        self._batched_cache = {}
        self._cache_mu = lockcheck.register("executor.Executor._cache_mu",
                                            threading.Lock())
        # Per-shape path selection (batched vs serial) learned online:
        # {(call structure, slice-count bucket): {"n", "b", "s",
        # "inel"}}. _force_path ("batched"/"serial"/None) pins the
        # choice — tests use it to make each arm deterministic.
        self._path_stats = {}
        self._path_mu = lockcheck.register("executor.Executor._path_mu",
                                           threading.Lock())
        # PILOSA_TPU_FORCE_PATH pins it process-wide — the hedge tail
        # benchmark pins a subprocess replica to "serial" so the
        # executor.slice.delay failpoint keeps firing instead of the
        # model learning its way around the injected slowness.
        forced_env = _os.environ.get("PILOSA_TPU_FORCE_PATH", "")
        self._force_path = (forced_env
                            if forced_env in ("serial", "batched")
                            else None)
        # Remote-subquery batch lanes (one per peer host): group-commit
        # batching of concurrent subcalls — see _remote_execute.
        self._rb_lanes = {}
        self._rb_lanes_mu = lockcheck.register(
            "executor.Executor._rb_lanes_mu", threading.Lock())
        self._rb_stats = {"rounds": 0, "batched_calls": 0,
                          "max_batch": 0}
        # Workload-observatory steady-state sampling tick for the
        # batched count program (see _batched_count) — racy GIL-atomic
        # increment, the _co_stats discipline.
        self._obs_tick = 0
        # Runtime-telemetry histograms (stats.py), wired by the server
        # via set_histograms; nop defaults keep bare Executor
        # construction (tests, benchmarks) at one attribute read per
        # instrumentation point.
        self.histograms = stats_mod.NOP_HISTOGRAMS
        self._hist_exec = stats_mod.NOP_HISTOGRAM
        self._hist_round = stats_mod.NOP_HISTOGRAM
        self._hist_co_group = stats_mod.NOP_HISTOGRAM

    # Fused-group size histogram bounds (queries per group, not
    # seconds): the le= series the coalescer's batching behavior reads
    # from directly.
    CO_GROUP_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)

    # Steady-state kernel-note stride for the batched count program
    # (compiles always record exactly; see _batched_count).
    OBS_STRIDE = 8

    def set_histograms(self, hset):
        """Install the server's HistogramSet: end-to-end execute
        latency, per-fan-out-round wall time, and the coalescer's
        fused-group size distribution. Accepts the nop set (everything
        stays a nop attribute read)."""
        self.histograms = hset
        self._hist_exec = hset.histogram("executor_latency_seconds")
        self._hist_round = hset.histogram("fanout_round_seconds")
        self._hist_co_group = hset.histogram("coalesce_group_size",
                                             buckets=self.CO_GROUP_BUCKETS)

    def close(self):
        """Release the persistent fan-out pool's parked threads
        (Server.close). A bare Executor that never fanned out has
        nothing to release."""
        self._fan_pool.close()

    # A replica can stay down for days; hints accrue per WRITE, so an
    # unbounded queue is a slow OOM on any write-heavy cluster. Beyond
    # the cap the OLDEST hints drop (newest state is likeliest to
    # still matter) and anti-entropy remains the backstop that repairs
    # whatever the dropped hints would have replayed.
    HINTS_MAX_PER_PEER = 10_000

    def _hints_allowed(self):
        """Hinted handoff is FORBIDDEN while an elastic resize is in
        flight (placement mid-transition/commit): the rebalancer's
        no-lost-acks argument rests on every acknowledged write having
        synchronously applied to EVERY owner of both generations — a
        write acked into a hint queue is invisible to the stream
        verify and the post-commit reconcile, and the post-cleanup
        prune would destroy its only applied copy. During a resize a
        down owner therefore fails the write loudly (the client
        retries) instead of acking a promise."""
        cl = self.cluster
        if cl is None:
            return True
        pl = getattr(cl, "placement", None)
        return pl is None or not pl.active \
            or pl.phase == "stable"

    def pending_hint_hosts(self):
        """Hosts with queued (acked-but-undelivered) hinted writes —
        the rebalancer refuses to begin a resize while any exist:
        replay targets the ORIGINAL node, which may no longer own the
        slice once a generation commits."""
        with self._hints_mu:
            return sorted(h for h, q in self._hints.items() if q)

    def _hint(self, node, index, call):
        with self._hints_mu:
            q = self._hints.get(node.host)
            if q is None:
                # deque(maxlen=...) evicts the oldest in O(1); a list
                # del q[0] shifted 10k entries per write while holding
                # the lock, exactly when the cluster is degraded.
                q = self._hints[node.host] = deque(
                    maxlen=self.HINTS_MAX_PER_PEER)
            dropped = len(q) == q.maxlen
            q.append((index, call))
            if dropped:
                self._hints_dropped += 1
        if dropped:
            self.holder.stats.count("hints_dropped_total", 1)

    @staticmethod
    def _canonical_hint_text(calls):
        """Serialize hinted write calls as PQL text; the burst regexes
        accept any arg order, so plain str(call) re-enters the burst
        fast path on the receiving node."""
        return "\n".join(str(call) for call in calls)

    def replay_hints(self, node, client):
        """Replay writes hinted while a node was DOWN. Consecutive
        same-index calls batch into one query per MaxWritesPerRequest
        window (write bursts to a down node would otherwise replay as
        thousands of single-call round trips); a failed batch retries
        its calls individually and requeues only the ones that still
        fail, so one bad hint can't block the rest."""
        with self._hints_mu:
            hints = list(self._hints.pop(node.host, ()))
        limit = self.max_writes_per_request or 5000  # as the syncer does
        i = 0
        while i < len(hints):
            index = hints[i][0]
            j = i
            while (j < len(hints) and hints[j][0] == index
                   and j - i < limit):
                j += 1
            batch = [call for _, call in hints[i:j]]
            try:
                client.execute_query(
                    node, index, self._canonical_hint_text(batch),
                    remote=True)
            except Exception:  # noqa: BLE001
                # One bad call (deleted frame, config skew) must not
                # poison the batch forever: retry individually and
                # requeue only the calls that still fail.
                for _, call in hints[i:j]:
                    try:
                        client.execute_query(node, index, Query([call]),
                                             remote=True)
                    except Exception:  # noqa: BLE001 — requeue just this
                        self._hint(node, index, call)
            i = j

    # ----------------------------------------------------------- entry

    PARSE_MEMO_MAX = 256

    def _parse_memo(self, q_string):
        """Parse with a bounded per-executor memo: dashboards repeat
        the same query strings, and tokenizing was ~28% of a warm
        dispatch (profiled at 64 slices). Hits return a CLONE — later
        stages annotate/normalize call args in place, so the cached
        tree must never be shared with an execution."""
        memo = getattr(self, "_parse_cache", None)
        if memo is None:
            memo = self._parse_cache = {}
        from pilosa_tpu.pql.ast import Query

        hit = memo.get(q_string)
        if hit is not None:
            return Query([c.clone() for c in hit.calls])
        from pilosa_tpu.pql import parse

        query = parse(q_string)
        # Cache only READ queries (writes are one-shot strings — an
        # import/anti-entropy stream would hold multi-KB bodies alive
        # and churn the memo), and cache a PRISTINE CLONE: the tree
        # handed to execution may be annotated in place, and the
        # cached copy must never see that.
        if query.write_call_n() == 0:
            if len(memo) >= self.PARSE_MEMO_MAX:
                memo.clear()
            memo[q_string] = Query([c.clone() for c in query.calls])
        return query

    def execute(self, index, query, slices=None, opt=None):
        """(ref: Executor.Execute executor.go:62-151). With hedged
        reads enabled, the whole request runs under ONE HedgeSession
        so the per-request hedge cap spans every call and fan-out
        round it performs (cluster/hedge.py)."""
        opt = opt or ExecOptions()
        hedger = self.hedger
        if (hedger is not None and hedger.enabled and hedger.reads
                and not opt.remote
                and getattr(self._hedge_tls, "session", None) is None):
            self._hedge_tls.session = hedger.session()
            try:
                return self._execute(index, query, slices, opt)
            finally:
                self._hedge_tls.session = None
        return self._execute(index, query, slices, opt)

    def _execute(self, index, query, slices=None, opt=None):
        opt = opt or ExecOptions()
        if isinstance(query, str):
            burst = kind = None
            if "SetBit(" in query:
                burst = _parse_write_burst(query, _SETBIT_CALL_RE)
                kind = "SetBit"
            elif "ClearBit(" in query:
                burst = _parse_write_burst(query, _CLEARBIT_CALL_RE)
                kind = "ClearBit"
            elif "SetFieldValue(" in query:
                burst = _parse_write_burst(query, _SETFIELD_CALL_RE)
                kind = "SetFieldValue"
            if burst is not None and len(burst) > 1:
                idx = self.holder.index(index)
                if idx is None:
                    raise perr.ErrIndexNotFound()
                if (self.max_writes_per_request
                        and len(burst) > self.max_writes_per_request):
                    raise perr.ErrTooManyWrites()
                t0 = time.perf_counter()
                if kind == "SetFieldValue":
                    results = self._execute_setfield_burst(index, burst, opt)
                else:
                    results = self._execute_setbit_burst(
                        index, burst, opt, set_value=(kind == "SetBit"))
                if results is not None:
                    self._bulk_write_stats(index, kind, len(burst),
                                           time.perf_counter() - t0, query)
                    return results
            with tracing.span("parse", bytes=len(query)):
                query = self._parse_memo(query)
        idx = self.holder.index(index)
        if idx is None:
            raise perr.ErrIndexNotFound()
        if (self.max_writes_per_request
                and query.write_call_n() > self.max_writes_per_request):
            raise perr.ErrTooManyWrites()

        if slices is None:
            needed = any(c.name not in ("SetBit", "ClearBit", "SetRowAttrs",
                                        "SetColumnAttrs", "SetFieldValue")
                         for c in query.calls)
            if needed:
                # Shared epoch-validated SliceLists (read-only by
                # convention): skips the per-query max_slice() walk
                # over every view of every frame AND pre-computes the
                # compact memo key every cache tier below keys on.
                std_slices, inv_slices = self.plans.slice_universe(
                    index, idx)
            else:
                std_slices = inv_slices = []
        else:
            std_slices = inv_slices = as_slice_list(slices)

        t0 = time.perf_counter()
        results = None
        if (len(query.calls) > 1
                and all(c.name == "SetRowAttrs" for c in query.calls)):
            # Bulk attribute insertion fast path (ref: hasOnlySetRowAttrs
            # executor.go:117-120, executeBulkSetRowAttrs :1222-1308):
            # one attr-store transaction per frame instead of one per call.
            results = self._execute_bulk_set_row_attrs(index, query.calls,
                                                       opt)
        elif (len(query.calls) > 1
                and (all(c.name == "SetBit" for c in query.calls)
                     or all(c.name == "ClearBit" for c in query.calls))):
            # SetBit/ClearBit bursts (the reference's `bench set-bit` /
            # MaxWritesPerRequest batching shape) vectorize into
            # grouped fragment applies; None when ineligible.
            results = self._execute_bulk_set_bits(
                index, query.calls, opt,
                set_value=(query.calls[0].name == "SetBit"))
        if results is None:
            results = []
            for c in query.calls:
                with tracing.span(f"call:{c.name}") as sp:
                    # Per-CALL attribution mark: in a multi-call
                    # query, this call's span must carry only ITS
                    # tier story, not the earlier calls' (the
                    # accumulator is request-scoped).
                    qs = (querystats.active()
                          if sp is not tracing.NOP_SPAN else None)
                    mark = qs.mark() if qs is not None else None
                    results.append(self._execute_call(
                        index, c, std_slices, inv_slices, opt))
                    if qs is not None:
                        # Tier attribution rides the call span into
                        # /debug/traces and the slow-query ring: a
                        # specific slow query's serving tier and
                        # decline reasons are recoverable from its
                        # trace, not just the aggregate fallback
                        # counters.
                        tier = qs.served_since(mark)
                        if tier is not None:
                            sp.tag(servedBy=tier)
                        falls = qs.falls_since(mark)
                        if falls:
                            sp.tag(fallbacks=",".join(falls))
        elapsed = time.perf_counter() - t0
        if self._hist_exec.enabled:
            self._hist_exec.observe(elapsed)
        long_query_time = getattr(self.cluster, "long_query_time", None)
        if long_query_time and elapsed > long_query_time:
            # (ref: Cluster.LongQueryTime logging, cluster.go:163)
            logger.warning("%.2fs query: %s", elapsed, query)
        return results

    # -------------------------------------------------------- dispatch

    def _execute_call(self, index, call, std_slices, inv_slices, opt):
        """(ref: executeCall executor.go:153-184 — incl. the per-call
        query counters tagged by index, :162-182)."""
        name = call.name
        if name not in KNOWN_CALLS:
            raise ValueError(f"unknown call: {name}")
        if not opt.remote:
            # Index.stats already carries the index tag (one shared
            # client, no per-call construction). Counting happens only
            # for validated names so bogus client queries can't mint
            # unbounded expvar keys.
            idx_stats = getattr(self.holder.index(index), "stats", None)
            if idx_stats is not None:
                idx_stats.count(name, 1)
        if name == "SetBit":
            return self._execute_set_bit(index, call, opt, set_value=True)
        if name == "ClearBit":
            return self._execute_set_bit(index, call, opt, set_value=False)
        if name == "SetFieldValue":
            return self._execute_set_field_value(index, call, opt)
        if name == "SetRowAttrs":
            return self._execute_set_row_attrs(index, call, opt)
        if name == "SetColumnAttrs":
            return self._execute_set_column_attrs(index, call, opt)

        slices = self._slices_for_call(index, call, std_slices, inv_slices)
        if name == "Count":
            return self._execute_count(index, call, slices, opt)
        if name == "TopN":
            return self._execute_topn(index, call, slices, opt)
        if name in ("Sum", "Average"):
            return self._execute_sum(index, call, slices, opt)
        if name == "Min":
            return self._execute_min_max(index, call, slices, opt, find_max=False)
        if name == "Max":
            return self._execute_min_max(index, call, slices, opt, find_max=True)
        # every remaining KNOWN_CALLS member is a bitmap-producing call
        return self._execute_bitmap_call(index, call, slices, opt)

    def _slices_for_call(self, index, call, std_slices, inv_slices):
        idx = self.holder.index(index)
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        row_label = frame.row_label if frame else "rowID"
        if call.supports_inverse() and call.is_inverse(row_label,
                                                       idx.column_label):
            return inv_slices
        return std_slices

    # ------------------------------------------------------ map/reduce

    def _map_reduce(self, index, slices, call, opt, map_fn, reduce_fn,
                    batch_fn=None):
        """(ref: mapReduce executor.go:1444-1535). This host's slices
        run through ``batch_fn`` — one fused XLA program over the whole
        local slice set, the TPU answer to the reference's
        goroutine-per-slice mapperLocal — falling back to the serial
        per-slice ``map_fn`` when the batched path is ineligible
        (returns None). Remote nodes fan out on threads; failed nodes'
        slices remap to replicas."""
        hm = heatmap_mod.ACTIVE
        if hm.enabled and not opt.remote and slices:
            # Coordinator-side per-index query pressure (one update,
            # never a per-slice loop — the batched warm path accesses
            # every slice uniformly and carries no skew; per-slice
            # heat comes from the fragment read layer, which only
            # individual-slice work touches).
            hm.note_query(index, len(slices))
        if (opt.remote or self.cluster is None
                or len(self.cluster.nodes) <= 1 or self.client is None):
            result = self._local_exec(call, slices, map_fn, reduce_fn,
                                      batch_fn)
            return None if result is BATCH_EMPTY else result

        result = None
        for _attempt in range(3):
            state0 = self.cluster.topology_state()
            # Collective data plane: when every owner slice is resident
            # in this node's mesh peer group, the whole query compiles
            # to ONE shard_map + psum program (cluster/meshplane.py) —
            # no sockets, no per-node threads. DECLINED (counted by
            # reason) proceeds to the HTTP fan-out, byte-identical to
            # pre-mesh behavior.
            mp = self.meshplane
            if mp is not None:
                from pilosa_tpu.cluster import meshplane as meshplane_mod

                out = mp.try_collective(self, index, call, slices)
                if out is not meshplane_mod.DECLINED:
                    if self.cluster.topology_state() == state0:
                        return out
                    # Same mid-flight hazard as the fan-out below: a
                    # resize phase landed while the collective staged/
                    # ran — restage on the settled topology.
                    result = out
                    continue
            result = self._fanout_map_reduce(index, slices, call, opt,
                                             map_fn, reduce_fn,
                                             batch_fn)
            if self.cluster.topology_state() == state0:
                return result
            # The topology moved WHILE the fan-out was in flight — an
            # elastic-resize phase change. A partial may have been
            # served by an owner that pruned its copy between this
            # query's slice→node mapping and the subquery's execution
            # (the prune races only the commit/cleanup boundary: the
            # coordinator applies its own placement flip BEFORE peers
            # hear it, so this token recheck always observes the
            # movement). Reads are side-effect free — remap on the
            # settled topology and rerun; the mesh plane is
            # re-consulted too (a mid-resize decline may now serve
            # collectively). Bounded: churn past the retries returns
            # the last answer, the pre-recheck behavior.
        return result

    def _fanout_map_reduce(self, index, slices, call, opt, map_fn,
                           reduce_fn, batch_fn):
        """One multi-node fan-out pass over a fixed topology view:
        slice→node mapping, per-node threads, failover remap. Split
        from ``_map_reduce`` so its topology-token retry loop can
        rerun the whole pass."""
        # Start from live membership when available so known-DOWN nodes
        # are excluded before the first mapping attempt.
        if self.cluster.node_set is not None:
            live = self.cluster.node_set.nodes()
            nodes = live if live else list(self.cluster.nodes)
        else:
            nodes = list(self.cluster.nodes)
        result = None
        pending = list(slices)
        # Captured before the fan-out: thread-locals don't cross
        # threading.Thread, so each node thread adopts the parent span,
        # the request deadline, AND the query-stats accumulator
        # explicitly (all nop when absent).
        parent_span = tracing.active_span()
        req_deadline = qos.current_deadline()
        qstats_acc = querystats.active()
        # Breaker-aware mapping: slices owned by a peer whose circuit
        # breaker is OPEN route straight to replicas up front, instead
        # of rediscovering the dead peer by timeout on every query.
        # Applied only when the reduced node list still covers every
        # slice — with no live replica, the query must still try the
        # breaker-open owner (its half-open probe path). The coverage
        # probe's mapping is reused for the first round, not computed
        # twice.
        all_nodes = list(nodes)  # pre-filter, for failover re-admission
        nodes, first_map = self._without_open_breakers(nodes, index,
                                                       pending)
        hedger = self.hedger
        hedge_on = (hedger is not None and hedger.enabled
                    and hedger.reads)
        route_on = (hedger is not None and hedger.enabled
                    and hedger.routing)
        session = None
        if hedge_on:
            # The request-scoped hedge session (execute() installs
            # one); direct _map_reduce callers get a fresh session so
            # the per-request cap still applies.
            session = getattr(self._hedge_tls, "session", None)
            if session is None:
                session = hedger.session()
        if route_on:
            # The breaker filter's coverage probe maps by preferred
            # owner; replica-aware routing recomputes with live
            # scores, so that mapping can't be reused.
            first_map = None
        while pending:
            if (req_deadline is not None
                    and time.monotonic() > req_deadline):
                raise qos.DeadlineExceeded()
            with tracing.span("exec.route") as rsp:
                if first_map is not None:
                    by_node, first_map = first_map, None
                elif route_on:
                    by_node = self._route_slices_by_node(nodes, index,
                                                         pending)
                else:
                    by_node = self._slices_by_node(nodes, index,
                                                   pending)
                if rsp is not tracing.NOP_SPAN:
                    rsp.tag(choice="fanout", nodes=len(by_node))
            if hedger is not None and hedger.enabled:
                remote_legs = sum(1 for node in by_node
                                  if node.host != self.host)
                if remote_legs:
                    # Load-proportional budget refill: every primary
                    # backend leg earns ratio tokens — the structural
                    # hedge-amplification bound (hedge.HedgeBudget).
                    hedger.on_primary_legs(remote_legs)
                if qstats_acc is not None and route_on:
                    for node, ns in by_node.items():
                        qstats_acc.note_hedge({
                            "host": node.host, "slices": len(ns),
                            "local": node.host == self.host,
                            "routing": hedger.rank(
                                (node.host,), self.host)[0][1]})
            if qstats_acc is not None and any(
                    node.host != self.host for node in by_node):
                # Tier attribution: this pass pays real socket
                # round-trips (the mesh plane declined or is absent).
                qstats_acc.note_tier("http")
            responses = []
            lock = threading.Lock()

            def run(node, node_slices):
                local_node = node.host == self.host
                try:
                    with qos.deadline_scope(req_deadline), \
                            querystats.scope(qstats_acc), \
                            tracing.child_of(
                                parent_span,
                                "node.local" if local_node
                                else "node.remote",
                                host=node.host, slices=len(node_slices)):
                        if local_node:
                            local = self._local_exec(call, node_slices,
                                                     map_fn, reduce_fn,
                                                     batch_fn)
                            res = (node, node_slices, local, None)
                        elif hedge_on:
                            out = self._hedged_remote_execute(
                                node, index, call, node_slices, session)
                            res = (node, node_slices, out, None)
                        else:
                            out = self._remote_execute(node, index, call,
                                                       node_slices)
                            res = (node, node_slices, out, None)
                except Exception as exc:  # noqa: BLE001 — failover path
                    res = (node, node_slices, None, exc)
                with lock:
                    responses.append(res)

            round_t0 = time.perf_counter()
            # Persistent pool instead of a fresh Thread per (node,
            # round): create/start/join was pure per-query overhead at
            # high q/s. run() owns its own error handling, and the
            # failover/deadline/trace-adoption semantics live in the
            # closure — unchanged by who executes it.
            waits = [self._fan_pool.run(
                        lambda node=node, ns=node_slices: run(node, ns))
                     for node, node_slices in by_node.items()]
            # Blocking on a fan-out round while holding any executor/
            # storage lock would convoy every other query behind the
            # slowest peer — the race hunter asserts it never happens.
            if lockcheck.ACTIVE.enabled:
                lockcheck.ACTIVE.io_point("executor.fanout.wait")
            if not fanpool_mod.wait_all(waits, deadline=req_deadline):
                # Budget spent with tasks still in flight: their remote
                # calls self-terminate on budget-bound socket timeouts;
                # nobody will read this round's partial responses.
                raise qos.DeadlineExceeded()
            if self._hist_round.enabled:
                self._hist_round.observe(time.perf_counter() - round_t0)

            pending = []
            for node, node_slices, value, exc in responses:
                if exc is not None:
                    if isinstance(exc, qos.DeadlineExceeded):
                        # The request's budget is spent — remapping the
                        # node's slices to replicas would burn replica
                        # time on an answer nobody will read.
                        raise exc
                    if (req_deadline is not None
                            and time.monotonic() > req_deadline):
                        raise qos.DeadlineExceeded() from exc
                    # Failover: drop the node, remap its slices
                    # (ref: executor.go:1487-1500).
                    nodes = [n for n in nodes if n != node]
                    covered = False
                    if nodes:
                        try:
                            self._slices_by_node(nodes, index,
                                                 node_slices)
                            covered = True
                        except SliceUnavailableError:
                            pass
                    if not covered:
                        # Survivors can't cover the slices: re-admit
                        # owners the up-front breaker filter excluded
                        # (minus the node that just failed) — trying a
                        # breaker-open peer as its half-open probe
                        # beats failing the whole query.
                        readd = [n for n in all_nodes
                                 if n != node and n not in nodes]
                        if not readd:
                            raise exc
                        nodes = nodes + readd
                        try:
                            self._slices_by_node(nodes, index,
                                                 node_slices)
                        except SliceUnavailableError:
                            raise exc
                    if qstats_acc is not None:
                        qstats_acc.add("fanoutRetries", 1)
                    pending.extend(node_slices)
                elif value is not BATCH_EMPTY:
                    # A proven-empty batched partial contributes
                    # nothing; skipping here keeps reduce_fns free of
                    # any sentinel/None handling obligation.
                    result = reduce_fn(result, value)
        return result

    def _windowed_batch(self, batch_fn, reduce_fn):
        """Wrap a read-path batch_fn so slice lists too large for the
        device stack budget stream through halved windows instead of
        dropping all the way to the serial per-slice path (SURVEY §5.7:
        a 10B-column index is ~9.5k slices streamed through device
        batches). Reads are side-effect free, so abandoning partial
        windows when a sub-window proves unbatchable is safe."""
        def fn(ns):
            out = batch_fn(ns)
            if out is not BATCH_OVER_BUDGET:
                return out  # success, BATCH_EMPTY, or structural None
            if len(ns) < 8:
                return None
            half = len(ns) // 2
            left = fn(ns[:half])
            if left is None:
                return None
            right = fn(ns[half:])
            if right is None:
                return None
            if left is BATCH_EMPTY:
                return right
            if right is BATCH_EMPTY:
                return left
            return reduce_fn(reduce_fn(None, left), right)
        return fn

    # Serial cost scales linearly with slice count, so probing it on a
    # huge slice list (a 10B-col index is ~9.5k slices) could cost
    # seconds; above this bound the model assumes batched wins (it
    # always has at scale — the serial path is thousands of dispatches).
    SERIAL_PROBE_MAX_SLICES = 512

    @classmethod
    def _call_shape(cls, call):
        """Structure key for the path cost model: op tree + arg names,
        never literal ids — TopN(f, n=3) and TopN(g, n=7) share one
        entry; a src-filtered TopN does not."""
        return (call.name, tuple(sorted(call.args)),
                tuple(cls._call_shape(c) for c in call.children))

    @staticmethod
    def _candidate_bucket(n_ids):
        """Candidate counts bucket to a power of two: the jitted TopN
        evaluator re-traces O(log R) times, not per candidate set."""
        return 1 << max(n_ids - 1, 0).bit_length()

    @staticmethod
    def _cover_bucket(n_views):
        """Operands a time Range's cover of ``n_views`` views is
        brought to: a power of two up to 16, then also the halfway
        steps (2, 4, 8, 16, 24, 32, 48, 64, 96, ...). The Union's width
        is part of the plan and so of the program's key: covers of 1
        to 63 views reach eight programs, not one a size."""
        p = 1 << max(n_views - 1, 1).bit_length()
        return p * 3 // 4 if p > 16 and n_views <= p * 3 // 4 else p

    def _serial_exec(self, node_slices, map_fn, reduce_fn, deadline=None,
                     probe=None):
        """Per-slice loop. With ``deadline`` (a perf_counter instant,
        set only for serial picks of the cost model that have a batched
        alternative), returns SERIAL_ABORT as soon as the loop runs
        past it — partial results are safely discarded because every
        read path is side-effect free.

        ``probe`` is the ``path.probe`` span of a look at the loser
        (_run_path): the loop then opens no ``slice`` span of its own
        (a probe is one span, not one a slice) and tags the probe with
        the ``slices`` it finished.

        Independently, the REQUEST deadline (qos.deadline_scope,
        stamped by the handler from X-Pilosa-Deadline / ?timeout=) is
        checked per slice: an expired query raises DeadlineExceeded
        (-> 504) instead of burning slices nobody will read. Hoisted
        like the trace check — no deadline, no per-slice cost."""
        result = None
        # Hoisted trace check: with tracing off, the per-slice loop
        # must not pay a span call (kwargs dict) per slice. The active
        # span can't change across iterations — spans opened inside
        # map_fn restore on exit.
        traced = probe is None and tracing.active_span() is not None
        req_deadline = qos.current_deadline()
        # Hoisted like the trace check: with faults disabled the loop
        # pays nothing (the chaos suite's knob for making a query
        # verifiably in-flight during drain).
        faulted = faults.ACTIVE.enabled
        for i, s in enumerate(node_slices):
            if (deadline is not None and i
                    and time.perf_counter() > deadline):
                if probe is not None:
                    probe.tag(slices=i)
                return SERIAL_ABORT
            if (req_deadline is not None and i
                    and time.monotonic() > req_deadline):
                raise qos.DeadlineExceeded()
            if faulted:
                faults.ACTIVE.fire("executor.slice.delay")
            if traced:
                with tracing.span("slice", slice=s):
                    v = map_fn(s)
            else:
                v = map_fn(s)
            result = reduce_fn(result, v)
        if probe is not None:
            probe.tag(slices=len(node_slices))
        return result

    def _local_exec(self, call, node_slices, map_fn, reduce_fn, batch_fn):
        """Path-model dispatch wrapper; see _local_exec_inner. The
        per-query slice counter records HERE, on SUCCESS only — once
        per (call, node) regardless of which path (serial, batched,
        windowed, aborted-probe retry) scanned them, and never for an
        attempt that raised and got its slices remapped to a replica
        (the replica's own count is the one that stands) — so a
        profiled fan-out's slice total tallies each slice exactly
        once cluster-wide."""
        out = self._local_exec_inner(call, node_slices, map_fn,
                                     reduce_fn, batch_fn)
        qs = querystats.active()
        if qs is not None and node_slices:
            qs.add("slices", len(node_slices))
        return out

    def _local_exec_inner(self, call, node_slices, map_fn, reduce_fn,
                          batch_fn):
        """Run this node's slice set by whichever path the per-shape
        cost model predicts faster (VERDICT r1: the batched path used
        to be unconditional and lost to serial on host-cache-bound
        shapes). Both paths are read-only, so measuring either is safe.
        The model records an aged rolling MINIMUM of wall time per
        (call structure, slice-count bucket) — a minimum, because both
        paths pay one-off warmup costs (XLA compile on the batched
        side, host plane/row cache fills on the serial side) that a
        mean would bake in; aged (1%/query inflation), so a stale
        minimum from before a cache eviction or backend change decays
        and the periodic re-measure of the losing path can win the
        spot back. Serial probing is bounded by
        SERIAL_PROBE_MAX_SLICES — serial cost is linear in slices, so
        probing a 9.5k-slice list could cost seconds."""
        forced = getattr(self, "_force_path", None)
        if batch_fn is None or forced == "serial":
            querystats.note_tier("serial")
            return self._serial_exec(node_slices, map_fn, reduce_fn)
        if forced == "batched":
            out = self._try_batch(batch_fn, node_slices)
            if out is None or out is BATCH_TRANSIENT:
                querystats.note_tier("serial")
                out = self._serial_exec(node_slices, map_fn, reduce_fn)
            else:
                querystats.note_tier("batched")
            return out
        with tracing.span("exec.route") as rsp:
            choice, probe, st, n, b = self._path_choice(call, node_slices)
            if rsp is not tracing.NOP_SPAN:
                rsp.tag(choice=choice)
        return self._run_path(choice, probe, st, n, b, node_slices, map_fn,
                              reduce_fn, batch_fn)

    def _path_choice(self, call, node_slices):
        """The path model's pick for one (call structure, slice-count
        bucket): (choice, whether the pick is a PROBE, the bucket's
        stat entry, its query count before this one, its batched
        minimum). A probe is a measurement, not the model's steady
        choice: a turn of the exploration, the first serial sample, or
        the 64th query's look at the losing path. The ``batched`` those
        branches fall back to where the slice list is too long to probe
        serially is the steady choice and is not one."""
        key = (self._call_shape(call), max(len(node_slices), 1).bit_length())
        with self._path_mu:
            st = self._path_stats.get(key)
            if st is None:
                st = self._path_stats[key] = self._seed_path_stat(key)
            n = st["n"]
            st["n"] = n + 1
            for p in ("b", "s"):  # age both minima toward re-measurement
                if p in st:
                    st[p] *= 1.01
            probe_ok = len(node_slices) <= self.SERIAL_PROBE_MAX_SLICES

            b, s = st.get("b"), st.get("s")
            probe = False
            if st.get("inel", 0) >= 2 and n % 64 != 63:
                # Batch planning declined twice in a row (structural
                # ineligibility) — skip the doomed re-plan; the rare
                # 64th query retries in case the schema changed.
                choice = "serial_inel"
            elif b is None or n < 2:
                choice = "batched"
            elif probe_ok and n < 12:
                # Exploration phase: alternate so both minima
                # accumulate several samples before the steady-state
                # choice — one noisy sample must not park the model on
                # the wrong path.
                choice = "serial" if n % 2 else "batched"
                probe = True
            elif s is None:
                choice = "serial" if probe_ok else "batched"
                probe = probe_ok
            elif n % 64 == 63:
                # Re-measure the currently losing path.
                choice = ("batched" if s <= b
                          else ("serial" if probe_ok else "batched"))
                probe = s <= b or probe_ok
            else:
                # Slight hysteresis so exact ties don't flap between
                # paths (flapping between near-equal paths costs
                # nothing anyway — the minima keep both honest).
                choice = ("serial" if (s < 0.98 * b and probe_ok)
                          else "batched")
        return choice, probe, st, n, b

    def _run_path(self, choice, probe, st, n, b, node_slices, map_fn,
                  reduce_fn, batch_fn):
        """Serve by the chosen path and record what it took. A PROBE
        (_path_choice) runs its attempt under span ``path.probe`` (tags
        ``path``, ``outcome`` = ``finished`` or ``aborted``; a serial
        one also ``deadline_ms`` and ``slices``) and is counted, traced
        or not: ``pathProbes`` / ``pathProbeAborts`` of the request
        (querystats) and ``probes`` / ``probeAborts`` / ``probeMs`` of
        the call shape (path_model_snapshot). An attempt is aborted
        when the serial loop ran past its deadline or the batched
        program declined; the request is then served by the other
        path, outside the span."""
        t0 = time.perf_counter()
        if choice.startswith("serial"):
            deadline = None
            if choice == "serial" and b is not None:
                # A serial pick with a batched alternative: once the
                # loop has provably lost (5x the batched minimum,
                # floored so a microsecond batched time can't abort a
                # run that deserves a fair sample), abandon it and
                # serve the query batched below. The pessimistic
                # elapsed still records as a serial sample, so the
                # model converges away from serial without ever paying
                # its full cost.
                deadline = t0 + max(5.0 * b, 0.05)
            if probe:
                with tracing.span(
                        "path.probe", path="serial",
                        deadline_ms=round((deadline - t0) * 1000, 3)) as psp:
                    out = self._serial_exec(node_slices, map_fn, reduce_fn,
                                            deadline, psp)
                    probe = self._probe_outcome(psp, out is SERIAL_ABORT)
            else:
                out = self._serial_exec(node_slices, map_fn, reduce_fn,
                                        deadline)
            if out is not SERIAL_ABORT:
                if choice == "serial":  # skip ineligibility-forced runs
                    self._record_path(st, "s", time.perf_counter() - t0,
                                      probe)
                querystats.note_tier("serial")
                return out
            # Aborted: the elapsed (already >= 5x the batched minimum)
            # is serial's sample, and the query falls through to the
            # batched path. Restart the clock so the batched minimum
            # isn't polluted by the aborted run's time.
            self._record_path(st, "s", time.perf_counter() - t0, probe)
            t0 = time.perf_counter()
            probe = False
        if probe:
            with tracing.span("path.probe", path="batched") as psp:
                out = self._try_batch(batch_fn, node_slices)
                probe = self._probe_outcome(
                    psp, out is None or out is BATCH_TRANSIENT)
        else:
            out = self._try_batch(batch_fn, node_slices)
        if out is None or out is BATCH_TRANSIENT:
            now = time.perf_counter()
            if probe:  # a declined program is no sample of a path
                self._record_path(st, None, now - t0, probe)
            t0 = now
            querystats.note_tier("serial")
            res = self._serial_exec(node_slices, map_fn, reduce_fn)
            if out is None:
                # Structurally ineligible — remember, so the model
                # stops paying the failed planning attempt every query.
                # (Transient device errors don't count: the next query
                # retries the batched path.)
                with self._path_mu:
                    st["inel"] = st.get("inel", 0) + 1
            self._record_path(st, "s", time.perf_counter() - t0)
            return res
        with self._path_mu:
            st["inel"] = 0
        if n > 0:  # skip the compile-laden first sample
            self._record_path(st, "b", time.perf_counter() - t0, probe)
        querystats.note_tier("batched")
        return out

    def _record_path(self, st, path, elapsed, probe=False):
        """One sample of ``path``'s wall time into the rolling minimum;
        ``probe`` (``finished`` | ``aborted``) where the attempt was a
        look at the loser, counted for the call shape under the same
        lock. The counts are not persisted: a restarted server starts
        from 0 (save_path_model)."""
        with self._path_mu:
            if path is not None:
                prev = st.get(path)
                st[path] = elapsed if prev is None else min(prev, elapsed)
            if probe:
                st["probes"] = st.get("probes", 0) + 1
                st["probe_s"] = st.get("probe_s", 0.0) + elapsed
                if probe == "aborted":
                    st["probe_aborts"] = st.get("probe_aborts", 0) + 1

    @staticmethod
    def _probe_outcome(psp, aborted):
        """Close the account of one probe attempt: the span's
        ``outcome`` and the request's counters. Returns the outcome,
        which ``_record_path`` then counts for the call shape."""
        outcome = "aborted" if aborted else "finished"
        psp.tag(outcome=outcome)
        querystats.add("pathProbes")
        if aborted:
            querystats.add("pathProbeAborts")
        return outcome

    @staticmethod
    def _shape_sig(shape):
        """Readable, stable signature for a _call_shape tuple — the
        persistence key and the /debug/vars label. Arg NAMES are part
        of the shape (_call_shape's contract: a filtered TopN must not
        share an entry with a plain one), so they must be part of the
        signature or distinct shapes would collide on one persistence
        key and seed each other's minima."""
        name, args, children = shape
        sig = name + (f"[{','.join(args)}]" if args else "")
        if not children:
            return sig
        return (f"{sig}("
                f"{','.join(Executor._shape_sig(c) for c in children)})")

    # Seeded entries start past exploration with both minima inflated:
    # live measurements beat a seed immediately (minimum-takes-all),
    # aging + the periodic loser re-measure keep a stale seed from
    # parking a shape, and the never-lose invariant is untouched.
    PATH_SEED_INFLATE = 1.2
    PATH_SEED_N = 12  # == the exploration horizon in _local_exec

    def _seed_path_stat(self, key):
        """Fresh per-(shape, bucket) stat entry, warm-started from a
        persisted model when one was loaded (load_path_model): a
        restarted server skips the ~12-query exploration phase —
        which on big indexes costs seconds of deliberately-losing
        probes — for every shape it served before."""
        seed = getattr(self, "_path_seed", None)
        if seed:
            hit = seed.get(f"{self._shape_sig(key[0])}|{key[1]}")
            if hit:  # values pre-sanitized by load_path_model
                st = {"n": self.PATH_SEED_N}
                for arm in ("b", "s"):
                    if arm in hit:
                        st[arm] = hit[arm] * self.PATH_SEED_INFLATE
                if "inel" in hit:
                    st["inel"] = hit["inel"]
                return st
        return {"n": 0}

    def save_path_model(self):
        """JSON-serializable snapshot of the learned path model for
        cross-restart warm start (cache-sidecar class persistence —
        best-effort, validated on load)."""
        out = {}
        with self._path_mu:
            for (shape, bucket), st in self._path_stats.items():
                if "b" not in st and "s" not in st:
                    continue
                out[f"{self._shape_sig(shape)}|{bucket}"] = {
                    "b": st.get("b"), "s": st.get("s"),
                    "inel": st.get("inel", 0)}
        return {"v": 1, "entries": out}

    def load_path_model(self, data):
        """Install a save_path_model payload as seeds. Every VALUE is
        sanitized here — a truncated/hand-edited/foreign file must
        degrade to 'no seed for that shape', never to a per-query
        exception inside _seed_path_stat."""
        try:
            if data.get("v") != 1:
                return
            entries = data["entries"]
            seed = {}
            for k, v in entries.items():
                if not (isinstance(k, str) and isinstance(v, dict)):
                    continue
                clean = {}
                for arm in ("b", "s"):
                    val = v.get(arm)
                    if isinstance(val, (int, float)) and val > 0:
                        clean[arm] = float(val)
                inel = v.get("inel", 0)
                if isinstance(inel, int) and inel > 0:
                    clean["inel"] = inel
                if clean:
                    seed[k] = clean
            self._path_seed = seed
        except (AttributeError, KeyError, TypeError):
            pass

    def path_model_snapshot(self):
        """Per-shape path-model stats for /debug/vars: readable call
        signature + slice bucket → query count and best times."""
        out = {}
        with self._path_mu:
            for (shape, bucket), st in self._path_stats.items():
                out[f"{self._shape_sig(shape)}/2^{bucket}slices"] = {
                    "queries": st.get("n", 0),
                    "batchedMs": (round(st["b"] * 1000, 3)
                                  if "b" in st else None),
                    "serialMs": (round(st["s"] * 1000, 3)
                                 if "s" in st else None),
                    # The looks at the loser (_run_path): attempts,
                    # those that did not finish, and their cumulative
                    # wall time.
                    "probes": st.get("probes", 0),
                    "probeAborts": st.get("probe_aborts", 0),
                    "probeMs": round(st.get("probe_s", 0.0) * 1000, 3),
                }
        return out

    def coalesce_snapshot(self):
        """Coalescer state for /debug/vars (countCoalescer group):
        resolved knobs plus the tick/fusion counters."""
        wait_s, group, comp_ok, densify = self._co_config()
        st = self._co_stats
        return {
            "enabled": self._co_enabled(),
            "maxWaitUs": int(wait_s * 1e6),
            "maxGroup": group,
            "compressed": comp_ok,
            "densifyBudgetBytes": densify,
            "rounds": st["rounds"],
            "fused_queries": st["fused_queries"],
            "compressedFusedQueries": st["compressed_fused"],
            "laneLaunches": st["lane_launches"],
            "densifiedBlocks": st["densified_blocks"],
            "expiredWaits": self._co_expired,
            "max_group": st["max_group"],
            "declined": dict(st["declined"]),
        }

    def coalesce_metrics(self):
        """Flat dict for the /metrics ``pilosa_coalesce_*`` group —
        always present (a zeroed group on an idle server, like
        plan_cache), with declines tagged by reason. The group-size
        distribution rides separately as the ``coalesce_group_size``
        histogram family."""
        st = self._co_stats
        out = {
            "enabled": 1 if self._co_enabled() else 0,
            "rounds_total": st["rounds"],
            "fused_queries_total": st["fused_queries"],
            "compressed_fused_queries_total": st["compressed_fused"],
            "lane_launches_total": st["lane_launches"],
            "densified_blocks_total": st["densified_blocks"],
            "expired_waits_total": self._co_expired,
            "max_group_size": st["max_group"],
        }
        for reason, n in sorted(st["declined"].items()):
            out[f"declined_total;reason:{reason}"] = n
        return out

    def _try_batch(self, batch_fn, node_slices):
        """Run a batched fast path defensively: its contract is
        return-None-when-ineligible, so an unexpected device error
        (jit failure, OOM) degrades to the serial per-slice loop rather
        than propagating — in multi-node mode an exception here would
        otherwise make the failover handler declare THIS node dead.
        Query-validation errors re-raise identically from the serial
        path, so swallowing here never changes the reported error."""
        try:
            out = batch_fn(node_slices)
            # Direct (unwindowed) callers treat over-budget as a plain
            # decline.
            return None if out is BATCH_OVER_BUDGET else out
        except Exception as e:
            logger.warning("batched path failed; falling back to "
                           "per-slice execution", exc_info=True)
            querystats.note_fallback("batched", "error")
            if "RESOURCE_EXHAUSTED" in str(e):
                self.oom_fallbacks += 1
                querystats.add("oomFallbacks")
            return BATCH_TRANSIENT

    def _node_is_down(self, node):
        ns = self.cluster.node_set if self.cluster else None
        return ns is not None and hasattr(ns, "is_down") and ns.is_down(
            node.host)

    def _without_open_breakers(self, nodes, index, slices):
        """Drop peers whose circuit breaker is open (qos.PeerBreakers
        on the internal client) from a fan-out node list — but only
        when the survivors still cover every slice; otherwise the
        open-breaker owner stays in and the query itself becomes its
        half-open probe. Returns ``(nodes, mapping-or-None)``: the
        coverage probe IS a full slice mapping, so the caller reuses
        it for its first fan-out round instead of partitioning twice.
        No breakers (the default) costs one attribute read."""
        brk = getattr(self.client, "breakers", None)
        if brk is None or self.cluster is None:
            return nodes, None
        filtered = self.cluster.healthy_nodes(nodes, keep_host=self.host)
        if len(filtered) == len(nodes) or not filtered:
            return nodes, None
        try:
            mapping = self._slices_by_node(filtered, index, slices)
        except SliceUnavailableError:
            return nodes, None
        return filtered, mapping

    SLICES_BY_NODE_MEMO_MAX = 16

    def _slices_by_node(self, nodes, index, slices):
        """(ref: slicesByNode executor.go:1424-1441).

        Memoized for the common case — the FULL contiguous slice range
        of an index partitioned over the current live node list, which
        every query recomputes identically (3.5 ms/query of pure
        partition looping at 954 slices, ~9 ms at 10B-column scale,
        profiled round 5). Keyed by (topology state, live-node hosts,
        index, first, last); non-contiguous inputs (failover remap
        subsets) compute unmemoized. The returned dict is fresh per
        call; its slice LISTS are shared with the memo and must not be
        mutated (no caller does — they fan out read-only)."""
        contiguous = False
        if len(slices) > 32 and slices[0] + len(slices) - 1 == slices[-1]:
            # Exact check in C — a Python element scan would cost the
            # milliseconds the memo exists to save. Span/length alone
            # is NOT sufficient (e.g. [0, 2, 2] spans like [0, 1, 2]
            # but routes differently).
            arr = np.asarray(slices)
            contiguous = bool(
                np.array_equal(arr, np.arange(arr[0], arr[-1] + 1)))
        key = None
        if contiguous:
            cl = self.cluster
            key = (cl.topology_state(),
                   tuple(n.host for n in nodes), index,
                   slices[0], slices[-1])
            memo = getattr(self, "_sbn_memo", None)
            if memo is None:
                memo = self._sbn_memo = {}
            hit = memo.get(key)
            if hit is not None:
                return dict(hit)
        m = {}
        for s in slices:
            for node in self.cluster.fragment_nodes(index, s):
                if node in nodes:
                    m.setdefault(node, []).append(s)
                    break
            else:
                raise SliceUnavailableError()
        if key is not None:
            if len(memo) >= self.SLICES_BY_NODE_MEMO_MAX:
                memo.clear()
            memo[key] = m
            return dict(m)
        return m

    def _route_slices_by_node(self, nodes, index, slices):
        """Replica-aware slice→node mapping ([cluster]
        replica-routing): each slice's read-valid owner candidates
        (cluster.read_owner_candidates — full replica set in steady
        state, preferred owner mid-resize) are ranked by live replica
        vitals (hedge.Hedger.rank: p99 / error EWMA / in-flight /
        degraded, local host nudged ahead), and the slice goes to the
        best serveable candidate present in ``nodes``. Cold vitals
        and score ties fall back deterministically to the owner-tuple
        order — i.e. exactly ``_slices_by_node``. Unmemoized by
        design: the scores are live (the vitals read itself is
        memoized ~250 ms inside the hedger); the per-slice owner
        lookups ride the fragment_nodes cache like the legacy path."""
        hedger = self.hedger
        cl = self.cluster
        by_host = {n.host: n for n in nodes}
        m = {}
        rank_memo = {}
        rerouted = set()
        for s in slices:
            cands = cl.read_owner_candidates(index, s)
            key = tuple(n.host for n in cands)
            order = rank_memo.get(key)
            if order is None:
                order = rank_memo[key] = [
                    h for h, _inputs in hedger.rank(key, self.host)]
            chosen = None
            for h in order:
                node = by_host.get(h)
                if node is None:
                    continue
                if h != self.host and not hedger.peer_serveable(h):
                    continue
                chosen = node
                break
            if chosen is None:
                # No ranked candidate is usable (all breaker-open /
                # stale, or candidates collapsed mid-resize): the
                # legacy first-present-owner rule, so routing can only
                # ever widen the serveable set, never shrink it.
                for node in cl.fragment_nodes(index, s):
                    if node in nodes:
                        chosen = node
                        break
            if chosen is None:
                raise SliceUnavailableError()
            if key and chosen.host != key[0]:
                rerouted.add(key)
            m.setdefault(chosen, []).append(s)
        for _ in rerouted:
            # One count per owner-tuple DECISION, not per slice — a
            # 9.5k-slice index must not mint 9.5k counter bumps.
            hedger.on_routed_non_preferred()
        return m

    # -------------------------------------------------------- bitmap ops

    def _execute_bitmap_call(self, index, call, slices, opt):
        """(ref: executeBitmapCall executor.go:241-306)."""
        pl = self.planner
        if (call.children and slices and pl.active()
                and call.name in self._BATCH_OPS):
            # Selectivity reordering applies to materializing bitmap
            # queries too (intersect/union are commutative — the
            # result is identical, the intermediates shrink). A
            # statically-empty tree serves an empty bitmap with zero
            # kernels. Tier overrides stay Count-only: this path's
            # batched-vs-serial choice is the generic path model's.
            planned = pl.plan_count(self, index, call, slices)
            if planned is not None:
                if planned["staticEmpty"]:
                    pl.note_static_empty()
                    querystats.note_tier("planner")
                    return Bitmap()
                call = planned["child"]

        def map_fn(s):
            return self._execute_bitmap_call_slice(index, call, s)

        def reduce_fn(prev, v):
            if prev is None:
                prev = Bitmap()
            return prev.merge(v)

        # Compound trees materialize this host's slices as one fused
        # sharded program; segments stay device-resident.
        batch_fn = None
        if call.children:
            batch_fn = self._windowed_batch(
                lambda ns: self._batched_bitmap(index, call, ns), reduce_fn)
        bm = self._map_reduce(index, slices, call, opt, map_fn, reduce_fn,
                              batch_fn=batch_fn)
        if bm is None:
            bm = Bitmap()
        if call.name == "Bitmap":
            if opt.exclude_attrs:
                bm.attrs = {}
            else:
                bm.attrs = self._bitmap_attrs(index, call)
        if opt.exclude_bits:
            bm.segments = {}  # setter invalidates the pre-seeded count
        return bm

    def _bitmap_attrs(self, index, call):
        idx = self.holder.index(index)
        col_id, col_ok = call.uint_arg(idx.column_label)
        if col_ok:
            return idx.column_attr_store.attrs(col_id)
        frame = idx.frame(call.args.get("frame") or DEFAULT_FRAME)
        if frame is not None:
            row_id, row_ok = call.uint_arg(frame.row_label)
            if row_ok:
                return frame.row_attr_store.attrs(row_id)
        return {}

    def _execute_bitmap_call_slice(self, index, call, slice_num):
        """(ref: executeBitmapCallSlice executor.go:308-326)."""
        name = call.name
        if name == "Bitmap":
            return self._execute_bitmap_slice(index, call, slice_num)
        if name == "Range":
            return self._execute_range_slice(index, call, slice_num)
        if name in ("Intersect", "Union", "Difference", "Xor"):
            if not call.children:
                raise ValueError(
                    f"empty {name} query is currently not supported")
            out = None
            for child in call.children:
                bm = self._execute_bitmap_call_slice(index, child, slice_num)
                if out is None:
                    out = bm
                elif name == "Intersect":
                    out = out.intersect(bm)
                elif name == "Union":
                    out = out.union(bm)
                elif name == "Difference":
                    out = out.difference(bm)
                else:
                    out = out.xor(bm)
            return out
        raise ValueError(f"unknown call: {name}")

    def _bitmap_row(self, index, call):
        """(frame name, view, row id in that view) a ``Bitmap()`` call
        names: ``rowID`` reads the standard view, ``columnID`` the
        inverse one. Raises what the reference raises for a call it
        refuses (executeBitmapSlice executor.go:523-568)."""
        idx = self.holder.index(index)
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()
        row_id, row_ok = call.uint_arg(frame.row_label)
        col_id, col_ok = call.uint_arg(idx.column_label)
        if row_ok and col_ok:
            raise ValueError(
                f"Bitmap() cannot specify both {frame.row_label} and "
                f"{idx.column_label} values")
        if not row_ok and not col_ok:
            raise ValueError(
                f"Bitmap() must specify either {frame.row_label} or "
                f"{idx.column_label} values")
        if col_ok:
            if not frame.inverse_enabled:
                raise ValueError("Bitmap() cannot retrieve columns unless "
                                 "inverse storage enabled")
            return frame_name, VIEW_INVERSE, col_id
        return frame_name, VIEW_STANDARD, row_id

    def _execute_bitmap_slice(self, index, call, slice_num):
        """(ref: executeBitmapSlice executor.go:523-568)."""
        frame_name, view, id_ = self._bitmap_row(index, call)
        frag = self.holder.fragment(index, frame_name, view, slice_num)
        if frag is None:
            return Bitmap()
        if containers_mod.enabled():
            # Compressed serving tier: the fragment picks the row's
            # format from its density stats; the Bitmap's algebra is
            # format-polymorphic (bitops.dispatch_*), so downstream
            # code — including Count's no-materialize fast path —
            # needs no per-format branches here.
            return Bitmap.from_device(slice_num, frag.row_container(id_))
        return Bitmap.from_device(slice_num, frag.device_row(id_))

    def _execute_range_slice(self, index, call, slice_num):
        """Time range or BSI condition (ref: executeRangeSlice
        executor.go:593-680)."""
        if call.has_condition_arg():
            return self._execute_field_range_slice(index, call, slice_num)

        idx = self.holder.index(index)
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()
        col_id, col_ok = call.uint_arg(idx.column_label)
        row_id, row_ok = call.uint_arg(frame.row_label)
        if col_ok and row_ok:
            raise ValueError(
                f'Range() cannot contain both "{idx.column_label}" and '
                f'"{frame.row_label}"')
        if not col_ok and not row_ok:
            raise ValueError(
                f'Range() must specify either "{idx.column_label}" or '
                f'"{frame.row_label}"')
        view_name, id_ = ((VIEW_INVERSE, col_id) if col_ok
                          else (VIEW_STANDARD, row_id))

        start = call.args.get("start")
        if not isinstance(start, str):
            raise ValueError("Range() start time required")
        end = call.args.get("end")
        if not isinstance(end, str):
            raise ValueError("Range() end time required")
        try:
            start_t = datetime.strptime(start, TIME_FORMAT)
        except ValueError:
            raise ValueError("cannot parse Range() start time")
        try:
            end_t = datetime.strptime(end, TIME_FORMAT)
        except ValueError:
            raise ValueError("cannot parse Range() end time")

        if not frame.time_quantum:
            return Bitmap()
        bm = Bitmap()
        for view in tq.views_by_time_range(view_name, start_t, end_t,
                                           frame.time_quantum):
            frag = self.holder.fragment(index, frame_name, view, slice_num)
            if frag is None:
                continue
            bm = bm.union(Bitmap.from_device(slice_num, frag.device_row(id_)))
        return bm

    def _execute_field_range_slice(self, index, call, slice_num):
        """(ref: executeFieldRangeSlice executor.go:682-819)."""
        idx = self.holder.index(index)
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()
        args = {k: v for k, v in call.args.items() if k != "frame"}
        if not args:
            raise ValueError("Range(): condition required")
        if len(args) > 1:
            raise ValueError("Range(): too many arguments")
        field_name, cond = next(iter(args.items()))
        if not isinstance(cond, Condition):
            raise ValueError(
                f'Range(): "{field_name}": expected condition argument, '
                f"got {cond}")

        field = frame.field(field_name)
        depth = field.bit_depth()
        frag = self.holder.fragment(index, frame_name,
                                    view_field_name(field_name), slice_num)

        def not_null():
            if frag is None:
                return Bitmap()
            return Bitmap.from_host_words(slice_num, frag.field_not_null(depth))

        if cond.op == "!=" and cond.value is None:
            return not_null()

        if cond.op == "><":
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise ValueError("Range(): BETWEEN condition requires exactly "
                                 "two integer values")
            lo, hi, out_of_range = field.base_value_between(*predicates)
            if out_of_range:
                return Bitmap()
            if frag is None:
                return Bitmap()
            if predicates[0] <= field.min and predicates[1] >= field.max:
                return not_null()
            return Bitmap.from_host_words(
                slice_num, frag.field_range_between(depth, lo, hi))

        if isinstance(cond.value, bool) or not isinstance(cond.value, int):
            raise ValueError("Range(): conditions only support integer values")
        value = cond.value
        base, out_of_range = field.base_value(cond.op, value)
        if out_of_range and cond.op != "!=":
            return Bitmap()
        if frag is None:
            return Bitmap()
        if ((cond.op == "<" and value > field.max)
                or (cond.op == "<=" and value >= field.max)
                or (cond.op == ">" and value < field.min)
                or (cond.op == ">=" and value <= field.min)):
            return not_null()
        if out_of_range and cond.op == "!=":
            return not_null()
        return Bitmap.from_host_words(
            slice_num, frag.field_range(cond.op, depth, base))

    # ------------------------------------------------------------- count

    def _scalar_result_memo(self, kind, index, call, slices, opt,
                            compute, enc, dec):
        """Whole-result memo for scalar aggregates (Count / Sum / Min /
        Max / full TopN): a warm repeated dashboard query replays a
        host value instead of re-dispatching the fused device program
        (a device round trip per query), or a full cluster fan-out on
        multi-node. Validity
        is epoch-scoped to the query's index: the process-local epoch
        when the query resolves entirely locally, the distributed
        epoch VECTOR over the owning nodes (cluster/epochs.py) on a
        cluster — a None token (unknown/stale peer) computes without
        memoizing, cold but never stale."""
        from pilosa_tpu.storage import fragment as _frag

        local_only = (self.cluster is None
                      or len(self.cluster.nodes) <= 1
                      or self.client is None)
        # (The memo-read kill switches — PILOSA_TPU_RESULT_MEMO=0 and
        # a pinned _force_path — live in _result_memo_get, shared with
        # the topnc candidate memo; the same condition here also skips
        # the WRITE so benchmark runs don't pollute the cache.)
        if (opt.remote or self._result_memo_off
                or getattr(self, "_force_path", None) is not None
                or (not local_only and self.epochs is None)):
            return compute()
        # Compact slice key (plancache.slice_key): hashing the full
        # slices tuple cost ~0.5 ms/query at 9,540 slices — the single
        # largest warm engine-path item profiled at 10B scale.
        with tracing.span("result.memo") as msp:
            pkey = (kind, index, str(call), slice_key(slices))
            hit = self._result_memo_get(pkey)
            if msp is not tracing.NOP_SPAN:
                msp.tag(kind=kind, hit=hit is not None)
        if hit is not None:
            # Tier attribution: a memo replay never reaches the
            # mesh/coalesce/batched decision chain — "memo" is the
            # whole story for this call.
            querystats.note_tier("memo")
            return dec(hit)
        if local_only:
            epoch = _frag.mutation_epoch(index)
        else:
            # Token read BEFORE the fan-out (a write landing mid-query
            # makes the entry stale-on-arrival, never wrong). No probe
            # here: the fan-out's own responses refresh the registry,
            # so at worst the FIRST query after a visibility lapse
            # skips memoization.
            epoch = self.epochs.token(
                index, self._owner_hosts(index, slices))
        out = compute()
        if epoch is not None:
            self._topn_counts_memoize(pkey, enc(out), epoch)
        return out

    def _owner_hosts(self, index, slices):
        """Hosts owning any of ``slices`` (+ this host), cached in the
        plan cache against the cluster topology state — per-slice
        fragment_nodes lookups per memo write would cost milliseconds
        at 10k-slice scale. Formerly an ad-hoc FIFO 64-entry dict;
        now one LRU/invalidation path with the other plan tiers (a
        topology change — membership, replica count, or a placement
        phase change during an elastic resize — rotates the token and
        every owner entry lazily recomputes). Mid-resize the owner set
        is the UNION of both generations (fragment_nodes), so result-
        memo tokens cover every node whose data could serve the query."""
        state = self.cluster.topology_state()
        key = ("owners", index, slice_key(slices))
        hit = self.plans.get(key, state)
        if hit is not None:
            return hit
        hosts = {self.host}
        for s in slices:
            for n in self.cluster.fragment_nodes(index, s):
                hosts.add(n.host)
        hit = tuple(sorted(hosts))
        self.plans.put(key, state, hit)
        return hit

    def _execute_count(self, index, call, slices, opt):
        """(ref: executeCount executor.go:859-889)."""
        if len(call.children) != 1:
            raise ValueError("Count() only accepts a single bitmap input")

        child = call.children[0]
        # Planner pass (planner.py): selectivity-ordered rewrite,
        # short-circuit verdicts, and the learned tier decision —
        # memoized, so a warm query pays one dict hit. None =
        # unplannable; the pre-planner path runs untouched.
        pl = self.planner
        with tracing.span("count.plan") as psp:
            planned = (pl.plan_count(self, index, child, slices)
                       if pl.active() and slices else None)
            if planned is not None and planned["staticEmpty"]:
                # Plan-time short-circuit: a statically-empty subtree
                # (the BSI out-of-range shortcut) zeroes the whole
                # count. No kernel, no fan-out — the plan derives
                # from schema facts every node shares.
                pl.note_static_empty()
                querystats.note_tier("planner")
                return 0
            child2 = planned["child"] if planned is not None else child
            use_sc = (planned is not None and planned["sc"]
                      and pl.short_circuit)
            tier, forced_record = (
                pl.decide_tier(self, planned)
                if planned is not None else (None, False))
            if psp is not tracing.NOP_SPAN:
                psp.tag(tier=tier or "static")

        def map_fn(s):
            if use_sc:
                return self._count_planned_slice(index, child2, s)
            return self._count_call_slice(index, child2, s)

        # batch_fn: this host's slice set as ONE fused XLA program over
        # a [n_slices, W] stack sharded across local devices, instead of
        # a kernel launch per (slice × tree node); oversized slice
        # lists stream through budget-sized windows. The planner's
        # tier override rewires it: "serial" drops the batched path
        # entirely (the ordered short-circuit loop serves), "batched"
        # bypasses the coalescer tick for a direct single-query fused
        # program; None keeps the static chain.
        reduce_fn = lambda prev, v: (prev or 0) + v  # noqa: E731

        if tier == "serial":
            batch_fn = None
        elif tier == "batched":
            batch_fn = self._windowed_batch(
                lambda ns: self._batched_count(index, child2, ns),
                reduce_fn)
        else:
            batch_fn = self._windowed_batch(
                lambda ns: self._coalesced_count(index, child2, ns),
                reduce_fn)
        if tier is not None:
            # The divergence is part of the query's narrative: the
            # static chain's tier declined nothing — the planner
            # routed around it.
            querystats.note_fallback(planned["static"], "planner")

        def run():
            return self._map_reduce(
                index, slices, call, opt, map_fn, reduce_fn,
                batch_fn=batch_fn) or 0

        def compute():
            # Cost-model calibration (observe/costmodel.py): sampled
            # engine Counts predict their cost per tier BEFORE
            # executing, then record predicted-vs-measured for the
            # tier that actually served (the querystats tier stamps
            # identify it). Inspected queries always record; the rest
            # 1-in-STRIDE — the disabled path is one attribute read.
            # Planner-overridden (and exploration) serves ALWAYS
            # record: the measured-history medians are what correct a
            # mispredicted override, so it cannot starve itself of
            # the evidence that would revert it.
            # Sampling is LOCAL-ONLY when it would have to install
            # its own accumulator: an active scope makes every
            # fan-out leg stamp X-Pilosa-Collect-Stats, which
            # bypasses the peers' response caches — a sampled
            # UNINSPECTED query must never change cluster serving.
            cm = costmodel_mod.ACTIVE
            if not (cm.enabled and slices
                    and (forced_record or cm.should_record())):
                return run()
            if (querystats.active() is None and not opt.remote
                    and self.cluster is not None
                    and len(self.cluster.nodes) > 1
                    and self.client is not None):
                return run()
            with tracing.span("costmodel.estimate"):
                est = cm.estimate_count(self, index, child, slices)
            qs0 = querystats.active()
            qs = qs0 if qs0 is not None else querystats.QueryStats()
            # Per-CALL mark: an inspected multi-call request's
            # accumulator already holds earlier calls' tier stamps —
            # THIS Count's sample must calibrate the tier that served
            # THIS call, not the request's precedence winner.
            mark = qs.mark()
            t0 = time.perf_counter()
            if qs0 is None:
                with querystats.scope(qs):
                    out = run()
            else:
                out = run()
            elapsed = time.perf_counter() - t0
            with tracing.span("costmodel.record"):
                cm.record_count(est, qs.served_since(mark), elapsed)
            return out

        return self._scalar_result_memo(
            "count_res", index, call, slices, opt, compute,
            enc=lambda v: np.asarray([v], dtype=np.int64),
            dec=lambda a: int(a[0]))

    _COUNT_OPS = {"Intersect": "and", "Union": "or",
                  "Difference": "andnot", "Xor": "xor"}

    def _count_call_slice(self, index, call, slice_num):
        """Count-only per-slice evaluation: a two-operand boolean node
        reduces through ``Bitmap.op_count`` (bitops.dispatch_count
        under the hood — compressed operands run their registered
        count kernels, and nothing dense is materialized for the
        result; the reference's count fast paths, roaring.go:
        1811-1923). Anything else materializes and counts, exactly as
        before — dense×dense dispatch IS the pre-existing fused
        popcount, so results are bit-identical either way."""
        op = self._COUNT_OPS.get(call.name)
        if op is not None and len(call.children) == 2:
            a = self._execute_bitmap_call_slice(
                index, call.children[0], slice_num)
            b = self._execute_bitmap_call_slice(
                index, call.children[1], slice_num)
            return a.op_count(op, b)
        return self._execute_bitmap_call_slice(
            index, call, slice_num).count()

    def _count_planned_slice(self, index, call, slice_num):
        """Count-only per-slice evaluation of a planner-ordered
        commutative chain, with runtime short-circuits: the operands
        arrive smallest-estimated-first, the running Intersect
        intermediate is checked for emptiness before every further
        operand (container cardinalities are host-known — the check
        is free on the compressed shapes this path engages for), and
        the final operand reduces through the count-only kernel
        without materializing. An empty intermediate returns without
        touching the remaining siblings — their containers are never
        fetched and no kernel launches for the killed branch."""
        if call.name == "Intersect" and len(call.children) >= 2:
            kids = call.children
            acc = self._sc_bitmap_slice(index, kids[0], slice_num)
            for ch in kids[1:-1]:
                if acc.count() == 0:
                    self.planner.note_shortcircuit("intersect_empty")
                    return 0
                acc = acc.intersect(
                    self._sc_bitmap_slice(index, ch, slice_num))
            if acc.count() == 0:
                self.planner.note_shortcircuit("intersect_empty")
                return 0
            return acc.op_count(
                "and", self._sc_bitmap_slice(index, kids[-1],
                                             slice_num))
        if call.name == "Union" and len(call.children) >= 2:
            return self._sc_bitmap_slice(index, call,
                                         slice_num).count()
        return self._count_call_slice(index, call, slice_num)

    def _sc_bitmap_slice(self, index, call, slice_num):
        """Bitmap-producing twin of _count_planned_slice for NESTED
        planner-ordered nodes: an Intersect chain stops the moment
        its intermediate goes empty (the result IS that empty
        bitmap), a Union chain stops the moment it saturates the
        slice (the full/complement identity — nothing further can
        change a full slice). Everything else — leaves, Difference,
        Xor — evaluates exactly as the pre-planner path."""
        name = call.name
        if name == "Intersect" and len(call.children) >= 2:
            out = self._sc_bitmap_slice(index, call.children[0],
                                        slice_num)
            for ch in call.children[1:]:
                if out.count() == 0:
                    self.planner.note_shortcircuit("intersect_empty")
                    return out
                out = out.intersect(
                    self._sc_bitmap_slice(index, ch, slice_num))
            return out
        if name == "Union" and len(call.children) >= 2:
            out = None
            for ch in call.children:
                if out is not None and out.count() >= SLICE_WIDTH:
                    self.planner.note_shortcircuit("union_full")
                    return out
                bm = self._sc_bitmap_slice(index, ch, slice_num)
                out = bm if out is None else out.union(bm)
            return out
        return self._execute_bitmap_call_slice(index, call, slice_num)

    # ------------------------------------------- batched mesh fast path

    _BATCH_OPS = ("Union", "Intersect", "Difference", "Xor")

    def _plan_memoized(self, index, call):
        """(plan, leaves) for ``call`` via the plan cache — the
        batched-dispatch plan lookup that runs BEFORE _local_exec's
        device work. The AST → plan walk re-derives frame/field
        schema per query; schema mutations (frame/field DDL, writes
        creating views/fragments) bump the index epoch, so epoch
        equality validates the memo. Ineligible (None) plans are not
        cached — schema can appear at any moment and the declined
        walk is cheap. Returns a fresh leaves list (callers extend
        it); the plan tuple itself is immutable and shared."""
        from pilosa_tpu.storage import fragment as _frag

        key = ("ast", index, str(call))
        epoch = _frag.mutation_epoch(index)
        hit = self.plans.get(key, epoch)
        if hit is not None:
            return hit[0], list(hit[1])
        leaves = []
        plan = self._batched_plan(index, call, leaves)
        if plan is not None:
            self.plans.put(key, epoch, (plan, tuple(leaves)))
        return plan, leaves

    def _batched_plan(self, index, call, leaves):
        """AST → nested op tuples with leaf indices, or None when the
        tree contains shapes the batched path doesn't cover (invalid
        arg combinations surface their errors from the serial path).
        Bitmap leaves carry their own orientation: columnID leaves read
        the inverse view, exactly like executeBitmapSlice. Time Ranges
        expand to a Union over the time-view cover's leaves, at a
        bucketed width (_cover_bucket); BSI conditions plan via
        _plan_bsi_range."""
        if call.name == "Bitmap":
            idx = self.holder.index(index)
            frame_name = call.args.get("frame") or DEFAULT_FRAME
            frame = idx.frame(frame_name)
            if frame is None:
                return None
            row_id, row_ok = call.uint_arg(frame.row_label)
            col_id, col_ok = call.uint_arg(idx.column_label)
            if row_ok and not col_ok:
                leaves.append(("row", frame_name, row_id, VIEW_STANDARD))
            elif col_ok and not row_ok and frame.inverse_enabled:
                leaves.append(("row", frame_name, col_id, VIEW_INVERSE))
            else:
                # both/neither id or inverse storage disabled: the
                # serial path raises the reference's error messages.
                return None
            return ("leaf", len(leaves) - 1)
        if call.name == "Range" and call.has_condition_arg():
            return self._plan_bsi_range(index, call, leaves)
        if call.name == "Range":
            # Time range = Union over the minimal time-view cover
            # (ref: executeRangeSlice executor.go:665-675 +
            # ViewsByTimeRange time.go:112-184): each cover view is
            # just another leaf stack.
            idx = self.holder.index(index)
            frame_name = call.args.get("frame") or DEFAULT_FRAME
            frame = idx.frame(frame_name)
            if frame is None or not frame.time_quantum:
                return None
            row_id, row_ok = call.uint_arg(frame.row_label)
            _, col_ok = call.uint_arg(idx.column_label)
            if not row_ok or col_ok:
                return None
            start, end = call.args.get("start"), call.args.get("end")
            if not (isinstance(start, str) and isinstance(end, str)):
                return None  # serial path raises the proper error
            try:
                start_t = datetime.strptime(start, TIME_FORMAT)
                end_t = datetime.strptime(end, TIME_FORMAT)
            except ValueError:
                return None
            with tracing.span("range.cover", frame=frame_name) as csp:
                views = tq.views_by_time_range(VIEW_STANDARD, start_t,
                                               end_t, frame.time_quantum)
                # Union is idempotent: the slots past the cover hold
                # its views again, so the plan's text (and the program
                # it keys) depends on the bucket alone.
                width = self._cover_bucket(len(views)) if views else 0
                csp.tag(views=len(views), operands=width)
            if not views:
                return None
            querystats.add("rangeCoverViews", len(views))
            querystats.add("rangeCoverOperands", width)
            with self._cache_mu:
                self.range_cover["rangeCoverViews"] += len(views)
                self.range_cover["rangeCoverOperands"] += width
            kids = []
            for k in range(width):
                leaves.append(("row", frame_name, row_id,
                               views[k % len(views)]))
                kids.append(("leaf", len(leaves) - 1))
            return ("Union", kids)
        if call.name in self._BATCH_OPS and call.children:
            kids = []
            for c in call.children:
                node = self._batched_plan(index, c, leaves)
                if node is None:
                    return None
                kids.append(node)
            return (call.name, kids)
        return None

    def _plan_bsi_range(self, index, call, leaves):
        """BSI condition → a "bsi" node over a planes-stack spec, with
        the serial path's out-of-range/not-null shortcuts folded in at
        plan time (they depend only on field/op/value, never the slice
        — executeFieldRangeSlice executor.go:682-819). Predicate bits
        ride as array args so distinct values share one executable:
        host int32 arrays, uploaded by the call that reads them."""
        idx = self.holder.index(index)
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        if frame is None:
            return None
        args = {k: v for k, v in call.args.items() if k != "frame"}
        if len(args) != 1:
            return None  # serial path raises the proper error
        field_name, cond = next(iter(args.items()))
        if not isinstance(cond, Condition):
            return None
        try:
            field = frame.field(field_name)
        except perr.ErrFieldNotFound:
            return None
        depth = field.bit_depth()

        def _pos(spec):
            # Dedup: N conditions on one field share one stack arg (the
            # cache would dedup device memory anyway, but the budget
            # and the jit signature should not be over-charged).
            if spec in leaves:
                return leaves.index(spec)
            leaves.append(spec)
            return len(leaves) - 1

        def planes_pos():
            return _pos(("planes", frame_name, field_name, depth))

        def notnull_node():
            # The exists plane IS row `depth` of the field view — an
            # ordinary (cached) row leaf, no plane matrix needed.
            return ("leaf", _pos(("row", frame_name, depth,
                                  view_field_name(field_name))))

        def bits_pos(value):
            return _pos(("bits", tuple((value >> i) & 1
                                       for i in range(depth)), depth))

        if cond.op == "!=" and cond.value is None:
            return notnull_node()
        if cond.op == "><":
            try:
                predicates = cond.int_slice_value()
            except (TypeError, ValueError):
                return None
            if len(predicates) != 2:
                return None
            lo, hi, out_of_range = field.base_value_between(*predicates)
            if out_of_range:
                return ("empty",)
            if predicates[0] <= field.min and predicates[1] >= field.max:
                return notnull_node()
            return ("bsi", planes_pos(), (bits_pos(lo), bits_pos(hi)),
                    "between", "", depth)
        if isinstance(cond.value, bool) or not isinstance(cond.value, int):
            return None
        value = cond.value
        base, out_of_range = field.base_value(cond.op, value)
        if out_of_range and cond.op != "!=":
            return ("empty",)
        if ((cond.op == "<" and value > field.max)
                or (cond.op == "<=" and value >= field.max)
                or (cond.op == ">" and value < field.min)
                or (cond.op == ">=" and value <= field.min)
                or (out_of_range and cond.op == "!=")):
            return notnull_node()
        return ("bsi", planes_pos(), (bits_pos(base),), "cmp", cond.op,
                depth)

    def _batched_count(self, index, child, slices):
        """Count over the local slice list as one sharded XLA program.

        Leaf rows stack into ``uint32[n_slices, W]`` device arrays
        (device-resident already — the stack is an on-device op), the
        tree evaluates once with the slice axis sharded over every
        local device (`jax.sharding` inserts the collectives), and the
        kernel returns per-slice counts — the same map/reduce shape as
        the reference's mapperLocal + sum (executor.go:1537), minus
        n_slices × tree_depth kernel launches."""
        with tracing.span("plan_and_stage", slices=len(slices)):
            prelude = self._plan_and_stacks(index, child, slices)
        if prelude is None or prelude is BATCH_OVER_BUDGET:
            return prelude
        plan, stacks, padded_n, win = prelude

        # Cache key is the tree STRUCTURE (leaf slots, not leaf ids):
        # Count(Intersect(Bitmap(3), Bitmap(9))) reuses the executable
        # compiled for Count(Intersect(Bitmap(1), Bitmap(2))).
        obs = kerneltime_mod.ACTIVE
        # ONE plan stringification per query (the fn-cache key):
        # tuple repr is µs-scale, and the observatory's hit check
        # reuses it rather than paying a second pass.
        tree_key = str(plan)
        with tracing.span("kernel:count_batched", slices=len(slices),
                          width32=win[1]) as ksp:
            hit = True
            if ksp is not tracing.NOP_SPAN or obs.enabled:
                # First-compile vs steady-state attribution: a fn-cache
                # miss means this dispatch pays the XLA compile (the
                # cost the width warmer pre-pays off the serving path —
                # its _warm_stats success count rides along as context).
                # Lock-free racy membership read (GIL-atomic): a
                # concurrent insert misattributes at most one sample,
                # and taking _cache_mu here would tax every warm query.
                hit = (tree_key, padded_n, win[1]) in self._batched_cache
            if ksp is not tracing.NOP_SPAN:
                ksp.tag(first_compile=not hit,
                        warm_compiled=self._warm_stats["compiled"])
                with tracing.span("kernel.fn") as fsp:
                    fn = self._batched_fn(tree_key, plan, padded_n,
                                          win[1])
                    fsp.tag(compile=not hit)
                run = _run_count_split
            else:
                fn = self._batched_fn(tree_key, plan, padded_n, win[1])
                run = _run_count
            if not obs.enabled:
                counts = run(fn, stacks)
            else:
                # The batched tree program: one cost row per
                # (slice-count, width) shape class — np.asarray
                # blocks, so samples are device time. COMPILE
                # dispatches (fn-cache miss, known up front) always
                # record exactly; steady-state dispatches record
                # 1-in-OBS_STRIDE with scaled weight — the hit check
                # already ran, and the stride keeps two clock readings
                # and a locked note off fifteen warm queries in
                # sixteen (counts and sums scale, means stay true).
                self._obs_tick = w = self._obs_tick + 1
                w = 0 if w % self.OBS_STRIDE else self.OBS_STRIDE
                if not hit or w:
                    t0 = time.perf_counter()
                    counts = run(fn, stacks)
                    obs.note(
                        "count_batched", "dense*dense",
                        kerneltime_mod.shape_bucket(padded_n * win[1] * 4),
                        time.perf_counter() - t0, compiled=not hit,
                        device=True, n=(1 if not hit else w))
                else:
                    counts = run(fn, stacks)
                if not hit:
                    # Cache-size gauge stamped on compiles only —
                    # per-query introspection would tax the warm path.
                    try:
                        obs.note_jit_cache("count_batched",
                                           fn._cache_size())
                    except Exception:  # noqa: BLE001 — jit internals vary; pilint: disable=swallow
                        pass
                    if devprof_mod.ACTIVE.enabled:
                        # This dispatch already paid the XLA compile —
                        # the analytic flops/bytes capture (one extra
                        # lowering, once per cell) rides it, never
                        # steady state.
                        devprof_mod.ACTIVE.note_compile(
                            "count_batched", "dense*dense",
                            kerneltime_mod.shape_bucket(
                                padded_n * win[1] * 4), fn, stacks)
        self._warm_wider(tree_key, plan, padded_n, win[1], stacks)
        with tracing.span("reduce"):
            return int(counts[: len(slices)].sum())

    # ------------------------------------- cross-query count coalescing

    _CO_PENDING = object()   # sentinel: request not yet served

    def _co_enabled(self):
        """Coalescing pays when device dispatch overhead dominates and
        the device is a separate resource (TPU). On the CPU backend
        the fused program competes with serving threads for the same
        cores, so it defaults off there. PILOSA_TPU_COALESCE=1/0
        overrides either way."""
        cached = getattr(self, "_co_enabled_memo", None)
        if cached is None:
            import os as _os

            env = _os.environ.get("PILOSA_TPU_COALESCE")
            if env is not None:
                cached = env not in ("0", "false", "no")
            else:
                import jax

                cached = jax.default_backend() != "cpu"
            self._co_enabled_memo = cached
        return cached

    # --------------------------------- remote subquery batching

    def _rb_enabled(self):
        """Remote-subquery batching (group commit per peer): while one
        round trip to a node is in flight, concurrent queries' subcalls
        for the same (index, slices) accumulate and go out as ONE
        multi-call query when it returns — batching grows with load, a
        lone query pays no added latency (its batch is size 1, no
        timed wait). PQL queries are multi-call natively (results map
        by position), so the peer's executor serves the batch in one
        HTTP round trip — N concurrent cluster counts stop paying N
        RTTs per peer. PILOSA_TPU_REMOTE_BATCH=0 disables."""
        cached = getattr(self, "_rb_enabled_memo", None)
        if cached is None:
            import os as _os

            cached = _os.environ.get("PILOSA_TPU_REMOTE_BATCH", "1") \
                not in ("0", "false", "no")
            self._rb_enabled_memo = cached
        return cached

    # Distinct (host, index, slices) combinations each get their own
    # lane, so unrelated round trips stay CONCURRENT (a single
    # per-host lane would serialize different queries' RTTs behind one
    # leader); only same-group subcalls — the ones that can actually
    # fuse into one multi-call query — ever park behind each other.
    RB_LANES_MAX = 64

    def _remote_execute(self, node, index, call, node_slices):
        """One remote subcall's decoded result, via the per-(host,
        index, slices) batch lane (or directly when batching is off).
        The active trace context (when any) rides the request as
        X-Pilosa-Trace-Id/X-Pilosa-Span-Id so the remote node's spans
        stitch under this coordinator's fan-out span."""
        if not self._rb_enabled():
            with tracing.span("remote.round", host=node.host):
                return self.client.execute_query(
                    node, index, Query([call]), slices=node_slices,
                    remote=True,
                    trace_headers=tracing.trace_headers(),
                    deadline=qos.current_deadline())[0]
        lane_key = (node.host, index, slice_key(node_slices))
        with self._rb_lanes_mu:
            lane = self._rb_lanes.get(lane_key)
            if lane is None:
                if len(self._rb_lanes) >= self.RB_LANES_MAX:
                    # Bound the table: drop idle lanes (no leader, no
                    # parked requests) — e.g. stale failover-remap
                    # slice subsets that will never recur.
                    for k in [k for k, ln in self._rb_lanes.items()
                              if not ln["leader"] and not ln["pending"]]:
                        del self._rb_lanes[k]
                lane = self._rb_lanes[lane_key] = {
                    # NOT lockcheck-registered: lanes churn (bounded
                    # live at RB_LANES_MAX but re-minted over time),
                    # and the checker's registry is append-only.
                    "mu": threading.Lock(),
                    "cv": None, "pending": [], "leader": False}
                lane["cv"] = threading.Condition(lane["mu"])
        req = {"call": call, "out": self._CO_PENDING}
        with tracing.span("remote.round", host=node.host):
            with lane["mu"]:
                lane["pending"].append(req)
                while req["out"] is self._CO_PENDING and lane["leader"]:
                    lane["cv"].wait()
                if req["out"] is not self._CO_PENDING:
                    out = req["out"]
                    if isinstance(out, BaseException):
                        raise out
                    return out
                lane["leader"] = True
                batch = lane["pending"]
                lane["pending"] = []
            try:
                self._rb_run(node, index, list(node_slices), batch)
            finally:
                with lane["mu"]:
                    lane["leader"] = False
                    lane["cv"].notify_all()
            out = req["out"]
            if isinstance(out, BaseException):
                raise out
            return out

    def _hedge_candidates(self, index, node_slices, primary_host):
        """Hosts able to serve EVERY slice of a hedged leg: the
        intersection of each slice's read-valid owner candidates,
        minus the primary, in first-seen owner order. Rides the
        memoized fragment_nodes lookups."""
        common = None
        for s in node_slices:
            hosts = [n.host for n in
                     self.cluster.read_owner_candidates(index, s)
                     if n.host != primary_host]
            if common is None:
                common = hosts
            else:
                keep = set(hosts)
                common = [h for h in common if h in keep]
            if not common:
                return []
        return common or []

    def _hedge_predicted_s(self, index, call, node_slices):
        """Cost-model predicted http-tier seconds for one leg (the
        hedge trigger), or None — unplannable shapes fall back to the
        primary peer's observed p99 (hedge.Hedger.hedge_delay)."""
        try:
            cm = costmodel_mod.ACTIVE
            if (not cm.enabled or call.name != "Count"
                    or not call.children):
                return None
            est = cm.estimate_count(self, index, call.children[0],
                                    node_slices)
            if est:
                return est.get("tiers", {}).get("http")
        except Exception:  # noqa: BLE001 — a failed estimate must never fail the leg; pilint: disable=swallow
            pass
        return None

    def _hedged_remote_execute(self, node, index, call, node_slices,
                               session):
        """One remote leg under the tail-tolerant contract
        (cluster/hedge.py): dispatch to the primary owner, arm a
        hedge timer from the predicted latency (clamped into the
        remaining deadline's headroom), and when the primary runs
        late issue the SAME leg to the best epoch-valid alternate —
        first success wins, the loser is cancelled (accounting only:
        its vitals sample is suppressed via CancelBox). Suppression
        reasons (no candidates, all alternates degraded, budget or
        QoS saturation, no deadline headroom, request cap) fall back
        to the plain lane path at the full deadline. Hedge-eligible
        legs bypass the remote-subquery batch lanes: a shared lane
        RPC cannot carry per-leg cancellation accounting."""
        hedger = self.hedger
        deadline = qos.current_deadline()
        qstats_acc = querystats.active()

        def plain(reason, **fields):
            hedger.suppress(reason, **fields)
            if qstats_acc is not None:
                qstats_acc.note_hedge({
                    "host": node.host, "slices": len(node_slices),
                    "suppressed": reason})
            return self._remote_execute(node, index, call, node_slices)

        cands = [h for h in self._hedge_candidates(index, node_slices,
                                                   node.host)
                 if hedger.peer_serveable(h)]
        if not cands:
            return plain("no_candidates")
        ranked = hedger.rank(tuple(cands), self.host)
        target_host = next((h for h, inp in ranked
                            if not inp["degraded"]), None)
        if target_host is None:
            # Degradation ladder's last rung: every alternate is
            # watchdog-degraded — run un-hedged at the FULL deadline
            # rather than burn budget on a slow-for-slow trade.
            return plain("all_degraded", index=index, host=node.host,
                         slices=len(node_slices))
        target = self.cluster.node_by_host(target_host)
        if target is None:
            return plain("no_candidates")
        delay = hedger.hedge_delay(
            node.host, self._hedge_predicted_s(index, call, node_slices),
            deadline)
        if delay is None:
            return plain("deadline")

        hedger.on_armed()
        cv = threading.Condition()
        results = []   # (leg name, value, exc)
        boxes = {"primary": hedge_mod.CancelBox(),
                 "hedge": hedge_mod.CancelBox()}
        parent_span = tracing.active_span()

        def leg(who, leg_node):
            box = boxes[who]
            try:
                if who == "hedge" and faults.ACTIVE.enabled:
                    # Chaos points for the hedge leg itself: slow
                    # (the hedge loses its race) and error (the hedge
                    # dies — the primary's answer must win
                    # un-corrupted, gauges must settle).
                    faults.ACTIVE.fire("client.hedge.slow")
                    faults.ACTIVE.fire("client.hedge.error")
                with qos.deadline_scope(deadline), \
                        querystats.scope(qstats_acc), \
                        tracing.child_of(parent_span, f"remote.{who}",
                                         host=leg_node.host,
                                         slices=len(node_slices)):
                    out = self.client.execute_query(
                        leg_node, index, Query([call]),
                        slices=node_slices, remote=True,
                        trace_headers=tracing.trace_headers(),
                        deadline=qos.current_deadline(),
                        cancel_box=box)[0]
                res = (who, out, None)
            except Exception as exc:  # noqa: BLE001 — resolved by the race loop
                res = (who, None, exc)
            with cv:
                results.append(res)
                cv.notify_all()

        self._fan_pool.run(lambda: leg("primary", node))
        entry = {"host": node.host, "slices": len(node_slices),
                 "armedMs": round(delay * 1000.0, 3)}
        fired = False
        if lockcheck.ACTIVE.enabled:
            # Waiting out a hedged race while holding a registered
            # lock would convoy every query behind the slow replica.
            lockcheck.ACTIVE.io_point("client.hedge")
        with cv:
            if not results:
                cv.wait(delay)
            settled_early = bool(results)
        if not settled_early:
            ok, reason = hedger.admit_hedge(session)
            if ok:
                fired = True
                hedger.on_fired()
                entry["hedged"] = True
                entry["target"] = target_host
                self._fan_pool.run(lambda: leg("hedge", target))
            else:
                hedger.suppress(reason)
                entry["suppressed"] = reason
        want = 2 if fired else 1
        winner = None
        errs = {}
        seen = 0
        while winner is None:
            with cv:
                while len(results) <= seen:
                    budget = None
                    if deadline is not None:
                        budget = deadline - time.monotonic()
                        if budget <= 0:
                            break
                    cv.wait(budget)
                if len(results) <= seen:
                    # Deadline expired mid-race: the legs carry
                    # budget-bound socket timeouts and self-terminate.
                    if fired:
                        hedger.on_settled(hedge_won=False,
                                          hedge_errored=True)
                    raise qos.DeadlineExceeded()
                new, seen = results[seen:], len(results)
            for who, value, exc in new:
                if exc is None:
                    winner = (who, value)
                    break
                errs[who] = exc
            if winner is None and seen >= want:
                # Every dispatched leg failed: settle the gauges and
                # surface the PRIMARY error — it feeds the caller's
                # failover remap exactly like the un-hedged path.
                if fired:
                    hedger.on_settled(hedge_won=False,
                                      hedge_errored=True)
                entry["winner"] = "error"
                if qstats_acc is not None:
                    qstats_acc.note_hedge(entry)
                raise errs.get("primary", errs.get("hedge"))
        who, value = winner
        boxes["hedge" if who == "primary" else "primary"].cancelled = True
        if fired:
            hedger.on_settled(hedge_won=(who == "hedge"),
                              hedge_errored=("hedge" in errs))
        entry["winner"] = who
        if qstats_acc is not None:
            qstats_acc.note_hedge(entry)
        return value

    def _rb_run(self, node, index, slices, reqs):
        """Serve a drained lane batch (all same (index, slices)) as
        one multi-call query; on a batch failure every member retries
        SINGLY so one poisoned call (bad frame, etc.) cannot fail its
        siblings with the wrong error. EVERY slot is filled on every
        path — a request must never wake to the PENDING sentinel
        (the _co_run invariant)."""
        try:
            with self._rb_lanes_mu:
                self._rb_stats["rounds"] += 1
                if len(reqs) > 1:
                    self._rb_stats["batched_calls"] += len(reqs)
                    self._rb_stats["max_batch"] = max(
                        self._rb_stats["max_batch"], len(reqs))
            # The leader's trace context and deadline stamp the shared
            # round trip (followers' contexts can't all ride one
            # request; same-group deadlines are near-identical anyway).
            thdr = tracing.trace_headers()
            dl = qos.current_deadline()
            if len(reqs) > 1:
                try:
                    outs = self.client.execute_query(
                        node, index, Query([r["call"] for r in reqs]),
                        slices=slices, remote=True, trace_headers=thdr,
                        deadline=dl)
                    if len(outs) == len(reqs):
                        for req, out in zip(reqs, outs):
                            req["out"] = out
                        return
                except Exception:  # noqa: BLE001 — retried singly below; pilint: disable=swallow
                    pass
            for req in reqs:
                if req["out"] is not self._CO_PENDING:
                    continue
                try:
                    req["out"] = self.client.execute_query(
                        node, index, Query([req["call"]]),
                        slices=slices, remote=True, trace_headers=thdr,
                        deadline=dl)[0]
                except BaseException as exc:  # noqa: BLE001 — delivered
                    req["out"] = exc
        except BaseException as exc:  # noqa: BLE001 — e.g. SystemExit
            for req in reqs:
                if req["out"] is self._CO_PENDING:
                    req["out"] = exc
            raise

    def _coalesced_count(self, index, child, slices):
        """Group-commit coalescing for count-shaped batched dispatches.

        Python serving threads serialize on the GIL, so N concurrent
        Count queries used to pay N device dispatches back-to-back
        (round-2 measurement: QPS flat from 1 to 10 clients). Here a
        request either becomes the LEADER — drains every pending
        request and serves them — or parks until a leader serves it.
        While the leader's fused program runs (the GIL is released
        inside XLA), new arrivals accumulate and dispatch as the next
        single program: batching grows with load, and a lone query
        pays no added latency (its batch is size 1, no timed wait).
        The reference gets concurrency from goroutines-on-all-cores
        (server.go:205-217); this is the single-device answer.

        Same contract as _batched_count: int, None (structurally
        unbatchable) or BATCH_OVER_BUDGET."""
        if not self._co_enabled():
            return self._batched_count(index, child, slices)
        plan, leaves = self._plan_memoized(index, child)
        if plan is None:
            querystats.note_fallback("batched", "plan")
            return None
        if not self._co_tick_route(index, leaves, slices):
            return self._batched_count(index, child, slices)
        return self._co_submit({
            "key": ("count", index, slice_key(slices), str(plan)),
            "index": index, "slices": slices,
            "plan": plan, "leaves": leaves, "out": self._CO_PENDING,
            "single": lambda: self._batched_count(index, child, slices),
            "fuse": self._co_run_fused,
        })

    # ---------------------------------- tick config + admission policy

    # Per-group densify budget default (bytes of compressed rows the
    # fused path may stage densely for a DEEP all-compressed tree):
    # one group may re-densify at most this much HBM, and every
    # densified block ticks container_conversions_total so the churn
    # is observable. 64 MiB ≈ 512 full-width rows — generous for real
    # deep trees, tiny next to the stack budget.
    CO_DENSIFY_BYTES = 64 << 20

    def _co_config(self):
        """(max_wait_s, max_group, compressed_ok, densify_bytes) for
        the batching tick — [executor] coalesce-max-wait-us /
        coalesce-max-group / coalesce-compressed /
        coalesce-densify-bytes via set_coalesce_config (server
        wiring), PILOSA_COALESCE_* env for bare construction.
        Memoized; malformed env keeps the default (the
        PILOSA_PLAN_CACHE_ENTRIES discipline)."""
        cached = getattr(self, "_co_config_memo", None)
        if cached is None:
            import os as _os

            def _num(name, default, cast):
                raw = _os.environ.get(name)
                if not raw:
                    return default
                try:
                    return cast(raw)
                except ValueError:
                    logger.warning("ignoring %s=%r (want a number)",
                                   name, raw)
                    return default

            wait_us = max(0, _num("PILOSA_COALESCE_MAX_WAIT_US", 0, int))
            group = max(1, _num("PILOSA_COALESCE_MAX_GROUP", 64, int))
            comp = _os.environ.get("PILOSA_COALESCE_COMPRESSED", "")
            comp_ok = comp.lower() not in ("0", "false", "no", "off")
            densify = max(0, _num("PILOSA_COALESCE_DENSIFY_BYTES",
                                  self.CO_DENSIFY_BYTES, int))
            cached = (wait_us / 1e6, group, comp_ok, densify)
            self._co_config_memo = cached
        return cached

    def set_coalesce_config(self, max_wait_us=None, max_group=None,
                            compressed=None, densify_bytes=None):
        """Server wiring for the [executor] coalesce knobs — explicit
        values override the env/default resolution; None keeps each
        knob's current value."""
        wait_s, group, comp_ok, densify = self._co_config()
        if max_wait_us is not None:
            wait_s = max(0, int(max_wait_us)) / 1e6
        if max_group is not None:
            group = max(1, int(max_group))
        if compressed is not None:
            comp_ok = bool(compressed)
        if densify_bytes is not None:
            densify = max(0, int(densify_bytes))
        self._co_config_memo = (wait_s, group, comp_ok, densify)

    def _co_note_decline(self, reason, reqs=None):
        """Count one fusion decline by reason (the group then serves
        singly). Leader-only mutation; dict item writes are atomic
        under the GIL for the snapshot readers. ``reqs`` stamps the
        decline hop on each affected member's own query-stats
        accumulator — the per-query twin of the aggregate counter, so
        a specific slow query's reason is recoverable from its
        profile/slow-ring entry instead of only the fleet total."""
        d = self._co_stats["declined"]
        d[reason] = d.get(reason, 0) + 1
        for req in reqs or ():
            qs = req.get("qs")
            if qs is not None:
                qs.note_fallback("coalesce", reason)

    def _co_tick_route(self, index, leaves, slices):
        """True → submit to the batching tick; False → the direct
        single-query batched path. Accelerator backends tick
        EVERYTHING — device dispatch is the cost that inflates under
        concurrency there. On the CPU backend the fused program
        competes with serving threads for the same cores and the
        dense single-query path is already ONE dispatch (PR 6), so
        only compressed-tier plans — whose serial cost is one
        dispatch PER SLICE, the lane tier's win — enter the tick,
        probed cheaply on sample fragments per row leaf. This is
        ROUTING only (both paths are bit-exact): a mixed index that
        mis-samples merely fuses less. ``_co_route_all`` pins the
        tick-everything behavior (tests simulating accelerator
        dispatch economics on the CPU backend)."""
        if not containers_mod.lane_host_mode() or self._co_route_all:
            return True
        if not self._co_config()[2] or not slices:
            # Compressed fusion disabled → the pre-lane tick behavior
            # (the group declines and serves singly, as before).
            return True
        for sp in leaves:
            if sp[0] == "planes":
                return True  # BSI keeps the plane-sharing tick
            if sp[0] != "row":
                continue
            _, fname, rid, view = sp
            frag = None
            for s in (slices[0], slices[len(slices) // 2]):
                frag = self.holder.fragment(index, fname, view, s)
                if frag is not None:
                    break
            if frag is not None and not frag.row_compressed(rid):
                return False
        return True

    def _co_submit(self, req):
        """Queue one coalescable request through the batching tick:
        become the leader (admit and serve a priority-ordered batch)
        or park until a leader serves it. Shape-agnostic — requests
        carry their own ``single`` fallback and group ``fuse``
        function; grouping is by ``key``.

        Parked waits are bounded by the request's own deadline: an
        expired coalescee leaves the queue and raises (→ 504) without
        touching the rest of the group — unless a leader already
        claimed it, in which case that leader delivers (it checks
        expiry itself before fusing)."""
        req.setdefault("prio", qos.current_priority())
        req.setdefault("deadline", qos.current_deadline())
        # The submitting thread's query-stats accumulator rides the
        # request: the leader serves the whole group on ITS thread, so
        # per-member work (container resolution, stack staging, the
        # single-serve fallback) must be charged to the member that
        # asked for it — a parked coalescee's ?profile=true resources
        # and slow-ring entry reflect its own query's share, not zero,
        # and the leader's reflect only its own, not the whole batch.
        req.setdefault("qs", querystats.active())
        expired = False
        with self._co_mu:
            self._co_pending.append(req)
            if self._co_tick_waiting:
                # A leader is holding its accumulation window open —
                # wake it so a full batch can dispatch early.
                self._co_cv.notify_all()
            while req["out"] is self._CO_PENDING and self._co_leader:
                dl = req["deadline"]
                remaining = (None if dl is None
                             else dl - time.monotonic())
                if remaining is None or remaining > 0:
                    self._co_cv.wait(remaining)
                    continue
                # Expired while parked. Only unclaimed requests may
                # abandon the queue — once a leader drained us into
                # its batch, it owns delivery (result or the expiry
                # error) and we keep waiting for it.
                for i, r in enumerate(self._co_pending):
                    if r is req:
                        del self._co_pending[i]
                        expired = True
                        break
                if expired:
                    self._co_expired += 1
                    break
                self._co_cv.wait()
            if not expired:
                if req["out"] is not self._CO_PENDING:
                    out = req["out"]
                    if isinstance(out, BaseException):
                        raise out
                    return out
                # No active leader: this thread leads the next tick.
                self._co_leader = True
                batch = self._co_admit_locked(req)
        if expired:
            raise qos.DeadlineExceeded()
        try:
            self._co_run(batch)
        finally:
            with self._co_mu:
                self._co_leader = False
                self._co_cv.notify_all()
        out = req["out"]
        if isinstance(out, BaseException):
            raise out
        return out

    def _co_admit_locked(self, req):
        """Tick admission (caller holds ``_co_mu`` and leadership):
        optionally hold the window open (``coalesce-max-wait-us``,
        clipped to the smallest deadline headroom among waiters — a
        batch wait must never spend anyone's whole budget), then admit
        up to ``coalesce-max-group`` requests in QoS priority order
        (FIFO within a class) — interactive coalescees are never
        parked behind batch/ingest ones when the tick truncates. The
        leader's own request always admits (it must leave _co_submit
        with a settled slot); leftovers lead the next tick."""
        max_wait, max_group, _, _ = self._co_config()
        if max_wait > 0 and len(self._co_pending) < max_group:
            limit = time.monotonic() + max_wait
            self._co_tick_waiting = True
            try:
                while len(self._co_pending) < max_group:
                    # Recomputed per wake: a LATE arrival with tighter
                    # headroom (it notifies the tick) must cut the
                    # window short — the batch wait is bounded by the
                    # smallest remaining deadline in the group, not
                    # just the deadlines seen at tick start.
                    bound = limit
                    for r in self._co_pending:
                        if r["deadline"] is not None:
                            bound = min(bound, r["deadline"])
                    remaining = bound - time.monotonic()
                    if remaining <= 0:
                        break
                    self._co_cv.wait(remaining)
            finally:
                self._co_tick_waiting = False
        pending = self._co_pending
        order = sorted(
            (i for i, r in enumerate(pending) if r is not req),
            key=lambda i: (pending[i]["prio"], i))
        take = order[: max_group - 1]
        batch = [req] + [pending[i] for i in take]
        batch.sort(key=lambda r: r["prio"])  # stable: FIFO per class
        taken = set(take)
        self._co_pending = [r for i, r in enumerate(pending)
                            if i not in taken and r is not req]
        return batch

    def _co_run(self, batch):
        """Serve one tick's admitted batch: fuse same-(kind, index,
        slices, structure) groups into one device program each, in
        admission (priority) order; singleton groups take the normal
        batched path. A member whose deadline expired during the batch
        wait gets DeadlineExceeded (→ 504) and is excluded BEFORE its
        group fuses — expiry never poisons or stalls siblings.
        Per-request failures land in that request's slot."""
        now = time.monotonic()
        groups = {}
        expired = 0
        for req in batch:
            if req.get("deadline") is not None and now > req["deadline"]:
                req["out"] = qos.DeadlineExceeded()
                expired += 1
                continue
            groups.setdefault(req["key"], []).append(req)
        if expired:
            with self._co_mu:
                self._co_expired += expired
        self._co_stats["rounds"] += 1
        for reqs in groups.values():
            self._hist_co_group.observe(len(reqs))
            try:
                if len(reqs) == 1 or not reqs[0]["fuse"](reqs):
                    for req in reqs:
                        if req["out"] is self._CO_PENDING:
                            # Single-serves run on the leader's thread
                            # but are one member's own work — charge
                            # that member's accumulator (or nobody's),
                            # never the leader's.
                            with querystats.exclusive_scope(
                                    req.get("qs")):
                                req["out"] = req["single"]()
            except BaseException as exc:  # noqa: BLE001 — delivered
                for req in reqs:
                    if req["out"] is self._CO_PENDING:
                        req["out"] = exc

    def _co_run_fused(self, reqs):
        """Fuse K same-structure counts into as few device launches as
        the group's formats allow. Dense-served plans stack per-leaf
        device rows with a query axis ([K, S, W], _co_fuse_dense);
        all-compressed plans — which this path used to DECLINE
        wholesale, leaving the 100B tier serving concurrency through
        serial per-slice kernels — fuse as format-bucketed container
        lanes (_co_fuse_lanes); deep all-compressed trees may stage
        densely within the per-group densify budget (each staged block
        ticking container_conversions_total). Returns False when any
        member was left unserved (callers serve those singly)."""
        index = reqs[0]["index"]
        slices = reqs[0]["slices"]
        if not slices or not reqs[0]["leaves"]:
            # A leafless plan (e.g. statically-empty Range shortcut)
            # gives vmap no mapped input to size the query axis.
            self._co_note_decline("structural", reqs)
            return False
        # One fragment-list pass per (frame, view) per TICK — group
        # members overwhelmingly share frames, so the per-request
        # holder walks (O(slices) each) collapse into shared lists,
        # reused for the format probe, the column window, and the
        # stack builds. The probe memo dedupes row_compressed checks
        # the same way (queries in a group share rows).
        shared = {}
        maps = [self._leaf_frags(index, req["leaves"], slices,
                                 shared=shared)
                for req in reqs]
        probe = {}
        comp = [self._compressed_plan(req["leaves"], fm, probe=probe)
                for req, fm in zip(reqs, maps)]
        dense_pairs = [(req, fm) for req, fm, c
                       in zip(reqs, maps, comp) if not c]
        ok = True
        densify_blocks = 0
        if len(dense_pairs) < len(reqs):
            _, _, comp_ok, densify_budget = self._co_config()
            if not comp_ok:
                # [executor] coalesce-compressed=false restores the
                # pre-lane behavior: the whole group serves singly
                # through the serial compressed kernels.
                self._co_note_decline("compressed_off", reqs)
                return False
            lane_pairs, deep_pairs = [], []
            for req, fm, c in zip(reqs, maps, comp):
                if not c:
                    continue
                if self._lane_plan_shape(req["plan"]) is not None:
                    lane_pairs.append((req, fm))
                else:
                    deep_pairs.append((req, fm))
            if deep_pairs:
                # Deep all-compressed trees have no count-identity
                # shortcut: stage densely IF the group's densify bytes
                # fit the explicit budget, making the conversion churn
                # observable; over budget they serve singly.
                merged = {}
                for _, fm in deep_pairs:
                    merged.update(fm)
                win = self._union_window(merged)
                blocks = sum(
                    sum(self._spec_rows(sp) for sp in req["leaves"])
                    for req, _ in deep_pairs) * len(slices)
                if blocks * win[1] * 4 <= densify_budget:
                    densify_blocks = blocks
                    dense_pairs.extend(deep_pairs)
                else:
                    self._co_note_decline("densify_budget",
                                          [r for r, _ in deep_pairs])
                    ok = False
            if lane_pairs:
                self._co_fuse_lanes([r for r, _ in lane_pairs],
                                    [m for _, m in lane_pairs])
        if dense_pairs:
            served = self._co_fuse_dense(dense_pairs)
            if served and densify_blocks:
                # Counted only AFTER the fused serve actually staged
                # the blocks — a device-budget decline (or a failure)
                # falls back to the serial compressed kernels, which
                # never densify, and must not report phantom churn.
                self._co_stats["densified_blocks"] += densify_blocks
                containers_mod.note_conversion(densify_blocks)
            ok = served and ok
        return ok

    def _co_fuse_dense(self, pairs):
        """Evaluate K dense-served same-structure counts as ONE device
        program: per-leaf-slot stacks gain a query axis ([K, S, W])
        and the tree evaluator is vmapped over it. Returns False when
        the group doesn't fit the device budget (callers then serve
        the unserved requests singly)."""
        import jax

        reqs = [req for req, _ in pairs]
        maps = [fm for _, fm in pairs]
        index = reqs[0]["index"]
        slices = reqs[0]["slices"]
        plan = reqs[0]["plan"]
        leaves0 = reqs[0]["leaves"]
        n_dev = len(jax.devices())
        pad = (-len(slices)) % n_dev
        k = len(reqs)
        k_pad = 1
        while k_pad < k:
            k_pad *= 2
        merged = {}
        for fm in maps:
            merged.update(fm)
        win = self._union_window(merged)
        rows = sum(self._spec_rows(sp) for sp in leaves0)
        if not self._fits_device_budget(rows * k_pad, len(slices) + pad,
                                        width32=win[1]):
            self._co_note_decline("budget", reqs)
            return False
        per_query = []
        for req, fm in zip(reqs, maps):
            # Stack staging reads fragments for ONE member's leaves —
            # charge that member (parked coalescees included), not the
            # leader running the loop.
            with querystats.exclusive_scope(req.get("qs")):
                per_query.append(
                    [self._spec_arg(index, sp, slices, pad, n_dev, win,
                                    fm)
                     for sp in req["leaves"]])
        args = self._co_stack_args(per_query, leaves0, k_pad, n_dev)
        obs = kerneltime_mod.ACTIVE
        tree_key = str(plan)
        key = ("countK", tree_key, len(slices) + pad, win[1], k_pad)
        with self._cache_mu:
            compiled = obs.enabled and key not in self._batched_cache
        fn = self._co_fused_fn(tree_key, plan, len(slices) + pad,
                               win[1], k_pad)
        t0 = time.perf_counter()
        counts = np.asarray(fn(*args))
        if obs.enabled:
            obs.note("coalesce_count_fused", "dense*dense",
                     kerneltime_mod.lane_bucket(k),
                     time.perf_counter() - t0, compiled=compiled,
                     device=True)
            if compiled and devprof_mod.ACTIVE.enabled:
                # Analytic capture rides the compile dispatch only.
                devprof_mod.ACTIVE.note_compile(
                    "coalesce_count_fused", "dense*dense",
                    kerneltime_mod.lane_bucket(k), fn, args)
        # Per-member kernel-cost share: the fused program popcounts
        # each member's own [rows, S, W] stack — the same
        # bytes-popcounted the serial path would have charged it.
        rows0 = sum(self._spec_rows(sp) for sp in leaves0)
        share = rows0 * (len(slices) + pad) * win[1] * 4
        for req in reqs:
            qs = req.get("qs")
            if qs is not None:
                qs.add("bytesPopcounted", share)
        for i, req in enumerate(reqs):
            req["out"] = int(counts[i, : len(slices)].sum())
            qs = req.get("qs")
            if qs is not None:
                qs.note_tier("coalesced_dense")
        self._co_stats["fused_queries"] += k
        self._co_stats["max_group"] = max(self._co_stats["max_group"], k)
        return True

    def _lane_plan_shape(self, plan):
        """Lane-tier eligibility of a count plan: ("count", leaf_pos)
        for a bare row leaf (served from host-known cardinalities —
        zero device work), (op, leaf_pos_a, leaf_pos_b) for a
        two-operand boolean node over row leaves (served through the
        or/xor/andnot count identities from ONE intersection lane per
        format cell — the roaring count-only contract,
        arXiv:1402.6407), None otherwise (deep trees take the
        budgeted-densify route)."""
        if plan[0] == "leaf":
            return ("count", plan[1])
        op = self._COUNT_OPS.get(plan[0])
        if (op is not None and len(plan[1]) == 2
                and plan[1][0][0] == "leaf"
                and plan[1][1][0] == "leaf"):
            return (op, plan[1][0][1], plan[1][1][1])
        return None

    # Transient lane budget: dense word lanes ([N, W] uint32) are the
    # one lane shape whose bytes scale with the window; cells are
    # chunked so no single launch stages more than this. Position/run
    # lanes are KBs per member and never bind.
    CO_LANE_BYTES = 256 << 20

    def _co_fuse_lanes(self, reqs, maps):
        """Serve K all-compressed same-structure counts from the
        container tier in one launch per format cell: every (query,
        slice) member pair resolves its two operand containers
        (row_container — the same objects the serial path serves),
        members bucket by (fmt_a, fmt_b), each bucket's payloads stack
        into sentinel-padded lanes, and the registered fused cell
        (bitops.fused_count_kernel) counts the whole lane in one
        vmapped program. Absent fragments resolve host-side by the
        op's identity (the Bitmap.op_count segment rules), run×run
        stays host-side, and or/xor/andnot derive from |a∩b| plus the
        host-known cardinalities — NOTHING densifies, so
        container_conversions_total stays flat by construction.

        Single-row-leaf plans never touch the device at all: the
        per-slice cardinality IS the container count."""
        from pilosa_tpu.ops import bitops

        k = len(reqs)
        shape = self._lane_plan_shape(reqs[0]["plan"])
        if shape[0] == "count":
            for req, fm in zip(reqs, maps):
                _, fname, rid, view = req["leaves"][shape[1]]
                frags = fm[(fname, view)]
                with querystats.exclusive_scope(req.get("qs")):
                    req["out"] = int(sum(f.row_count(rid) for f in frags
                                         if f is not None))
        elif (containers_mod.lane_host_mode()
                and self._co_fuse_lanes_host(reqs, maps, shape)):
            pass  # served via whole-row host lanes (CPU backend)
        else:
            op = shape[0]
            totals = [0] * k
            members = []  # (query idx, container a, container b)
            # Tick-shared container memo: group members overwhelmingly
            # share rows (N queries over M rows touch M×S containers,
            # not N×S×2), so each (fragment, row) resolves once per
            # tick — the Python half of the lane tier stays O(unique
            # rows), only the device lanes are per member.
            conts = {}

            def cont(frag, rid):
                ckey = (id(frag), rid)
                c = conts.get(ckey)
                if c is None:
                    c = conts[ckey] = frag.row_container(rid)
                return c

            for qi, (req, fm) in enumerate(zip(reqs, maps)):
                _, fa_name, rid_a, view_a = req["leaves"][shape[1]]
                _, fb_name, rid_b, view_b = req["leaves"][shape[2]]
                frags_a = fm[(fa_name, view_a)]
                frags_b = fm[(fb_name, view_b)]
                # Container resolution is this member's own work
                # (shared rows memoized in `conts` charge whichever
                # member resolved them first — its share).
                with querystats.exclusive_scope(req.get("qs")):
                    for fr_a, fr_b in zip(frags_a, frags_b):
                        if fr_a is None and fr_b is None:
                            continue
                        if fr_b is None:
                            # Absent right side: and → 0; or/xor/
                            # andnot count the unopposed left
                            # (op_count's segment identities).
                            if op != "and":
                                totals[qi] += fr_a.row_count(rid_a)
                            continue
                        if fr_a is None:
                            if op in ("or", "xor"):
                                totals[qi] += fr_b.row_count(rid_b)
                            continue
                        members.append((qi, cont(fr_a, rid_a),
                                        cont(fr_b, rid_b)))
            cells = {}
            for m in members:
                cells.setdefault((m[1].fmt, m[2].fmt), []).append(m)
            launches = 0
            for (fa, fb), ms in cells.items():
                kern = bitops.fused_count_kernel(op, fa, fb)
                if kern is None:
                    # Unregistered cell (a future format before its
                    # lane lands): the serial kernels, one dispatch
                    # per member — bit-exact, just unbatched.
                    for qi, ca, cb in ms:
                        with querystats.exclusive_scope(
                                reqs[qi].get("qs")):
                            totals[qi] += int(bitops.dispatch_count(
                                op, ca, cb))
                    continue
                per = containers_mod.fused_lane_bytes(
                    fa, fb, ms[0][1].width32)
                chunk = (len(ms) if per == 0
                         else max(1, self.CO_LANE_BYTES // per))
                for i in range(0, len(ms), chunk):
                    part = ms[i:i + chunk]
                    counts = kern([m[1] for m in part],
                                  [m[2] for m in part])
                    launches += 1
                    for (qi, ca, cb), cnt in zip(part, counts):
                        totals[qi] += int(cnt)
                        # Each member's share of the lane's kernel
                        # cost: its own operand payloads (the
                        # bytes-popcounted unit, arXiv:1611.07612).
                        qs = reqs[qi].get("qs")
                        if qs is not None:
                            qs.add("bytesPopcounted",
                                   ca.nbytes() + cb.nbytes())
            for req, total in zip(reqs, totals):
                req["out"] = int(total)
            self._co_stats["lane_launches"] += launches
        for req in reqs:
            qs = req.get("qs")
            if qs is not None:
                qs.note_tier("coalesced_lane")
        self._co_stats["fused_queries"] += k
        self._co_stats["compressed_fused"] += k
        self._co_stats["max_group"] = max(self._co_stats["max_group"], k)
        return True

    # Host row-representation cache budget (CPU lane tier): whole-row
    # global-column (positions, runs) vectors, token-validated like
    # the device stack cache. Compressed rows are ≤4096 positions per
    # slice, so even 10k-slice rows fit comfortably under this.
    LANE_ROWS_BYTES = 64 << 20

    def _lane_row_repr(self, index, spec, slices, frags):
        """Whole-row host representation of one row leaf across the
        slice list: per-slice ARRAY positions and RUN intervals
        rebased to GLOBAL columns and concatenated → (positions,
        runs, count). Cached against the fragments' version tokens
        (the stack-cache validity rule), byte-bounded LRU. None when
        any slice serves the row dense — callers fall back to
        per-slice lane members."""
        _, fname, rid, view = spec
        key = ("lanerow", index, fname, view, rid, slice_key(slices))
        tokens = frags.tokens
        with self._cache_mu:
            hit = self._lane_rows.get(key)
            if hit is not None and hit[0] == tokens:
                self._lane_rows[key] = self._lane_rows.pop(key)
                return hit[1]
        pos_parts, run_parts = [], []
        for snum, frag in zip(slices, frags):
            if frag is None:
                continue
            c = frag.row_container(rid)
            if not c.count:
                continue
            base = snum * SLICE_WIDTH
            if c.fmt == "array":
                pos_parts.append(c.positions.astype(np.int64) + base)
            elif c.fmt == "run":
                run_parts.append(c.runs.astype(np.int64) + base)
            else:
                return None
        repr_ = containers_mod.host_row_repr(pos_parts, run_parts)
        nbytes = int(repr_[0].nbytes + repr_[1].nbytes)
        with self._cache_mu:
            prev = self._lane_rows.pop(key, None)
            if prev is not None:
                self._lane_rows_bytes -= prev[2]
            self._lane_rows[key] = (tokens, repr_, nbytes)
            self._lane_rows_bytes += nbytes
            while (self._lane_rows_bytes > self.LANE_ROWS_BYTES
                   and self._lane_rows):
                old = next(iter(self._lane_rows))  # LRU-oldest
                self._lane_rows_bytes -= self._lane_rows.pop(old)[2]
        return repr_

    def _co_fuse_lanes_host(self, reqs, maps, shape):
        """CPU-backend lane serve: every pair's whole-row (positions,
        runs) representations intersect in a handful of vectorized C
        passes (containers.host_repr_and_counts) — repeated pairs in
        the group dedupe, hot rows come from the token-validated repr
        cache, so tick cost tracks the DATA touched, not K×S member
        segmentation. Returns False when any row serves dense
        somewhere (callers use the per-slice member cells)."""
        op = shape[0]
        index = reqs[0]["index"]
        slices = reqs[0]["slices"]
        span = (max(slices) + 1) * SLICE_WIDTH + 1
        pair_ids = {}
        reprs_a, reprs_b = [], []
        member_pair = []
        for req, fm in zip(reqs, maps):
            spa = req["leaves"][shape[1]]
            spb = req["leaves"][shape[2]]
            pid = pair_ids.get((spa, spb))
            if pid is None:
                # Row-representation builds (container reads on cache
                # miss) are this member's own work; deduped pairs
                # charge whichever member resolved them first.
                with querystats.exclusive_scope(req.get("qs")):
                    ra = self._lane_row_repr(index, spa, slices,
                                             fm[(spa[1], spa[3])])
                    rb = self._lane_row_repr(index, spb, slices,
                                             fm[(spb[1], spb[3])])
                if ra is None or rb is None:
                    return False
                pid = pair_ids[(spa, spb)] = len(reprs_a)
                reprs_a.append(ra)
                reprs_b.append(rb)
            member_pair.append(pid)
        obs = kerneltime_mod.ACTIVE
        t0 = time.perf_counter()
        inter = containers_mod.host_repr_and_counts(reprs_a, reprs_b,
                                                    span)
        if obs.enabled:
            obs.note(f"fused_count_{op}", "hostrepr",
                     kerneltime_mod.lane_bucket(len(reprs_a)),
                     time.perf_counter() - t0, device=True)
        for req, pid in zip(reqs, member_pair):
            ca = reprs_a[pid][2]
            cb = reprs_b[pid][2]
            iv = int(inter[pid])
            qs = req.get("qs")
            if qs is not None:
                # This member's share of the host pass: its own
                # pair's representation payloads.
                qs.add("bytesPopcounted", int(
                    reprs_a[pid][0].nbytes + reprs_a[pid][1].nbytes
                    + reprs_b[pid][0].nbytes + reprs_b[pid][1].nbytes))
            if op == "and":
                req["out"] = iv
            elif op == "or":
                req["out"] = ca + cb - iv
            elif op == "xor":
                req["out"] = ca + cb - 2 * iv
            else:  # andnot
                req["out"] = ca - iv
        self._co_stats["lane_launches"] += 1
        return True

    def _co_stack_args(self, per_query, leaves0, k_pad, n_dev):
        """Give each leaf slot a query axis: stack the K per-query
        args to [K, ...], zero-padding to the k_pad bucket. The slice
        axis is re-sharded for row/plane stacks only — "bits"
        predicate args have no slice axis and are stacked on the host
        ([K, depth] NumPy, uploaded by the fused call). The ONE
        stacking loop shared by every fused shape (count, sum)."""
        import jax
        import jax.numpy as jnp

        args = []
        for j in range(len(per_query[0])):
            cols = [pq[j] for pq in per_query]
            if leaves0[j][0] == "bits":
                cols += [np.zeros_like(cols[0])] * (k_pad - len(cols))
                args.append(np.stack(cols))
                continue
            while len(cols) < k_pad:
                cols.append(jnp.zeros_like(cols[0]))
            stacked = jnp.stack(cols)
            if n_dev > 1 and stacked.ndim >= 2:
                from jax.sharding import NamedSharding, PartitionSpec

                spec = PartitionSpec(None, "slice",
                                     *([None] * (stacked.ndim - 2)))
                stacked = jax.device_put(
                    stacked, NamedSharding(self._local_mesh(), spec))
            args.append(stacked)
        return args

    def _coalesced_sum(self, index, call, slices):
        """Group-commit coalescing for Sum: concurrent same-structure
        Sums share ONE device program — the BSI plane stack is shared
        across the group (same field), only the filter-leaf stacks
        gain a query axis. Same contract as _batched_sum."""
        if not self._co_enabled():
            return self._batched_sum(index, call, slices)
        resolved = self._co_bsi_resolve(index, call)
        if resolved is None:
            return None
        frame_name, field_name, field, depth, plan, leaves = resolved
        return self._co_submit({
            "key": ("sum", index, slice_key(slices), frame_name,
                    field_name, depth, str(plan)),
            "index": index, "slices": slices, "plan": plan,
            "leaves": leaves, "field": field, "depth": depth,
            "frame_name": frame_name, "field_name": field_name,
            "out": self._CO_PENDING,
            "single": lambda: self._batched_sum(index, call, slices),
            "fuse": self._co_run_fused_sum,
        })

    def _co_bsi_resolve(self, index, call):
        """Submit-side eligibility for coalescable BSI aggregates
        (Sum/Min/Max): (frame_name, field_name, field, depth, plan,
        leaves), or None → structural fallback."""
        frame_name = call.args.get("frame") or ""
        field_name = call.args.get("field") or ""
        idx = self.holder.index(index)
        frame = idx.frame(frame_name) if idx is not None else None
        if frame is None:
            return None
        try:
            field = frame.field(field_name)
        except perr.ErrFieldNotFound:
            return None
        depth = field.bit_depth()
        leaves = []
        plan = None
        if len(call.children) == 1:
            plan, leaves = self._plan_memoized(index, call.children[0])
            if plan is None:
                return None
        elif call.children:
            return None
        return frame_name, field_name, field, depth, plan, leaves

    def _co_run_fused_sum(self, reqs):
        """Evaluate K same-structure Sums as ONE device program. The
        planes stack is passed once (vmap in_axes=None); each filter
        leaf slot gains a query axis. Filterless Sums are all
        identical — compute once, share the result."""
        prelude = self._co_bsi_group_prelude(reqs)
        if prelude is False or prelude is True:
            return prelude
        planes_stack, args, win, pad, k, k_pad = prelude
        slices = reqs[0]["slices"]
        plan = reqs[0]["plan"]
        field = reqs[0]["field"]
        depth = reqs[0]["depth"]
        fn = self._co_sum_fn(str(plan), plan, depth,
                             len(slices) + pad, win[1], k_pad,
                             len(reqs[0]["leaves"]))
        plane_counts, filt_counts = fn(planes_stack, *args)
        plane_counts = np.asarray(plane_counts)[:, : len(slices)]
        filt_counts = np.asarray(filt_counts)[:, : len(slices)]
        for i, req in enumerate(reqs):
            count = int(filt_counts[i].sum())
            total = sum((1 << b) * int(plane_counts[i, :, b].sum())
                        for b in range(depth))
            req["out"] = SumCount(total + count * field.min, count)
            qs = req.get("qs")
            if qs is not None:
                qs.note_tier("coalesced_dense")
        self._co_stats["fused_queries"] += k
        self._co_stats["max_group"] = max(self._co_stats["max_group"], k)
        return True

    def _coalesced_min_max(self, index, call, slices, find_max):
        """Group-commit coalescing for Min/Max: same grouping and
        fused-program shape as Sum (shared plane stack, per-query
        filter leaves), with the global bit-descent vmapped over the
        query axis. Same contract as _batched_min_max."""
        if not self._co_enabled():
            return self._batched_min_max(index, call, slices, find_max)
        resolved = self._co_bsi_resolve(index, call)
        if resolved is None:
            return None
        frame_name, field_name, field, depth, plan, leaves = resolved
        return self._co_submit({
            "key": ("minmax", find_max, index, slice_key(slices),
                    frame_name, field_name, depth, str(plan)),
            "index": index, "slices": slices, "plan": plan,
            "leaves": leaves, "field": field, "depth": depth,
            "frame_name": frame_name, "field_name": field_name,
            "find_max": find_max, "out": self._CO_PENDING,
            "single": lambda: self._batched_min_max(index, call,
                                                    slices, find_max),
            "fuse": self._co_run_fused_minmax,
        })

    def _co_run_fused_minmax(self, reqs):
        prelude = self._co_bsi_group_prelude(reqs)
        if prelude is False or prelude is True:
            return prelude
        planes_stack, args, win, pad, k, k_pad = prelude
        slices = reqs[0]["slices"]
        field = reqs[0]["field"]
        depth = reqs[0]["depth"]
        plan = reqs[0]["plan"]
        fn = self._co_minmax_fn(str(plan), plan, depth,
                                reqs[0]["find_max"], len(slices) + pad,
                                win[1], k_pad, len(reqs[0]["leaves"]))
        indicators, counts = fn(planes_stack, *args)
        indicators = np.asarray(indicators)
        counts = np.asarray(counts)
        for i, req in enumerate(reqs):
            count = int(counts[i])
            if count == 0:
                req["out"] = BATCH_EMPTY
            else:
                value = sum((1 << b) * int(v)
                            for b, v in enumerate(indicators[i]))
                req["out"] = SumCount(value + field.min, count)
            qs = req.get("qs")
            if qs is not None:
                qs.note_tier("coalesced_dense")
        self._co_stats["fused_queries"] += k
        self._co_stats["max_group"] = max(self._co_stats["max_group"], k)
        return True

    def _co_bsi_group_prelude(self, reqs):
        """Shared fused-BSI group setup (Sum and Min/Max): resolves
        the group window, budget, shared plane stack, and per-query
        leaf args. Returns True when the group was served directly
        (identical filterless queries — compute once, share), False
        when ineligible, else (planes_stack, args, win, pad, k,
        k_pad)."""
        import jax

        index = reqs[0]["index"]
        slices = reqs[0]["slices"]
        plan = reqs[0]["plan"]
        leaves0 = reqs[0]["leaves"]
        depth = reqs[0]["depth"]
        if not slices:
            self._co_note_decline("structural", reqs)
            return False
        if plan is None or not leaves0:
            # One shared compute for identical filterless queries —
            # charged to the member it runs as (the group head), like
            # any other shared-work resolution.
            with querystats.exclusive_scope(reqs[0].get("qs")):
                out = reqs[0]["single"]()
            for req in reqs:
                req["out"] = out
                qs = req.get("qs")
                if qs is not None and req is not reqs[0]:
                    # The head's own serve stamped its real tier
                    # inside the single(); the sharing members were
                    # served BY the group.
                    qs.note_tier("coalesced_dense")
            self._co_stats["fused_queries"] += len(reqs)
            self._co_stats["max_group"] = max(
                self._co_stats["max_group"], len(reqs))
            return True
        n_dev = len(jax.devices())
        pad = (-len(slices)) % n_dev
        k = len(reqs)
        k_pad = 1
        while k_pad < k:
            k_pad *= 2
        frame_name = reqs[0]["frame_name"]
        field_name = reqs[0]["field_name"]
        planes_map = self._leaf_frags(
            index, [("planes", frame_name, field_name, depth)], slices)
        maps = [self._leaf_frags(index, req["leaves"], slices)
                for req in reqs]
        merged = dict(planes_map)
        for fm in maps:
            merged.update(fm)
        win = self._union_window(merged)
        rows = depth + 1 + k_pad * sum(
            self._spec_rows(sp) for sp in leaves0)
        if not self._fits_device_budget(rows, len(slices) + pad,
                                        width32=win[1]):
            self._co_note_decline("budget", reqs)
            return False
        planes_stack = self._planes_stack(
            index, frame_name, field_name, depth, slices, pad, n_dev,
            win=win,
            frags=merged.get((frame_name, view_field_name(field_name))))
        per_query = []
        for req, fm in zip(reqs, maps):
            # Per-member staging charges the member, not the leader
            # (the _co_fuse_dense attribution rule).
            with querystats.exclusive_scope(req.get("qs")):
                per_query.append(
                    [self._spec_arg(index, sp, slices, pad, n_dev, win,
                                    fm)
                     for sp in req["leaves"]])
        args = self._co_stack_args(per_query, leaves0, k_pad, n_dev)
        return planes_stack, args, win, pad, k, k_pad

    def _co_minmax_fn(self, tree_key, plan, depth, find_max, padded_n,
                      width32, k_pad, arity):
        """K fused filtered Min/Max global bit-descents (planes
        shared, filter leaves per query)."""
        import jax
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def single(planes, *leaf_args):
                exists = planes[:, depth, :]
                m = lax.bitwise_and(
                    exists, eval_node(plan, leaf_args, shape))
                return Executor._minmax_descent(planes, m, depth,
                                                find_max)
            return jax.vmap(single, in_axes=(None,) + (0,) * arity)

        return self._cached_fn(
            ("minmaxK", tree_key, depth, find_max, padded_n, width32,
             k_pad, arity), build,
            "max_fused" if find_max else "min_fused", arity)

    @staticmethod
    def _minmax_descent(planes, m, depth, find_max):
        """The ONE global bit-descent body (MSB→LSB keep/exclude with
        cross-slice occupancy tests), shared by the single-query and
        fused Min/Max kernels so the two cannot diverge. Returns
        (indicators[depth] int32, matching-column count)."""
        import jax.numpy as jnp
        from jax import lax

        indicators = []
        for i in range(depth - 1, -1, -1):
            p = planes[:, i, :]
            ones = lax.bitwise_and(m, p)
            zeros = lax.bitwise_and(m, lax.bitwise_not(p))
            prefer = ones if find_max else zeros
            fallback = zeros if find_max else ones
            has_pref = jnp.sum(
                lax.population_count(prefer).astype(jnp.int32)) > 0
            m = jnp.where(has_pref, prefer, fallback)
            indicators.append(jnp.where(
                has_pref,
                jnp.int32(1 if find_max else 0),
                jnp.int32(0 if find_max else 1)))
        indicators.reverse()
        count = jnp.sum(lax.population_count(m).astype(jnp.int32))
        if depth == 0:
            return jnp.zeros(0, jnp.int32), count
        return jnp.stack(indicators), count

    def _co_sum_fn(self, tree_key, plan, depth, padded_n, width32,
                   k_pad, arity):
        """K fused filtered Sums: planes shared (in_axes=None), each
        of ``arity`` filter-leaf stacks mapped over the query axis."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def single(planes, *leaf_args):
                exists = planes[:, depth, :]
                filt = lax.bitwise_and(
                    exists, eval_node(plan, leaf_args, shape))
                masked = lax.bitwise_and(planes[:, :depth, :],
                                         filt[:, None, :])
                counts = jnp.sum(
                    lax.population_count(masked).astype(jnp.int32),
                    axis=2)
                filt_counts = jnp.sum(
                    lax.population_count(filt).astype(jnp.int32),
                    axis=1)
                return counts, filt_counts
            return jax.vmap(single, in_axes=(None,) + (0,) * arity)

        return self._cached_fn(
            ("sumK", tree_key, depth, padded_n, width32, k_pad, arity),
            build, "sum_fused", arity)

    def _co_fused_fn(self, tree_key, plan, padded_n, width32, k_pad):
        import jax
        import jax.numpy as jnp
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def single(*args):
                out = eval_node(plan, args, shape)
                return jnp.sum(
                    lax.population_count(out).astype(jnp.int32), axis=1)
            return jax.vmap(single)

        return self._cached_fn(
            ("countK", tree_key, padded_n, width32, k_pad), build,
            "count_fused", _plan_operands(plan))

    def _leaf_stack(self, index, frame_name, row_id, slices, pad, n_dev,
                    view=VIEW_STANDARD, win=None, frags=None):
        """Sharded ``uint32[n_slices+pad, width]`` stack of one row
        across the slice list at the plan's column window, cached until
        any underlying fragment mutates (version vector check — the
        stack/reshard is the dominant cost, not the count kernel)."""
        import jax
        import jax.numpy as jnp

        from pilosa_tpu import WORDS_PER_SLICE

        base32, width32 = win if win is not None else (0, WORDS_PER_SLICE)
        if frags is None:
            frags = self._frag_list(index, frame_name, view, slices)
        key = ("row", index, frame_name, view, row_id,
               slice_key(slices), n_dev, base32, width32)
        tokens = frags.tokens
        hit, stale = self._stack_cache_lookup(key, tokens)
        if hit is not None:
            return hit
        querystats.add("stackBuilds")

        zero = self._zero_row(width32)
        stack = self._stack_incremental(
            key, tokens, stale,
            lambda changed: [frags[i].device_row_win(row_id, base32,
                                                     width32)
                             if frags[i] is not None else zero
                             for i in changed],
            n_dev, 2)
        if stack is not None:
            return stack

        rows = [f.device_row_win(row_id, base32, width32)
                if f is not None else zero for f in frags]
        rows.extend([zero] * pad)  # zero slices count 0 in any fold
        stack = jnp.stack(rows)
        stack = self._shard_stack(stack, n_dev, 2)
        self._stack_cache_put(key, tokens, stack)
        return stack

    def _batched_bitmap(self, index, call, slices):
        """Materialize a compound bitmap tree as one fused sharded
        program; result segments are rows of the device stack (empty
        slices dropped via the same kernel's per-slice counts), and the
        total count comes for free."""
        prelude = self._plan_and_stacks(index, call, slices, extra_rows=1,
                                        compound_only=True)
        if prelude is None or prelude is BATCH_OVER_BUDGET:
            return prelude
        plan, stacks, padded_n, win = prelude
        fn = self._batched_bitmap_fn(str(plan), plan, padded_n, win[1])
        result, counts = fn(*stacks)
        counts = np.asarray(counts)[: len(slices)]
        # The result stays ONE device stack: slicing it into per-slice
        # segments here would cost a dispatch (sharded: a cross-device
        # gather) per slice. Bitmap.defer_stack materializes segments
        # with a single bulk host fetch only if a caller touches the
        # words — count-only consumers never fetch, which is also what
        # lets this path run sharded on a mesh.
        bm = Bitmap()
        bm.defer_stack(result, slices, counts, word_base=win[0])
        bm._count = int(counts.sum())
        return bm

    def _planes_stack(self, index, frame_name, field_name, depth, slices,
                      pad, n_dev, win=None, frags=None):
        """Sharded ``uint32[S+pad, depth+1, width]`` BSI plane stack
        across the slice list at the plan's column window, cached like
        leaf stacks."""
        import jax.numpy as jnp

        from pilosa_tpu import WORDS_PER_SLICE

        base32, width32 = win if win is not None else (0, WORDS_PER_SLICE)
        view = view_field_name(field_name)
        if frags is None:
            frags = self._frag_list(index, frame_name, view, slices)
        key = ("planes", index, frame_name, field_name, depth,
               slice_key(slices), n_dev, base32, width32)
        tokens = frags.tokens
        stack, stale = self._stack_cache_lookup(key, tokens)
        if stack is not None:
            return stack
        querystats.add("stackBuilds")
        zero_planes = jnp.zeros((depth + 1, width32), jnp.uint32)
        stack = self._stack_incremental(
            key, tokens, stale,
            lambda changed: [frags[i].planes_win(depth, base32, width32)
                             if frags[i] is not None else zero_planes
                             for i in changed],
            n_dev, 3)
        if stack is not None:
            return stack
        mats = [f.planes_win(depth, base32, width32)
                if f is not None else zero_planes for f in frags]
        mats.extend([zero_planes] * pad)
        stack = self._shard_stack(jnp.stack(mats), n_dev, 3)
        self._stack_cache_put(key, tokens, stack)
        return stack

    def _rows_stack(self, frags, row_ids, n_rows, pad, n_dev, win):
        """Sharded ``uint32[S+pad, n_rows, width]`` stack of TopN's
        candidate rows (zero rows after ``row_ids``) across ``frags``
        at the plan's column window: one gather a fragment
        (``Fragment.device_rows_win``) and one operand of the candidate
        program, whatever the number of candidates. Built, used and
        dropped: no cache holds it (a repeated TopN is answered by the
        ``topnc`` memo before it reaches a stack)."""
        import jax.numpy as jnp

        querystats.add("stackBuilds")
        mats = [None if f is None else
                f.device_rows_win(row_ids, n_rows, win[0], win[1])
                for f in frags] + [None] * pad
        if any(m is None for m in mats):
            zero = jnp.zeros((n_rows, win[1]), jnp.uint32)
            mats = [zero if m is None else m for m in mats]  # count 0
        return self._shard_stack(jnp.stack(mats), n_dev, 3)

    @staticmethod
    def _spec_rows(spec):
        """Row-equivalents a spec's arg occupies on device (budgeting)."""
        if spec[0] == "row":
            return 1
        if spec[0] == "planes":
            return spec[3] + 1
        return 0  # bits: a few dozen host bytes

    def _spec_arg(self, index, spec, slices, pad, n_dev, win=None,
                  frag_map=None):
        """Build the program operand for one typed leaf spec: a device
        stack for a row or a plane matrix, a host int32[depth] array
        for predicate bits (it travels with the launch that reads it;
        an eager jnp.asarray would be a program of its own)."""
        if spec[0] == "row":
            _, fname, rid, view = spec
            frags = frag_map.get((fname, view)) if frag_map else None
            return self._leaf_stack(index, fname, rid, slices, pad, n_dev,
                                    view=view, win=win, frags=frags)
        if spec[0] == "planes":
            _, fname, field_name, depth = spec
            frags = (frag_map.get((fname, view_field_name(field_name)))
                     if frag_map else None)
            return self._planes_stack(index, fname, field_name, depth,
                                      slices, pad, n_dev, win=win,
                                      frags=frags)
        _, bits, depth = spec
        return np.asarray(bits, dtype=np.int32)

    # Minimum device-stack window width (uint32 words): 2 × the
    # fragment minimum (_MIN_W64=64 u64 words), and a multiple of the
    # TPU's 128-lane vector register so narrow stacks still tile.
    MIN_WIN32 = 128

    def _compressed_plan(self, leaves, frag_map, probe=None):
        """True when EVERY row leaf of this plan serves from a
        compressed container on every slice (fragment.row_compressed —
        a pure density-stat probe). Staging those plans as dense
        device stacks would densify the whole compressed tier back
        into HBM, so they decline the batched path and run serially,
        where Bitmap/dispatch_count route to the registered compressed
        kernels. Any dense row — and any BSI plane leaf, planes are
        dense by design — keeps the batched path: the dense hot path
        is byte-identical to before, and mixed dense×compressed pairs
        are still bit-exact there via the densify fallback. A row
        found dense is remembered on its fragment list
        (``FragList.dense``), so one known-dense leaf settles a plan
        with no probe at all."""
        if not containers_mod.enabled():
            return False
        rows = []
        for sp in leaves:
            if sp[0] == "planes":
                return False
            if sp[0] != "row":
                continue
            _, fname, rid, view = sp
            frags = frag_map[(fname, view)]
            if rid in frags.dense:
                return False
            rows.append((rid, frags))
        for rid, frags in rows:
            for frag in frags:
                if frag is None:
                    continue
                if probe is None:
                    hit = frag.row_compressed(rid)
                else:
                    # Tick-shared probe memo: a coalesced group's
                    # members share rows, so the per-(fragment, row)
                    # density checks dedupe across the whole group.
                    pkey = (id(frag), rid)
                    hit = probe.get(pkey)
                    if hit is None:
                        hit = probe[pkey] = frag.row_compressed(rid)
                if not hit:
                    frags.dense.add(rid)
                    return False
        return bool(rows)

    @staticmethod
    def _leaf_key(index, frame_name, view, slices):
        """Plan-cache key of one (frame, view)'s ``FragList``."""
        return ("leaf", index, frame_name, view, slice_key(slices))

    def _frag_list(self, index, frame_name, view, slices, walked=None):
        """The ``FragList`` of one (frame, view) over ``slices``: from
        the plan cache while the index's mutation epoch stands, else
        one holder walk (a locked ``view.fragment`` per slice, then
        ``win32`` and a token per fragment) whose key is appended to
        ``walked``. The epoch is read BEFORE the walk, so a racing
        write makes the entry stale on arrival, never wrong."""
        from pilosa_tpu.storage import fragment as _frag

        epoch = _frag.mutation_epoch(index)
        key = self._leaf_key(index, frame_name, view, slices)
        frags = self.plans.get(key, epoch)
        stat = "leafMemoHits"
        if frags is None:
            stat = "leafMemoMisses"
            frags = FragList(
                self.holder.fragments(index, frame_name, view, slices))
            frags.tokens = self._frag_tokens(frags)
            frags.extent = self._list_extent(frags)
            frags.dense = set()
            self.plans.put(key, epoch, frags)
            if walked is not None:
                walked.append(key)
        querystats.add(stat)
        with self._cache_mu:
            self.leaf_memo[stat] += 1
        return frags

    def _known_dense(self, index, frame_name, view, row_id, slices):
        """True when the memoised facts of the row's fragment list say
        some fragment serves it dense. A pure read (no walk, no LRU
        refresh, no counter), for the planner's sampled probe and its
        explain-only mode."""
        from pilosa_tpu.storage import fragment as _frag

        frags = self.plans.peek(
            self._leaf_key(index, frame_name, view, slices),
            _frag.mutation_epoch(index))
        return frags is not None and row_id in frags.dense

    def _leaf_frags(self, index, leaves, slices, shared=None, walked=None):
        """(frame, view) -> ``FragList`` for every row and plane leaf:
        the lists shared by the compressed-tier probe, window
        negotiation and stack builds. One _frag_list lookup per
        distinct (frame, view); a list is walked only when the epoch
        has moved since it was last read. ``shared`` (a coalescer-tick
        cache) dedupes even the lookups ACROSS a fused group's
        requests."""
        frag_map = {}
        for sp in leaves:
            if sp[0] == "row":
                _, fname, _rid, view = sp
            elif sp[0] == "planes":
                _, fname, field_name, _depth = sp
                view = view_field_name(field_name)
            else:
                continue
            key = (fname, view)
            if key in frag_map:
                continue
            lst = shared.get(key) if shared is not None else None
            if lst is None:
                lst = self._frag_list(index, fname, view, slices, walked)
                if shared is not None:
                    shared[key] = lst
            frag_map[key] = lst
        return frag_map

    @staticmethod
    def _list_extent(frags):
        """(lo, hi) in uint32 device words covering the column window
        of every fragment in the list, or None when none holds a row."""
        lo = hi = None
        for f in frags:
            if f is None:
                continue
            win = f.win32()
            if win is None:
                continue
            b, w = win
            lo = b if lo is None else min(lo, b)
            hi = b + w if hi is None else max(hi, b + w)
        return None if lo is None else (lo, hi)

    def _union_window(self, frag_map):
        """Common column window (base, width in uint32 device words)
        covering every fragment a batched plan touches, so device
        stacks allocate HBM for the data's span instead of the full
        32,768-word slice (narrow/clustered data would otherwise pay
        up to 256× its host bytes in HBM): the union of the lists'
        memoised extents (``FragList.extent``, no fragment is touched
        here), bucketed by _bucket_window.
        ``frag_map`` comes from _leaf_frags; callers with fragments
        outside the leaf specs (TopN candidate rows) insert their
        _frag_list into the map first. Ref contrast: containers never
        materialize empty space (roaring.go:1011-1024)."""
        from pilosa_tpu import WORDS_PER_SLICE

        if self._fixed_full_window:
            # Operator opt-out of window economy (PILOSA_TPU_FULL_WIN=1)
            # for write-heavy indexes whose clusters keep spreading:
            # one fixed width means one compiled program per shape,
            # at the cost of full-slice HBM stacks.
            return 0, WORDS_PER_SLICE
        extents = [frags.extent for frags in frag_map.values()
                   if frags.extent is not None]
        if not extents:
            return 0, self.MIN_WIN32
        return self._bucket_window(min(lo for lo, _ in extents),
                                   max(hi for _, hi in extents))

    def _bucket_window(self, lo, hi):
        """Smallest width-aligned power-of-FOUR window (base, width)
        covering device words [lo, hi); full slice width when the data
        really spans it."""
        from pilosa_tpu import WORDS_PER_SLICE

        # Width buckets are powers of FOUR (128, 512, 2048, 8192,
        # 32768): every distinct width is a distinct XLA program, and a
        # mixed read/write load whose writes keep nudging some
        # fragment's host window would otherwise recompile the fused
        # kernels at each power-of-two step — 20-40 s per compile on
        # TPU turned sustained mixed serving into a compile convoy
        # (measured 1.6 q/s at 8 clients). Five buckets cap the
        # lifetime compile count per query shape, and since host
        # windows are powers of two, device width stays ≤ 2× the host
        # window — the HBM-economy bound tests assert.
        w = self.MIN_WIN32
        while True:
            b = lo // w * w
            if hi <= b + w or w >= WORDS_PER_SLICE:
                break
            w *= 4
        if w >= WORDS_PER_SLICE:
            return 0, WORDS_PER_SLICE
        return b, w

    # Epoch-validated prelude memo: a repeated query's whole prelude
    # (plan, window, stack-cache keys) under one key. Epoch equality
    # (no fragment of THIS index mutated/opened/closed since the memo)
    # is an O(1) sufficient condition for validity. A miss composes
    # the prelude from the per-list facts (_frag_list, same epoch
    # rule), O(leaves) lookups; only a list whose epoch moved pays the
    # O(slices) walk and the precise per-fragment tokens. Storage
    # lives in the plan cache (plancache.py): real LRU, configurable
    # capacity, shared hit/miss/invalidation counters.

    @property
    def _prelude_cache(self):
        """Introspection/test view of the prelude-class plan entries
        (key -> stored payload); the live store is self.plans."""
        return self.plans.entries_view(kinds=("plan", "bsi", "topnp"))

    def _prelude_memo_get(self, pkey):
        """Memo hit → (head, stacks, tail) with device stacks resolved
        FROM the byte-budgeted stack cache (the memo stores keys, not
        arrays — pinning arrays here would bypass STACK_CACHE_BYTES).
        Resolution refreshes each stack's LRU recency so hot stacks
        keep their incremental-update entries across writes."""
        from pilosa_tpu.storage import fragment as _frag

        # pkey[1] is the query's index in every prelude key shape
        # ("plan"/"bsi"/"topnp"); the scoped epoch lets memos survive
        # writes to OTHER indexes. record=False: the lookup only
        # SUCCEEDS once every device stack resolves — a hit counted
        # here but evicted below would report walk-free serving while
        # the query pays the full walk.
        hit = self.plans.get(pkey, _frag.mutation_epoch(pkey[1]),
                             record=False)
        if hit is None:
            self._prelude_record(pkey, False)
            return None
        head, specs, tail = hit
        with self._cache_mu:
            stacks = []
            for kind, v in specs:
                if kind == "direct":
                    stacks.append(v)
                    continue
                ent = self._stack_cache.get(v)
                if ent is None:
                    # Evicted under budget → full path (which re-puts
                    # the same key with fresh stacks).
                    stacks = None
                    break
                self._stack_cache[v] = self._stack_cache.pop(v)
                stacks.append(ent[1])
        self._prelude_record(pkey, stacks is not None)
        if stacks is None:
            return None
        qs = querystats.active()
        if qs is not None:
            qs.add("planCacheHit", 1)
        return head, stacks, tail

    def _prelude_record(self, pkey, hit):
        """The outcome of one prelude-memo lookup, told to the plan
        cache. A ``bsi`` prelude's (Sum/Min/Max) is the same count
        shown twice more: ``bsiPreludeHits`` / ``bsiPreludeMisses``
        of the query's ``?profile=true`` block and of /debug/vars."""
        self.plans.record(pkey[1], hit)
        if pkey[0] == "bsi":
            stat = "bsiPreludeHits" if hit else "bsiPreludeMisses"
            querystats.add(stat)
            with self._cache_mu:
                self.bsi_prelude[stat] += 1

    def _prelude_memo_put(self, pkey, head, specs, tail, epoch):
        self.plans.put(pkey, epoch, (head, specs, tail))

    def _prelude_specs(self, index, leaves, stacks, slices, n_dev, win):
        """Memo descriptors per leaf: the stack-cache KEY for row/plane
        stacks (must match _leaf_stack/_planes_stack key layout), the
        raw array only for tiny host-derived args (BSI predicate
        bits: a host int32[depth] array, a few dozen bytes, pinned by
        the memo)."""
        specs = []
        skey = slice_key(slices)
        for sp, st in zip(leaves, stacks):
            if sp[0] == "row":
                _, fname, rid, view = sp
                specs.append(("key", ("row", index, fname, view, rid,
                                      skey, n_dev,
                                      win[0], win[1])))
            elif sp[0] == "planes":
                _, fname, field_name, depth = sp
                specs.append(("key", ("planes", index, fname,
                                      field_name, depth, skey,
                                      n_dev, win[0], win[1])))
            else:
                specs.append(("direct", st))
        return specs

    def _plan_and_stacks(self, index, call, slices, extra_rows=0,
                         compound_only=False):
        """Shared batched-path prelude: plan the tree, negotiate the
        column window, check the device budget, build sharded leaf
        stacks. None → serial fallback. Epoch-memoized: see
        _prelude_memo_get. The plan phase is timed into the active
        query-stats accumulator (``planMs``) so ``?profile=true``
        shows whether a query paid the walk."""
        if not slices:
            return None
        qs = querystats.active()
        t0 = time.perf_counter() if qs is not None else 0.0
        with tracing.span("plan.tree"):
            plan, leaves = self._plan_memoized(index, call)
        if plan is None or (compound_only and plan[0] == "leaf"):
            if qs is not None and plan is None:
                qs.note_fallback("batched", "plan")
            return None
        with tracing.span("stacks.memo") as msp:
            pkey = ("plan", index, slice_key(slices), str(plan),
                    tuple(leaves), extra_rows)
            memo = self._prelude_memo_get(pkey)
            if msp is not tracing.NOP_SPAN:
                msp.tag(hit=memo is not None)
        if memo is not None:
            if qs is not None:
                qs.add("planMs", (time.perf_counter() - t0) * 1000)
            (mplan,), stacks, (padded_n, win) = memo
            return mplan, stacks, padded_n, win
        with tracing.span("stacks.build"):
            out = self._build_stacks(index, plan, leaves, slices,
                                     extra_rows, pkey, qs)
        if qs is not None and isinstance(out, tuple):
            qs.add("planMs", (time.perf_counter() - t0) * 1000)
        return out

    def _build_stacks(self, index, plan, leaves, slices, extra_rows, pkey,
                      qs):
        """The prelude of a plan the memo does not hold, composed from
        the per-list facts (_frag_list): the compressed-tier and budget
        gates, the column window, one device stack per leaf (from the
        stack cache where it holds one), and the memo entry for the
        next query of this plan. O(leaves) while the index's epoch
        stands; a list whose epoch moved is walked once."""
        import jax

        from pilosa_tpu.storage import fragment as _frag

        epoch = _frag.mutation_epoch(index)  # BEFORE building (racy writes
        # during the build make the memo stale-on-arrival, not wrong)
        n_dev = len(jax.devices())
        pad = (-len(slices)) % n_dev
        with tracing.span("build.frags") as fsp:
            walked = []
            frag_map = self._leaf_frags(index, leaves, slices,
                                        walked=walked)
            if fsp is not tracing.NOP_SPAN:
                fsp.tag(walked=len(walked))
        with tracing.span("build.window"):
            if self._compressed_plan(leaves, frag_map):
                if qs is not None:
                    qs.note_fallback("batched", "compressed")
                return None  # serial fallback = the compressed tier
            win = self._union_window(frag_map)
            rows = sum(self._spec_rows(sp) for sp in leaves) + extra_rows
            if not self._fits_device_budget(rows, len(slices) + pad,
                                            width32=win[1]):
                if qs is not None:
                    qs.note_fallback("batched", "budget")
                return BATCH_OVER_BUDGET
        with tracing.span("build.args"):
            stacks = [self._spec_arg(index, sp, slices, pad, n_dev, win,
                                     frag_map)
                      for sp in leaves]
            self._prelude_memo_put(
                pkey, (plan,),
                self._prelude_specs(index, leaves, stacks, slices,
                                    n_dev, win),
                (len(slices) + pad, win), epoch)
        return plan, stacks, len(slices) + pad, win

    def _batched_bitmap_fn(self, tree_key, plan, padded_n, width32):
        import jax
        import jax.numpy as jnp
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def fn(*args):
                out = eval_node(plan, args, shape)
                counts = jnp.sum(
                    lax.population_count(out).astype(jnp.int32), axis=1)
                return out, counts
            return fn

        return self._cached_fn(("bitmap", tree_key, padded_n, width32),
                               build, "bitmap_batched",
                               _plan_operands(plan))

    def _topn_call_params(self, call):
        """Shared TopN arg parsing + validation: (frame_name, view, n,
        min_threshold, tanimoto)."""
        tanimoto, _ = call.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise ValueError("Tanimoto Threshold is from 1 to 100 only")
        if len(call.children) > 1:
            raise ValueError("TopN() can only have one input bitmap")
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        view = (VIEW_INVERSE if call.args.get("inverse") is True
                else VIEW_STANDARD)
        n, _ = call.uint_arg("n")
        min_threshold, _ = call.uint_arg("threshold")
        return (frame_name, view, int(n),
                max(int(min_threshold), MIN_THRESHOLD), int(tanimoto))

    def _topn_attr_allowed(self, index, call, frame_name):
        """Row ids passing the attribute filter (from the row attr
        store, as the serial path computes it), or None when the call
        has no filter (ref: executeTopNSlice filter_row_ids)."""
        attr_name = call.args.get("field") or ""
        filters = call.args.get("filters")
        if not attr_name or filters is None:
            return None
        store = self.holder.index(index).frame(frame_name).row_attr_store
        return {rid for rid in store.ids()
                if store.attrs(rid).get(attr_name) in filters}

    # Candidate sets past this are served by the per-fragment path: the
    # candidate stack holds its bucket of rows for every slice.
    TOPN_CANDIDATES_MAX = 1024
    # The candidates are gathered afresh into ONE operand (a gather a
    # fragment, held by no cache) while that operand is at most this
    # many slice-rows (128 MiB at the full width): few slices, rows
    # from a large universe that rarely repeat. Past it a stack a
    # candidate, kept in the stack cache and shared with Count's: at
    # 954 slices a 16-row bucket would be 2 GB of fresh stack a query.
    TOPN_GATHER_MAX_ROWS = 1024

    def _topn_candidate_counts(self, index, frame_name, view, row_ids,
                               slices, tanimoto, plan, leaves,
                               candidates_shrink=False):
        """Per-(candidate, slice) count matrix [len(row_ids),
        len(slices)] in one fused XLA program: |row ∩ src| (zeroed by
        the Tanimoto gate when requested) or |row| without a plan.
        The single device path under both batched TopN phases. None
        when the candidate set exceeds the jit-arity bucket or the
        device budget."""
        import jax
        import jax.numpy as jnp

        from pilosa_tpu.storage import fragment as _frag

        # Epoch-validated result memo: the per-(candidate, slice) count
        # matrix is a pure function of fragment state, and TopN phase 1
        # re-queries the same candidate set every time for a hot
        # dashboard — the heaviest repeated serving shape. Bounded by
        # the matrix size so huge candidate sets don't bloat the memo.
        pkey = ("topnc", index, frame_name, view, tuple(row_ids),
                slice_key(slices), tanimoto, str(plan),
                tuple(leaves) if leaves else (), candidates_shrink)
        memo = self._result_memo_get(pkey)
        if memo is not None:
            return memo
        epoch = _frag.mutation_epoch(index)

        n_dev = len(jax.devices())
        pad = (-len(slices)) % n_dev
        r_pad = self._candidate_bucket(len(row_ids))
        # Candidate sets are data-dependent: above the device budget
        # (or TOPN_CANDIDATES_MAX) the serial per-slice matrix path wins.
        if r_pad > self.TOPN_CANDIDATES_MAX and not candidates_shrink:
            # Explicit-ids candidate sets don't shrink with the window:
            # decline immediately so no halving recursion probes this.
            return None
        # Prelude-class epoch memo (the _plan_and_stacks pattern): the
        # window negotiation, bulk fragment walk, and per-stack token
        # revalidation are O(slices) Python per query — at 10k slices
        # that dwarfed the phase-2 kernel itself. Stacks resolve from
        # the byte-budgeted stack cache; eviction falls back here.
        # A gathered operand is rebuilt a query: its prelude has no
        # stack-cache keys to keep.
        padded_n = len(slices) + pad
        gathered = padded_n * r_pad <= self.TOPN_GATHER_MAX_ROWS
        pkey2 = ("topnp", index, frame_name, view, tuple(row_ids),
                 slice_key(slices),
                 str(plan) if plan is not None else None,
                 tuple(leaves) if leaves else ())
        hit2 = None if gathered else self._prelude_memo_get(pkey2)
        if hit2 is not None:
            (colwin,), all_stacks, _ = hit2
            rows = list(all_stacks[: len(row_ids)])
            leaf_stacks = list(all_stacks[len(row_ids):])
        else:
            # Column window: the candidate rows' own fragments plus
            # the filter plan's leaves (one shared stack width).
            frag_map = self._leaf_frags(index, leaves, slices)
            if (frame_name, view) not in frag_map:
                frag_map[(frame_name, view)] = self._frag_list(
                    index, frame_name, view, slices)
            colwin = self._union_window(frag_map)
            cand_frags = frag_map[(frame_name, view)]
            if not self._fits_device_budget(
                    r_pad + sum(self._spec_rows(sp) for sp in leaves),
                    padded_n, width32=colwin[1]):
                return BATCH_OVER_BUDGET
            if r_pad > self.TOPN_CANDIDATES_MAX:
                # Phase 1's candidate set is the window's cache union,
                # so smaller windows can fit.
                return BATCH_OVER_BUDGET
            with tracing.span("topn.stacks", candidates=len(row_ids)):
                if gathered:
                    rows = [self._rows_stack(cand_frags, row_ids, r_pad,
                                             pad, n_dev, colwin)]
                else:
                    rows = [self._leaf_stack(index, frame_name, rid, slices,
                                             pad, n_dev, view=view,
                                             win=colwin, frags=cand_frags)
                            for rid in row_ids]
            leaf_stacks = []
            if plan is not None:
                leaf_stacks = [self._spec_arg(index, sp, slices, pad,
                                              n_dev, colwin, frag_map)
                               for sp in leaves]
            if not gathered:
                # Candidate rows as ("row", ...) leaf specs so the ONE
                # key-layout authority (_prelude_specs) builds every
                # descriptor — an inline copy would silently drift if
                # the stack-cache key ever changes shape.
                cand_leaves = [("row", frame_name, rid, view)
                               for rid in row_ids]
                specs = self._prelude_specs(
                    index, cand_leaves + list(leaves),
                    rows + leaf_stacks, slices, n_dev, colwin)
                self._prelude_memo_put(pkey2, (colwin,), specs, None, epoch)
        if not gathered:
            rows += [jnp.zeros_like(rows[0])] * (r_pad - len(rows))
        src_stack = None
        if plan is not None:
            src_stack = self._batched_src_fn(
                str(plan), plan, padded_n, colwin[1])(*leaf_stacks)

        querystats.add("topnRowsScanned", len(row_ids) * len(slices))
        if tanimoto and src_stack is not None:
            # One fused program yields per-(candidate, slice) |row∩src|
            # already zeroed by the gate (ops.topn.tanimoto_keep, the
            # rule the per-fragment program applies): popcounts to
            # keep/drop in integers, on the device. The threshold is
            # a traced operand: one executable for every threshold.
            op = "topn_tanimoto"
            fn, hit = self._batched_topn_tanimoto_fn(r_pad, padded_n,
                                                     gathered)
            args = [src_stack, np.int32(tanimoto)] + rows
        else:
            op = "topn_src_rows" if src_stack is not None else "topn_rows"
            fn, hit = self._batched_topn_fn(src_stack is not None, r_pad,
                                            padded_n, gathered)
            args = ([src_stack] if src_stack is not None else []) + rows
        t0 = time.perf_counter()
        run = (_run_count if tracing.active_span() is None
               else _run_count_split)
        counts = run(fn, args)
        if not hit and kerneltime_mod.ACTIVE.enabled:
            # The fn-cache miss is this program's XLA compile: counted
            # where /debug/kernels counts the Count programs'.
            kerneltime_mod.ACTIVE.note(
                op, "dense*dense",
                kerneltime_mod.shape_bucket(rows[0].nbytes * len(rows)),
                time.perf_counter() - t0, compiled=True, device=True)
        out = counts[: len(row_ids), : len(slices)]
        return self._topn_counts_memoize(pkey, out, epoch)

    # Host result-array memo (epoch-validated, SEPARATE from the
    # key-only prelude cache so pinned arrays can't evict plan
    # preludes): byte-budgeted like the stack cache.
    RESULT_MEMO_BYTES = 64 << 20
    RESULT_MEMO_ENTRY_MAX = 4 << 20

    def _memo_epoch_current(self, index, stored):
        """Current validity value matching a STORED memo epoch's
        shape: ints are process-local scoped epochs; tuples are
        distributed epoch-vector tokens, re-derived (with probes for
        stale peers, TTL-bounded) over the token's own host set.
        None -> unverifiable -> miss."""
        from pilosa_tpu.storage import fragment as _frag

        if type(stored) is int:
            return _frag.mutation_epoch(index)
        ep = self.epochs
        if ep is None:
            return None
        return ep.validate(index, stored)

    def _result_memo_get(self, key):
        # Central kill switch: covers the whole-result memos AND the
        # topnc candidate-matrix memo, so PILOSA_TPU_RESULT_MEMO=0 (or
        # a pinned _force_path in tests/benchmarks) measures execution
        # paths, never dict lookups.
        if (self._result_memo_off
                or getattr(self, "_force_path", None) is not None):
            return None
        qs = querystats.active()
        with self._cache_mu:
            hit = self._result_memo.get(key)
        if hit is None:
            if qs is not None:
                qs.add("cacheMisses", 1)
            return None
        # Validation OUTSIDE the cache lock: a cluster token check may
        # probe a stale peer (cluster/epochs.py) and must not wedge
        # every other memo under _cache_mu while it waits.
        # key[1] is the index in every result-memo key shape.
        cur = self._memo_epoch_current(key[1], hit[0])
        if cur is None or hit[0] != cur:
            if cur is not None:
                # Stale entries are dead weight: unreadable forever
                # (epochs are monotone) yet still charged — drop them
                # now so they can't crowd out live entries at the
                # budget edge. (A None token is only a visibility
                # lapse; the entry may validate again.)
                with self._cache_mu:
                    if self._result_memo.get(key) is hit:
                        self._result_memo.pop(key)
                        self._result_memo_bytes -= hit[2]
            if qs is not None:
                qs.add("cacheMisses", 1)
            return None
        with self._cache_mu:
            if key in self._result_memo:
                self._result_memo[key] = self._result_memo.pop(key)
        if qs is not None:
            qs.add("cacheHits", 1)
        return hit[1]

    @staticmethod
    def _memo_key_cost(key):
        """Rough host bytes a memo KEY itself pins: the slices tuple of
        a 10k-slice query is ~300 KB of ints/pointers — far more than a
        scalar entry's 8-byte value — so the budget must charge it or
        distinct-query churn grows unbounded under a budget that
        "never" fills."""
        cost = 64
        for part in key:
            if isinstance(part, tuple):
                cost += 16 + 32 * len(part)
            elif isinstance(part, str):
                cost += 49 + len(part)
            else:
                cost += 28
        return cost

    def _topn_counts_memoize(self, key, counts, epoch):
        """Cache a result array (host ints); callers must treat the
        cached array as immutable (both phase callers derive fresh
        arrays via np.where before mutating). Budget accounting
        charges the key's own footprint alongside the array."""
        if (self._result_memo_off
                or getattr(self, "_force_path", None) is not None):
            # Reads are blocked in this mode (kill switch / pinned
            # execution path) — writing unreadable entries would only
            # pay lock + eviction churn and pin dead arrays.
            return counts
        cost = counts.nbytes + self._memo_key_cost(key)
        if cost > self.RESULT_MEMO_ENTRY_MAX:
            return counts
        with self._cache_mu:
            old = self._result_memo.pop(key, None)
            if old is not None:
                self._result_memo_bytes -= old[2]
            while (self._result_memo
                   and self._result_memo_bytes + cost
                   > self.RESULT_MEMO_BYTES):
                k = next(iter(self._result_memo))
                self._result_memo_bytes -= self._result_memo.pop(k)[2]
            self._result_memo[key] = (epoch, counts, cost)
            self._result_memo_bytes += cost
        return counts

    @staticmethod
    def _topn_pairs(row_ids, counts):
        """Sum the per-slice count matrix and sort pairs the way
        pairs_add orders a merged result: (-count, id)."""
        totals = counts.sum(axis=1)
        pairs = [(int(rid), int(t))
                 for rid, t in zip(row_ids, totals) if t > 0]
        pairs.sort(key=lambda rc: (-rc[1], rc[0]))
        return pairs

    def _batched_topn_ids(self, index, call, slices):
        """Exact TopN re-query (phase 2): per-candidate popcounts over
        slice stacks in one fused XLA program, mirroring the serial
        per-slice threshold-then-sum semantics — including the Tanimoto
        threshold variant. None when ineligible (unbatchable src
        tree / candidate set too large / empty)."""
        row_ids, has_ids = call.uint_slice_arg("ids")
        if not slices or not has_ids or not row_ids:
            return None
        frame_name, view, _, min_threshold, tanimoto = (
            self._topn_call_params(call))
        # The serial path walks physical rows against set(row_ids), so
        # duplicate user-supplied ids yield one pair each — dedupe.
        row_ids = sorted(set(row_ids))

        leaves = []
        plan = None
        if call.children:
            plan, leaves = self._plan_memoized(index, call.children[0])
            if plan is None:
                return None

        allowed = self._topn_attr_allowed(index, call, frame_name)
        if allowed is not None:
            row_ids = [rid for rid in row_ids if rid in allowed]
            if not row_ids:
                return []

        counts = self._topn_candidate_counts(
            index, frame_name, view, row_ids, slices, tanimoto, plan,
            leaves)
        if counts is None or counts is BATCH_OVER_BUDGET:
            return counts
        counts = np.where(counts >= min_threshold, counts, 0)
        return self._topn_pairs(row_ids, counts)

    def _batched_topn_phase1(self, index, call, slices):
        """Approximate TopN phase 1 (candidate discovery) as one fused
        program, eligible when a src tree is present (without one the
        serial path reads host-cached row counts and never touches the
        device). Exact |row ∩ src| per (candidate, slice) over the union
        of the slices' ranked-cache entries, masked per slice back to
        that slice's own cache membership (ref: topBitmapPairs
        fragment.go:965), per-slice threshold + top-n truncation, then
        the cross-slice pairs_add merge — bit-identical to the serial
        per-fragment walk. None when ineligible."""
        if not slices:
            return None
        frame_name, view, n, min_threshold, tanimoto = (
            self._topn_call_params(call))
        if not call.children:
            return None
        plan, leaves = self._plan_memoized(index, call.children[0])
        if plan is None:
            return None

        frags = self._frag_list(index, frame_name, view, slices)
        allowed = self._topn_attr_allowed(index, call, frame_name)
        if allowed is None and any(
                frag is not None
                and frag.cache_entry_count() > self.TOPN_CANDIDATES_MAX
                for frag in frags):
            # One slice's own candidates already pass what the
            # candidate program takes, so no window of the slices
            # fits: decline before the union below is built. (At
            # 500,000 cached rows in one fragment that union cost the
            # path model's every-64th retry of this shape ~100 ms
            # only to be declined at the bucket check.)
            return None
        # cache_entry_ids serves evicted fragments from the sidecar
        # through the lazy path — phase 1 over a cold slice list no
        # longer faults every fragment in just to read candidate ids.
        ent_sets = [
            frag.cache_entry_ids() if frag is not None else frozenset()
            for frag in frags]
        if allowed is not None:
            ent_sets = [es & allowed for es in ent_sets]

        union_ids = sorted(set().union(*ent_sets))
        if not union_ids:
            return []
        counts = self._topn_candidate_counts(
            index, frame_name, view, union_ids, slices, tanimoto, plan,
            leaves, candidates_shrink=True)
        if counts is None or counts is BATCH_OVER_BUDGET:
            return counts

        # Per-slice cache-membership mask + threshold, then the serial
        # path's per-slice top-n truncation before the merge.
        mask = np.zeros(counts.shape, dtype=bool)
        pos = {rid: i for i, rid in enumerate(union_ids)}
        for j, es in enumerate(ent_sets):
            for rid in es:
                mask[pos[rid], j] = True
        counts = np.where(mask & (counts >= min_threshold), counts, 0)
        if n:
            ids_arr = np.asarray(union_ids, dtype=np.uint64)
            for j in range(counts.shape[1]):
                col = counts[:, j]
                nz = np.nonzero(col)[0]
                if len(nz) > n:
                    order = nz[np.lexsort((ids_arr[nz], -col[nz]))]
                    col[order[n:]] = 0
        return self._topn_pairs(union_ids, counts)

    def _batched_src_fn(self, tree_key, plan, padded_n, width32):
        import jax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def fn(*args):
                return eval_node(plan, args, shape)
            return fn

        return self._cached_fn(("src", tree_key, padded_n, width32),
                               build, "topn_src", _plan_operands(plan))

    def _batched_topn_fn(self, has_src, r_pad, padded_n, gathered):
        """(program, whether the fn cache already held it): int32
        [r_pad, padded_n] popcounts of the candidates' rows (one
        ``_rows_stack`` or ``r_pad`` leaf stacks), each intersected
        with its slice's src where there is one."""
        def build():
            def fn(*args):
                src, rows = (args[0], args[1:]) if has_src else (None, args)
                return _per_candidate(
                    lambda r, s: _popcounts(r if s is None else r & s),
                    src, rows, gathered)
            return fn

        key = ("topn", has_src, r_pad, padded_n, gathered)
        hit = key in self._batched_cache
        return self._cached_fn(
            key, build, "topn_src_rows" if has_src else "topn_rows",
            r_pad), hit

    def _batched_topn_tanimoto_fn(self, r_pad, padded_n, gathered):
        """(program, whether the fn cache already held it): the
        per-(candidate, slice) |row ∩ src| of the candidates' rows,
        zeroed where the integer gate drops the pair."""
        import jax.numpy as jnp

        from pilosa_tpu.ops import topn as topn_ops

        def build():
            def fn(src, threshold, *rows):
                inter = _per_candidate(lambda r, s: _popcounts(r & s),
                                       src, rows, gathered)
                row_n = _per_candidate(lambda r, s: _popcounts(r),
                                       src, rows, gathered)
                keep = topn_ops.tanimoto_keep(
                    inter, row_n, _popcounts(src)[None, :], threshold)
                return jnp.where(keep, inter, 0)
            return fn

        key = ("topn_tan", r_pad, padded_n, gathered)
        hit = key in self._batched_cache
        return self._cached_fn(key, build, "topn_tanimoto", r_pad), hit

    def _batched_sum(self, index, call, slices):
        """Sum over the local slice list as one sharded XLA program:
        planes stack ``uint32[S, depth+1, W]`` + optional filter tree,
        fused popcounts per (slice, plane) — the cross-slice analog of
        Fragment.field_sum. Returns None when ineligible. Under a
        trace the call is cut as a batched Count is: ``sum.plan`` (the
        prelude), ``kernel:sum_batched`` (> ``kernel.fn`` /
        ``.dispatch`` / ``.wait`` / ``.fetch``), ``sum.reduce``."""
        with tracing.span("sum.plan", slices=len(slices)) as psp:
            pre = self._bsi_batch_prelude(index, call, slices, psp)
        if pre is None or pre is BATCH_OVER_BUDGET:
            return pre
        field, depth, plan, planes_stack, leaf_stacks, padded_n, win = pre

        tree_key = str(plan)
        stacks = [planes_stack, *leaf_stacks]
        obs = kerneltime_mod.ACTIVE
        with tracing.span("kernel:sum_batched", slices=len(slices),
                          width32=win[1]) as ksp:
            traced = ksp is not tracing.NOP_SPAN
            hit = True
            if traced or obs.enabled:
                # As _batched_count: a racy, lock-free membership read.
                hit = (("sum", tree_key, depth, padded_n, win[1])
                       in self._batched_cache)
            with tracing.span("kernel.fn") as fsp:
                fn = self._batched_sum_fn(tree_key, plan, depth, padded_n,
                                          win[1])
                if traced:
                    fsp.tag(compile=not hit)
            run = _run_outputs_split if traced else _run_outputs
            t0 = time.perf_counter()
            plane_counts, filt_counts = run(fn, stacks)
            if obs.enabled:
                # One cost row per (slice-count, width) shape class, as
                # a batched Count's: a compile always records (it is
                # what ``compileCalls`` of /debug/kernels counts), a
                # steady dispatch 1-in-OBS_STRIDE with scaled weight.
                self._obs_tick = w = self._obs_tick + 1
                w = 0 if w % self.OBS_STRIDE else self.OBS_STRIDE
                if not hit or w:
                    obs.note(
                        "sum_batched", "dense*dense",
                        kerneltime_mod.shape_bucket(padded_n * win[1] * 4),
                        time.perf_counter() - t0, compiled=not hit,
                        device=True, n=(1 if not hit else w))
        with tracing.span("sum.reduce"):
            count = int(filt_counts[: len(slices)].sum())
            total = sum((1 << i) * int(plane_counts[: len(slices), i].sum())
                        for i in range(depth))
            return SumCount(total + count * field.min, count)

    def _bsi_batch_prelude(self, index, call, slices,
                           span=tracing.NOP_SPAN):
        """Shared eligibility + stack build for batched BSI aggregates
        (Sum/Min/Max): (field, depth, plan, planes_stack, leaf_stacks,
        padded_n), or None when ineligible (missing frame/field,
        unbatchable filter tree, over device budget). ``span`` (the
        caller's, around this call) is tagged ``memo`` = hit or miss,
        ``leaves`` and ``rows`` (row-equivalents a slice the program
        reads); a miss runs under ``build.frags`` / ``build.window`` /
        ``build.args`` as a Count's does."""
        import jax

        from pilosa_tpu.storage import fragment as _frag

        if not slices:
            return None
        qs = querystats.active()
        t0 = time.perf_counter() if qs is not None else 0.0
        resolved = self._co_bsi_resolve(index, call)
        if resolved is None:
            return None
        frame_name, field_name, field, depth, plan, leaves = resolved
        rows = depth + 1 + sum(self._spec_rows(sp) for sp in leaves)
        pkey = ("bsi", index, slice_key(slices), frame_name, field_name,
                depth, str(plan), tuple(leaves))
        memo = self._prelude_memo_get(pkey)
        if span is not tracing.NOP_SPAN:
            span.tag(memo="hit" if memo is not None else "miss",
                     leaves=len(leaves), rows=rows)
        if memo is not None:
            if qs is not None:
                qs.add("planMs", (time.perf_counter() - t0) * 1000)
            (mfield, mdepth, mplan), stacks, (padded_n, win) = memo
            return (mfield, mdepth, mplan, stacks[0], stacks[1:],
                    padded_n, win)
        epoch = _frag.mutation_epoch(index)

        n_dev = len(jax.devices())
        pad = (-len(slices)) % n_dev
        with tracing.span("build.frags") as fsp:
            # The planes spec may not be among the filter's leaves;
            # include it explicitly so the window covers the BSI
            # fragments too.
            win_leaves = leaves + [("planes", frame_name, field_name, depth)]
            walked = []
            frag_map = self._leaf_frags(index, win_leaves, slices,
                                        walked=walked)
            if fsp is not tracing.NOP_SPAN:
                fsp.tag(walked=len(walked))
        with tracing.span("build.window"):
            win = self._union_window(frag_map)
            if not self._fits_device_budget(rows, len(slices) + pad,
                                            width32=win[1]):
                return BATCH_OVER_BUDGET
        with tracing.span("build.args"):
            planes_stack = self._planes_stack(
                index, frame_name, field_name, depth, slices, pad, n_dev,
                win=win,
                frags=frag_map.get((frame_name,
                                    view_field_name(field_name))))
            leaf_stacks = [self._spec_arg(index, sp, slices, pad, n_dev,
                                          win, frag_map)
                           for sp in leaves]
            planes_spec = [("key", ("planes", index, frame_name,
                                    field_name, depth, slice_key(slices),
                                    n_dev, win[0], win[1]))]
            leaf_specs = self._prelude_specs(index, leaves, leaf_stacks,
                                             slices, n_dev, win)
            self._prelude_memo_put(pkey, (field, depth, plan),
                                   planes_spec + leaf_specs,
                                   (len(slices) + pad, win), epoch)
        if qs is not None:
            qs.add("planMs", (time.perf_counter() - t0) * 1000)
        return (field, depth, plan, planes_stack, leaf_stacks,
                len(slices) + pad, win)

    def _batched_min_max(self, index, call, slices, find_max):
        """Min/Max over the local slice list as ONE global bit-descent:
        instead of per-slice descents reduced host-side, the descent
        runs over the whole sharded ``uint32[S, depth+1, W]`` plane
        stack, choosing each bit by a cross-slice (psum) occupancy test.
        The result equals the serial reduce exactly — a slice whose
        local extremum loses globally holds no columns at the global
        extremum. None when ineligible; BATCH_EMPTY when no value
        matches (the serial path reports empty as None)."""
        pre = self._bsi_batch_prelude(index, call, slices)
        if pre is None or pre is BATCH_OVER_BUDGET:
            return pre
        field, depth, plan, planes_stack, leaf_stacks, padded_n, win = pre

        fn = self._batched_minmax_fn(str(plan), plan, depth, find_max,
                                     padded_n, win[1])
        indicators, count = fn(planes_stack, *leaf_stacks)
        count = int(count)
        if count == 0:
            return BATCH_EMPTY
        value = sum((1 << i) * int(b)
                    for i, b in enumerate(np.asarray(indicators)))
        return SumCount(value + field.min, count)

    def _batched_minmax_fn(self, tree_key, plan, depth, find_max,
                           padded_n, width32):
        import jax
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def fn(planes, *leaf_args):
                exists = planes[:, depth, :]
                if plan is None:
                    m = exists
                else:
                    m = lax.bitwise_and(
                        exists, eval_node(plan, leaf_args, shape))
                return Executor._minmax_descent(planes, m, depth,
                                                find_max)
            return fn

        return self._cached_fn(
            ("minmax", tree_key, depth, find_max, padded_n, width32),
            build, "max_batched" if find_max else "min_batched",
            _plan_operands(plan))

    def _batched_sum_fn(self, tree_key, plan, depth, padded_n, width32):
        import jax
        import jax.numpy as jnp
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def fn(planes, *leaf_args):
                exists = planes[:, depth, :]
                if plan is None:
                    filt = exists
                else:
                    filt = lax.bitwise_and(
                        exists, eval_node(plan, leaf_args, shape))
                masked = lax.bitwise_and(planes[:, :depth, :],
                                         filt[:, None, :])
                counts = jnp.sum(
                    lax.population_count(masked).astype(jnp.int32), axis=2)
                filt_counts = jnp.sum(
                    lax.population_count(filt).astype(jnp.int32), axis=1)
                return counts, filt_counts
            return fn

        return self._cached_fn(("sum", tree_key, depth, padded_n,
                                width32), build, "sum_batched",
                               _plan_operands(plan))

    def _fits_device_budget(self, n_rows, padded_slices, width32=None):
        """Up-front HBM guard for batched stacks: ``n_rows`` row-sized
        planes of ``padded_slices`` slices at the plan's column-window
        width must fit the stack budget — otherwise the allocation
        itself could OOM the device before any cache-size check runs,
        where the serial per-slice path streams one small matrix at a
        time. Narrow windows admit plans full-width stacks could not."""
        from pilosa_tpu import WORDS_PER_SLICE

        if width32 is None:
            width32 = WORDS_PER_SLICE
        return (n_rows * padded_slices * width32 * 4
                <= self.STACK_CACHE_BYTES)

    @staticmethod
    def _frag_tokens(frags):
        """Cache-validity token per fragment: (process-unique id,
        mutation version) — a deleted+recreated fragment gets a new uid,
        so version-counter collisions can never serve stale stacks."""
        return tuple((f._uid, f._version) if f is not None else (-1, -1)
                     for f in frags)

    def _stack_cache_lookup(self, key, tokens):
        """One locked lookup → (valid_stack | None, stale entry
        (old_tokens, stack) | None). The stale entry feeds the
        incremental-update path (SURVEY §7 'hard part': writes merge
        into device blocks instead of forcing full rebuilds)."""
        with self._cache_mu:
            hit = self._stack_cache.get(key)
            if hit is None:
                return None, None
            if hit[0] is tokens or hit[0] == tokens:
                # LRU: a hit refreshes recency so hot stacks survive
                # eviction pressure. Re-stamped with the caller's
                # tuple: a stack validated against a FragList's tokens
                # revalidates by identity, not by comparing one pair
                # per slice, until the list itself is walked again.
                del self._stack_cache[key]
                self._stack_cache[key] = (tokens, hit[1], hit[2])
                return hit[1], None
            return None, (hit[0], hit[1])

    def _scatter_rows_fn(self):
        """Jitted row scatter for incremental stack updates — one
        compiled program per (stack, idx, rows) shape signature instead
        of eager per-op dispatch (which also breaks downstream compile
        caches by changing placement)."""
        import jax

        def build():
            def fn(stack, idx, rows):
                return stack.at[idx].set(rows)
            return fn

        return self._cached_fn(("scatter_rows",), build,
                               "stack_scatter", 1)

    def _stack_incremental(self, key, tokens, stale, build_changed,
                           n_dev, ndim):
        """Shared incremental-update policy for row and plane stacks:
        when a stale cached stack differs in ≤1/4 of its fragments,
        scatter just those fragments' fresh rows into it (jitted) and
        re-cache. Returns the updated stack, or None → full rebuild."""
        import jax.numpy as jnp

        if stale is None:
            return None
        old_tokens, stack = stale
        changed = [i for i, (o, nw) in enumerate(zip(old_tokens, tokens))
                   if o != nw]
        if not changed or len(changed) > max(1, len(tokens) // 4):
            return None
        stack = self._scatter_rows_fn()(
            stack, jnp.asarray(changed), jnp.stack(build_changed(changed)))
        stack = self._shard_stack(stack, n_dev, ndim)
        self._stack_cache_put(key, tokens, stack)
        return stack

    def _stack_cache_put(self, key, tokens, stack):
        """``tokens`` MUST be captured before the stack was built: a
        concurrent writer between build and put then makes the next
        get miss (tokens advanced) instead of serving the stale stack.
        Re-deriving tokens here would stamp old data as current."""
        nbytes = stack.size * 4
        with self._cache_mu:
            old = self._stack_cache.pop(key, None)
            if old is not None:
                self._stack_cache_bytes -= old[2]
            if nbytes <= self.STACK_CACHE_BYTES:
                # Evict least-recently-used until under the device-
                # memory budget (stacks can be GBs at ~10k-slice scale).
                while (self._stack_cache_bytes + nbytes
                       > self.STACK_CACHE_BYTES):
                    k = next(iter(self._stack_cache))
                    self._stack_cache_bytes -= self._stack_cache.pop(k)[2]
                self._stack_cache[key] = (tokens, stack, nbytes)
                self._stack_cache_bytes += nbytes

    def _shard_stack(self, stack, n_dev, ndim):
        if n_dev <= 1:
            return stack
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec("slice", *([None] * (ndim - 1)))
        return jax.device_put(stack, NamedSharding(self._local_mesh(),
                                                   spec))

    def _warm_enabled(self):
        """Width warming pays on an accelerator (a 20-40 s XLA compile
        would otherwise land in the serving path the first time a
        write widens the window into a new bucket); on CPU the
        background compile competes with serving threads. Forced via
        PILOSA_TPU_WARM_WIDTHS=1/0."""
        cached = getattr(self, "_warm_enabled_memo", None)
        if cached is None:
            import os as _os

            env = _os.environ.get("PILOSA_TPU_WARM_WIDTHS")
            if env is not None:
                cached = env.lower() in ("1", "true", "yes")
            else:
                import jax

                cached = jax.default_backend() != "cpu"
            self._warm_enabled_memo = cached
        return cached

    def _warm_budget_bytes(self):
        """Transient-HBM cap for background width warming (see
        _warm_wider). Memoized; 0 = unbounded."""
        cached = getattr(self, "_warm_budget_memo", None)
        if cached is not None:
            return cached
        import os as _os

        env = _os.environ.get("PILOSA_TPU_WARM_BUDGET_MB")
        if env is not None:
            try:
                budget = max(0, int(env)) << 20
            except ValueError:
                # Warming is best-effort; a malformed knob must not
                # take down the serving path that calls this.
                budget = 4 << 30
        else:
            budget = 4 << 30
            try:
                import jax

                stats = jax.local_devices()[0].memory_stats()
                limit = (stats or {}).get("bytes_limit", 0)
                if limit:
                    budget = limit // 4
            except Exception:  # noqa: BLE001 — stats are best-effort; pilint: disable=swallow
                pass
        self._warm_budget_memo = budget
        return budget

    def _warm_wider(self, tree_key, plan, padded_n, width32, stacks):
        """After serving a count-tree query at window width W, compile
        the SAME shape's wider width buckets in a daemon thread using
        dummy zero stacks (matching dtype/shape/sharding, so the jit
        cache key is identical to a future real call). A write that
        later widens the window then finds its program already
        compiled instead of stalling serving for a full XLA compile.
        Only uniform-stack plans warm (every arg is a row stack
        ``uint32[padded_n, W]``); mixed-arg shapes (BSI bits args)
        skip."""
        from pilosa_tpu import WORDS_PER_SLICE

        if (width32 >= WORDS_PER_SLICE or self._fixed_full_window
                or not self._warm_enabled()):
            return
        if any(getattr(s, "shape", None) != (padded_n, width32)
               for s in stacks):
            return
        wider, w = [], width32 * 4
        while w < WORDS_PER_SLICE:
            wider.append(w)
            w *= 4
        wider.append(WORDS_PER_SLICE)
        # HBM bound: warming executes with a real zero stack, so the
        # transient footprint is ~3 buffers of padded_n x w x 4 B
        # (shared input + output + one fusion intermediate). Skip
        # buckets that would spike past the budget — a concurrent
        # serving query pushed into OOM-and-serial-fallback costs more
        # latency than the compile the warm was meant to hide.
        # Default: 25% of device memory (memory_stats bytes_limit),
        # 4 GiB when the backend doesn't report one. Override via
        # PILOSA_TPU_WARM_BUDGET_MB; <= 0 lifts the bound.
        budget = self._warm_budget_bytes()
        if budget > 0:
            # The budget is PER-DEVICE (memory_stats of one device);
            # the warm dummy is sharded over the slice axis, so each
            # device holds 1/n_dev of the stack.
            import jax

            n_dev = max(1, len(jax.devices()))
            wider = [w for w in wider
                     if padded_n * w * 4 * 3 // n_dev <= budget]
            if not wider:
                return
        # Warm-or-not keys off _batched_cache MEMBERSHIP, not a
        # permanent latch: an fn evicted by the FIFO cap (or dropped
        # after a failed warm) becomes warmable again, so wider-bucket
        # protection survives cache churn.
        with self._cache_mu:
            missing = [w for w in wider
                       if (tree_key, padded_n, w) not in self._batched_cache]
        if not missing:
            return
        with self._warm_mu:
            for w in missing:
                qk = (tree_key, padded_n, w, len(stacks))
                if qk in self._warm_inflight:
                    continue
                self._warm_inflight.add(qk)
                self._warm_q.append((plan,) + qk)
            if self._warm_q and (self._warm_thread is None
                                 or not self._warm_thread.is_alive()):
                self._warm_thread = threading.Thread(
                    target=self._warm_loop, daemon=True)
                self._warm_thread.start()

    def _warm_loop(self):
        import jax.numpy as jnp

        while True:
            with self._warm_mu:
                if not self._warm_q:
                    # Clear the handle under the lock BEFORE exiting so
                    # an enqueuer racing this exit spawns a fresh
                    # worker instead of seeing a still-alive corpse and
                    # stranding its queue entries.
                    self._warm_thread = None
                    return
                plan, tree_key, padded_n, w, n_args = self._warm_q.pop(0)
            try:
                import jax

                fn = self._batched_fn(tree_key, plan, padded_n, w)
                dummy = self._shard_stack(
                    jnp.zeros((padded_n, w), jnp.uint32),
                    len(jax.devices()), 2)
                jax.block_until_ready(fn(*([dummy] * n_args)))
                self._warm_stats["compiled"] += 1
            except Exception:  # noqa: BLE001 — warming is best-effort
                logger.warning("width warm of %r at %dx%d failed",
                               tree_key, padded_n, w, exc_info=True)
                self._warm_stats["failed"] += 1
                # Drop the (possibly uncompiled) wrapper so a later
                # query re-triggers warming rather than trusting it.
                with self._cache_mu:
                    self._batched_cache.pop((tree_key, padded_n, w),
                                            None)
            finally:
                with self._warm_mu:
                    self._warm_inflight.discard(
                        (tree_key, padded_n, w, n_args))

    def warm_snapshot(self):
        """Width-warmer state for /debug/vars (widthWarmer group):
        programs compiled and failed so far, and those still queued or
        compiling — zero ``inflight`` means the warmer is quiescent."""
        with self._warm_mu:
            return dict(self._warm_stats,
                        inflight=len(self._warm_inflight))

    def _cached_fn(self, key, build, tier, operands):
        """Bounded cache of jitted tree evaluators. ``build`` returns
        the plain function; it is jitted here under its program name
        (``program_name``), which is what a device trace shows."""
        import jax

        with self._cache_mu:
            if key in self._batched_cache:
                return self._batched_cache[key]
        fn = build()
        fn.__name__ = fn.__qualname__ = program_name(tier, operands)
        fn = jax.jit(fn)
        with self._cache_mu:
            while len(self._batched_cache) >= self.BATCHED_FN_CACHE_MAX:
                self._batched_cache.pop(next(iter(self._batched_cache)))
            self._batched_cache[key] = fn
        return fn

    def _zero_row(self, width32=None):
        import jax.numpy as jnp

        from pilosa_tpu import WORDS_PER_SLICE

        if width32 is None:
            width32 = WORDS_PER_SLICE
        if getattr(self, "_zero_rows", None) is None:
            self._zero_rows = {}
        arr = self._zero_rows.get(width32)
        if arr is None:
            arr = self._zero_rows[width32] = jnp.zeros(width32,
                                                       jnp.uint32)
        return arr

    def _local_mesh(self):
        """Local device mesh for sharded batched stacks, memoized
        against the device-topology fingerprint: a runtime whose
        device set changed between calls (a multi-host group joining
        or degrading, a forced-host-platform test reconfigure) must
        never serve stacks sharded over a mesh naming dead devices —
        the stale memo was silently permanent before this versioning."""
        import jax

        devs = jax.devices()
        fp = (len(devs), tuple(d.id for d in devs))
        if getattr(self, "_mesh", None) is None \
                or getattr(self, "_mesh_fp", None) != fp:
            from pilosa_tpu.parallel.mesh import make_mesh

            self._mesh = make_mesh()
            self._mesh_fp = fp
        return self._mesh

    @staticmethod
    def _eval_node(node, args, shape=None):
        """Left-fold tree evaluation on stacked arrays — same pairwise
        order as the serial _execute_bitmap_call_slice fold. "bsi"
        nodes vmap the per-fragment descent kernels over the slice
        axis; "empty" is a statically-known-zero result (out-of-range
        shortcut) costing no stack arg."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from pilosa_tpu.ops import bsi as bsi_ops

        kind = node[0]
        if kind == "leaf":
            return args[node[1]]
        if kind == "empty":
            return jnp.zeros(shape, jnp.uint32)
        if kind == "bsi":
            _, ppos, bpos, bkind, op, depth = node
            planes = args[ppos]
            exists = planes[:, depth, :]
            body = planes[:, :depth, :]
            if bkind == "between":
                return jax.vmap(bsi_ops.bsi_between,
                                in_axes=(0, 0, None, None))(
                    body, exists, args[bpos[0]], args[bpos[1]])
            fn = {"==": bsi_ops.bsi_eq, "!=": bsi_ops.bsi_neq,
                  "<": bsi_ops.bsi_lt, "<=": bsi_ops.bsi_lte,
                  ">": bsi_ops.bsi_gt, ">=": bsi_ops.bsi_gte}[op]
            return jax.vmap(fn, in_axes=(0, 0, None))(
                body, exists, args[bpos[0]])
        out = None
        for kid in node[1]:
            v = Executor._eval_node(kid, args, shape)
            if out is None:
                out = v
            elif kind == "Intersect":
                out = lax.bitwise_and(out, v)
            elif kind == "Union":
                out = lax.bitwise_or(out, v)
            elif kind == "Difference":
                out = lax.bitwise_and(out, lax.bitwise_not(v))
            else:  # Xor
                out = lax.bitwise_xor(out, v)
        return out

    def _batched_fn(self, tree_key, plan, padded_n, width32):
        """Jitted tree evaluator, cached per (tree shape, stack height,
        window width) so repeated query shapes reuse one compiled
        executable."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        eval_node = self._eval_node
        shape = (padded_n, width32)

        def build():
            def fn(*args):
                out = eval_node(plan, args, shape)
                return jnp.sum(
                    lax.population_count(out).astype(jnp.int32), axis=1)
            return fn

        return self._cached_fn((tree_key, padded_n, width32), build,
                               "count_batched", _plan_operands(plan))

    # --------------------------------------------------------------- sum

    def _execute_sum(self, index, call, slices, opt):
        """(ref: executeSum executor.go:328-366 + executeSumCountSlice)."""
        if call.args.get("field") is None:
            raise ValueError("Sum(): field required")

        def map_fn(s):
            return self._execute_sum_count_slice(index, call, s)

        def reduce_fn(prev, v):
            if prev is None:
                return v
            return SumCount(prev.sum + v.sum, prev.count + v.count)

        def compute():
            out = self._map_reduce(
                index, slices, call, opt, map_fn, reduce_fn,
                batch_fn=self._windowed_batch(
                    lambda ns: self._coalesced_sum(index, call, ns),
                    reduce_fn))
            return out or SumCount(0, 0)

        return self._scalar_result_memo(
            "sum_res", index, call, slices, opt, compute,
            enc=lambda v: np.asarray([v.sum, v.count], dtype=np.int64),
            dec=lambda a: SumCount(int(a[0]), int(a[1])))

    def _execute_sum_count_slice(self, index, call, slice_num):
        filt = None
        if len(call.children) == 1:
            bm = self._execute_bitmap_call_slice(index, call.children[0],
                                                 slice_num)
            filt = bm.host_words(slice_num)
        frame_name = call.args.get("frame") or ""
        field_name = call.args.get("field") or ""
        frame = self.holder.index(index).frame(frame_name)
        if frame is None:
            return SumCount(0, 0)
        try:
            field = frame.field(field_name)
        except perr.ErrFieldNotFound:
            return SumCount(0, 0)
        frag = self.holder.fragment(index, frame_name,
                                    view_field_name(field_name), slice_num)
        if frag is None:
            return SumCount(0, 0)
        vsum, vcount = frag.field_sum(filt, field.bit_depth())
        return SumCount(vsum + vcount * field.min, vcount)

    def _execute_min_max(self, index, call, slices, opt, find_max):
        """Min/Max over a BSI field — TPU bit-descent per slice, reduced
        host-side."""
        field_name = call.args.get("field") or ""
        frame_name = call.args.get("frame") or ""
        frame = self.holder.index(index).frame(frame_name)
        if frame is None:
            return SumCount(0, 0)
        field = frame.field(field_name)

        def map_fn(s):
            filt = None
            if len(call.children) == 1:
                bm = self._execute_bitmap_call_slice(index, call.children[0], s)
                filt = bm.host_words(s)
            frag = self.holder.fragment(index, frame_name,
                                        view_field_name(field_name), s)
            if frag is None:
                return None
            value, count = frag.field_min_max(filt, field.bit_depth(), find_max)
            if count == 0:
                return None
            return SumCount(value + field.min, count)

        def reduce_fn(prev, v):
            # Skip empty partials: a node with no matching values
            # reports SumCount(0, 0) over the wire, which must not
            # compete as a real extremum of 0 (ref: executeMinMax
            # reduce skips other.Cnt == 0).
            if v is None or v.count == 0:
                return prev
            if prev is None:
                return v
            if v.sum == prev.sum:
                return SumCount(prev.sum, prev.count + v.count)
            better = v.sum > prev.sum if find_max else v.sum < prev.sum
            return v if better else prev

        def compute():
            out = self._map_reduce(
                index, slices, call, opt, map_fn, reduce_fn,
                batch_fn=self._windowed_batch(
                    lambda ns: self._coalesced_min_max(index, call, ns,
                                                        find_max),
                    reduce_fn))
            return out or SumCount(0, 0)

        return self._scalar_result_memo(
            "max_res" if find_max else "min_res", index, call, slices,
            opt, compute,
            enc=lambda v: np.asarray([v.sum, v.count], dtype=np.int64),
            dec=lambda a: SumCount(int(a[0]), int(a[1])))

    # -------------------------------------------------------------- topn

    def _execute_topn(self, index, call, slices, opt):
        """Two-phase TopN (ref: executeTopN executor.go:369-406):
        approximate per-slice candidates, then exact re-query of the
        merged id set. The re-query exists because a row that made one
        slice's top ``n`` may have been cut from another's; over ONE
        slice phase 1's pairs are the totals already (exact counts
        under the same gate, threshold and filter, in ``(-count, id)``
        order, cut at ``n``), so they are the answer and the re-query
        is skipped: a stated departure from ``executeTopN``, whose
        result it equals (PARITY.md). A call that arrives with
        ``ids`` is phase 2 by definition and always runs."""
        ids_arg, has_ids = call.uint_slice_arg("ids")
        n, _ = call.uint_arg("n")

        def phase(name, phase_call):
            """One pass over the slices under its span, tagged with
            the path that served it (``serial`` / ``batched``), the
            ids it was given and their bucket."""
            with tracing.span(name) as sp:
                qs = querystats.active()
                traced = sp is not tracing.NOP_SPAN and qs is not None
                mark = qs.mark() if traced else None
                pairs = self._execute_topn_slices(index, phase_call,
                                                  slices, opt)
                if traced:
                    ids = phase_call.args.get("ids") or ()
                    sp.tag(path=qs.served_since(mark),
                           candidates=len(ids),
                           bucket=self._candidate_bucket(len(ids)))
            return pairs

        def compute():
            pairs = phase("topn.phase2" if has_ids else "topn.phase1",
                          call)
            if pairs and not has_ids and not opt.remote:
                if len(slices) > 1:
                    other = call.clone()
                    other.args["ids"] = sorted(rid for rid, _ in pairs)
                    querystats.add("topnCandidates",
                                   len(other.args["ids"]))
                    pairs = phase("topn.phase2", other)
                else:
                    # The span stays where the second pass would have
                    # been, so a trace reads what it costs a request.
                    with tracing.span(
                            "topn.phase2", path="skipped",
                            candidates=len(pairs),
                            bucket=self._candidate_bucket(len(pairs))):
                        querystats.add("topnRecountsSkipped")
                if n:
                    pairs = pairs[:n]
            querystats.add("topnKept", len(pairs or ()))
            return pairs

        if has_ids:
            return compute()
        # Whole-result memo for full local TopN queries (both phases):
        # a repeated dashboard TopN over a large evicted index pays an
        # O(slices) sidecar walk per phase (~13 ms at 954 slices) for
        # an answer that cannot change until its index mutates. Pairs
        # round-trip through a uint64 array (row ids span the full
        # uint64 space); the shared helper applies the same local-only
        # and epoch rules as the scalar aggregates.
        return self._scalar_result_memo(
            "topn_res", index, call, slices, opt, compute,
            enc=lambda pairs: np.asarray(
                pairs, dtype=np.uint64).reshape(-1, 2),
            dec=lambda a: [(int(r), int(c)) for r, c in a])

    # 4 entries × (≤10 MB pairs + the pinned slices tuple) bounds the
    # memo's worst case at tens of MB without result-memo accounting.
    TOPN_DISCOVERY_MEMO_MAX = 4

    def _execute_topn_slices(self, index, call, slices, opt):
        """Both phases batch this host's slice set on the mesh:
        explicit-ids calls (phase 2, or arriving at a remote node) go
        through the exact re-query kernel; candidate discovery with a
        src tree goes through the phase-1 kernel; cross-node results
        merge via pairs_add.

        Src-less discovery has no device kernel — it reads host cache
        metadata fragment by fragment, which at 10k-slice scale is
        ~25 µs of Python per fragment per query. Its merged pairs are
        epoch-memoized (the prelude-memo class, like the device stack
        caches that also persist across "cold" queries; NOT a result
        memo — the phase-2 exact device re-count still runs per
        query). The memo is PER-NODE-LOCAL and therefore correct on
        any topology (round 5; VERDICT r4 #4): every mutation of a
        fragment this node holds — client write, remote-forwarded
        write, anti-entropy merge, hinted replay — executes in this
        process and bumps this process's epoch, so an entry over
        LOCAL slices can never outlive a local change. It covers (a)
        the whole slice set when single-node or serving a remote
        subquery (slices are all local then), and (b) the
        coordinator's own subset on a cluster, with the remote
        subsets fanning out per query (remote nodes hit their own
        memo via their opt.remote path) — cross-node merge is a
        cheap pairs_add; no cross-node invalidation protocol is
        needed because no entry ever spans another node's data. Off
        under _force_path (pinned tests must keep exercising the
        pinned path). The epoch is read BEFORE the walk so a racy
        write makes the entry stale-on-arrival, never wrong;
        oversized candidate sets skip memoization."""
        _, has_ids = call.uint_slice_arg("ids")

        discovery = (not has_ids and not call.children
                     and self._force_path is None)
        all_local = (self.cluster is None
                     or len(self.cluster.nodes) <= 1 or opt.remote
                     or self.client is None)
        if discovery and all_local:
            # Single-node, or serving a remote subquery: every slice
            # handed in is ours — one memo entry covers the set.
            return self._topn_discovery_memoized(index, call, slices)
        if discovery:
            # Coordinator on a cluster: memoize the subset this node
            # would execute anyway (primary-replica assignment, as
            # _slices_by_node), fan the rest out per query. The remote
            # fan-out is dispatched FIRST on a thread so the local
            # walk overlaps the remote round trip — as _map_reduce's
            # thread-per-node layout did before this split.
            own, remote = [], []
            for s in slices:
                owners = self.cluster.fragment_nodes(index, s)
                (own if owners and owners[0].host == self.host
                 else remote).append(s)
            rem_box = {}

            def run_remote():
                try:
                    rem_box["out"] = self._topn_map_reduce(
                        index, call, remote, opt, has_ids)
                except Exception as exc:  # noqa: BLE001 — re-raised below
                    rem_box["exc"] = exc

            wait = None
            if remote:
                wait = self._fan_pool.run(run_remote)
            out = (self._topn_discovery_memoized(index, call, own)
                   if own else [])
            if wait is not None:
                wait.wait()
                if "exc" in rem_box:
                    raise rem_box["exc"]
                rem = rem_box.get("out")
                out = pairs_add(list(out), rem or []) if out else \
                    (rem or [])
            return out
        return self._topn_map_reduce(index, call, slices, opt,
                                     has_ids) or []

    def _topn_discovery_memoized(self, index, call, slices):
        """Epoch-validated memo over a LOCAL slice subset's src-less
        discovery walk (correctness argument in _execute_topn_slices's
        docstring). Execution deliberately goes through _local_exec:
        every slice here is held by this node, whatever the ring says
        about primaries elsewhere."""
        from pilosa_tpu.storage import fragment as _frag

        memo = getattr(self, "_topn_disc_memo", None)
        if memo is None:
            memo = self._topn_disc_memo = {}
        memo_key = ("topn1", index, str(call), slice_key(slices))
        hit = memo.get(memo_key)
        if hit is not None and hit[0] == _frag.mutation_epoch(index):
            return list(hit[1])
        epoch = _frag.mutation_epoch(index)

        def batch_fn(ns):
            return self._batched_topn_phase1(index, call, ns)

        def map_fn(s):
            return self._execute_topn_slice(index, call, s)

        out = self._local_exec(call, slices, map_fn, pairs_add,
                               self._windowed_batch(batch_fn, pairs_add))
        out = [] if out is BATCH_EMPTY or out is None else out
        # 100k pairs ≈ 10 MB of tuples — beyond that the memo would be
        # an unaccounted host-memory sink, not a walk-skip.
        if len(out) <= 100_000:
            while (memo_key not in memo
                   and len(memo) >= self.TOPN_DISCOVERY_MEMO_MAX):
                memo.pop(next(iter(memo)))  # FIFO, as _result_memo
            memo[memo_key] = (epoch, tuple(out))
        return out

    def _topn_map_reduce(self, index, call, slices, opt, has_ids):
        def batch_fn(ns):
            if has_ids:
                return self._batched_topn_ids(index, call, ns)
            return self._batched_topn_phase1(index, call, ns)

        def map_fn(s):
            return self._execute_topn_slice(index, call, s)

        return self._map_reduce(index, slices, call, opt, map_fn,
                                pairs_add,
                                batch_fn=self._windowed_batch(batch_fn,
                                                              pairs_add))

    def _topn_probe_row(self, index, call, frame_name, view):
        """The row id of a TopN's probe where its one child is a plain
        ``Bitmap`` of a row of the very fragments the TopN scans (the
        TopN's frame and view: ``rowID`` with a standard-view TopN,
        ``columnID`` with ``inverse=true``), else None. Read from the
        call alone; a ``Bitmap`` the executor refuses raises here what
        executing it would have raised."""
        child = call.children[0]
        if child.name != "Bitmap" or child.children:
            return None
        child_frame, child_view, row_id = self._bitmap_row(index, child)
        return (row_id if (child_frame, child_view) == (frame_name, view)
                else None)

    def _execute_topn_slice(self, index, call, slice_num):
        """(ref: executeTopNSlice executor.go:433-500). Where the src
        is a row of the fragment the scan reads (``_topn_probe_row``)
        the child is not executed: the fragment is given the row's id
        and its program takes the probe from the HBM mirror. Any other
        child is executed to host words, as in the reference."""
        frame_name = call.args.get("frame") or DEFAULT_FRAME
        inverse = call.args.get("inverse") is True
        n, _ = call.uint_arg("n")
        attr_name = call.args.get("field") or ""
        row_ids, has_ids = call.uint_slice_arg("ids")
        min_threshold, _ = call.uint_arg("threshold")
        filters = call.args.get("filters")
        tanimoto, _ = call.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise ValueError("Tanimoto Threshold is from 1 to 100 only")

        view = VIEW_INVERSE if inverse else VIEW_STANDARD
        src = src_row = None
        if len(call.children) == 1:
            src_row = self._topn_probe_row(index, call, frame_name, view)
            if src_row is None:
                bm = self._execute_bitmap_call_slice(
                    index, call.children[0], slice_num)
                src = bm.host_words(slice_num)
        elif len(call.children) > 1:
            raise ValueError("TopN() can only have one input bitmap")

        frag = self.holder.fragment(index, frame_name, view, slice_num)
        if frag is None:
            return []

        filter_row_ids = None
        if attr_name and filters is not None:
            frame = self.holder.index(index).frame(frame_name)
            filter_row_ids = [
                rid for rid in frame.row_attr_store.ids()
                if frame.row_attr_store.attrs(rid).get(attr_name) in filters]

        if call.children:
            stat = ("topnProbeFromHost" if src_row is None
                    else "topnProbeFromMirror")
            querystats.add(stat)
            with self._cache_mu:
                self.topn_probe[stat] += 1
        opt = TopOptions(
            n=int(n),
            src=src,
            src_row=src_row,
            row_ids=row_ids if has_ids else None,
            filter_row_ids=filter_row_ids,
            min_threshold=max(int(min_threshold), MIN_THRESHOLD),
            tanimoto_threshold=int(tanimoto),
        )
        pairs = frag.top(opt)
        if opt.selected:
            stat = "topnSelect" + opt.selected.capitalize()
            querystats.add(stat)
            with self._cache_mu:
                self.topn_select[stat] += 1
        return pairs

    # ------------------------------------------------------------ writes

    @staticmethod
    def _burst_text(kind, tuples):
        """Re-emit canonical burst text for a subset of calls — the
        receiving node's executor re-enters the burst fast path."""
        return "\n".join(f'{kind}(frame="{f}", {k1}={v1}, {k2}={v2})'
                         for f, k1, v1, k2, v2 in tuples)

    def _burst_fanout(self, index, burst, opt, kind, set_value=True):
        """Multi-node write burst: group calls by owning node, apply
        this host's subset through the bulk path, and forward each
        remote subset as ONE canonical burst query (the peer re-enters
        the burst fast path under Remote=true) instead of the serial
        path's one HTTP round trip per call per replica. Per-call
        results OR across replicas exactly like executeSetBitView
        (executor.go:1059-1088); DOWN replicas get per-call hints.
        None when ineligible (inverse-enabled frames — the two views'
        owner sets differ — or any shape bulk can't take)."""
        from pilosa_tpu.pql import Call

        idx = self.holder.index(index)
        call_slices = []
        # Upfront validation mirrors EVERYTHING the per-node bulk
        # executors check (ids, labels, field range, inverse), so no
        # sub-burst can be rejected after another was already applied.
        for frame_name, k1, v1, k2, v2 in burst:
            frame = idx.frame(frame_name)
            if frame is None:
                return None
            if kind == "SetFieldValue":
                if k1 == idx.column_label:
                    col, fname, val = int(v1), k2, int(v2)
                elif k2 == idx.column_label:
                    col, fname, val = int(v2), k1, int(v1)
                else:
                    return None
                try:
                    field = frame.field(fname)
                except perr.ErrFieldNotFound:
                    return None
                if val < field.min or val > field.max:
                    return None
            else:
                if frame.inverse_enabled:
                    return None
                if k1 == frame.row_label and k2 == idx.column_label:
                    row, col = int(v1), int(v2)
                elif k2 == frame.row_label and k1 == idx.column_label:
                    row, col = int(v2), int(v1)
                else:
                    return None
                if not 0 <= row < 2 ** 63:
                    return None
            if col < 0 or col >= 2 ** 63:
                return None
            call_slices.append(col // SLICE_WIDTH)

        by_host, nodes_by_host = {}, {}
        for k, s in enumerate(call_slices):
            for node in self.cluster.fragment_nodes(index, s):
                nodes_by_host[node.host] = node
                by_host.setdefault(node.host, []).append(k)

        bits = kind != "SetFieldValue"
        results = [False if bits else None] * len(burst)
        sub_opt = ExecOptions(remote=True)
        lock = threading.Lock()
        errors = []

        def run(host, ks):
            node = nodes_by_host[host]
            sub = [burst[k] for k in ks]
            try:
                if host == self.host:
                    if bits:
                        out = self._execute_setbit_burst(
                            index, sub, sub_opt, set_value)
                    else:
                        out = self._execute_setfield_burst(index, sub,
                                                           sub_opt)
                    if out is None:
                        raise RuntimeError(
                            "bulk apply disqualified after validation")
                elif self._node_is_down(node) and self._hints_allowed():
                    for f, k1, v1, k2, v2 in sub:
                        self._hint(node, index, Call(
                            kind, {"frame": f, k1: int(v1), k2: int(v2)}))
                    return
                else:
                    out = self.client.execute_query(
                        node, index, self._burst_text(kind, sub),
                        remote=True)
                if bits:
                    with lock:
                        for j, k in enumerate(ks):
                            results[k] = results[k] or bool(out[j])
            except Exception as exc:  # noqa: BLE001 — re-raised below
                with lock:
                    errors.append(exc)

        # One thread per node, like the read path's _map_reduce mapper:
        # burst latency is the slowest node's round trip, not the sum.
        threads = [threading.Thread(target=run, args=(h, ks))
                   for h, ks in by_host.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        idx_stats = getattr(idx, "stats", None)
        if idx_stats is not None and not opt.remote:
            idx_stats.count(kind, len(burst))
        return results

    def _bulk_write_stats(self, index, name, n, elapsed, query):
        """Long-query warning for the early-returning burst paths (the
        per-index counters are emitted inside each bulk executor —
        _apply_bulk_set_bits for SetBit, _execute_setfield_burst for
        SetFieldValue — gated to the coordinator)."""
        if self._hist_exec.enabled:
            self._hist_exec.observe(elapsed)
        long_query_time = getattr(self.cluster, "long_query_time", None)
        if long_query_time and elapsed > long_query_time:
            logger.warning("%.2fs query: %d-call %s burst", elapsed, n, name)

    def _bulk_slices_owned(self, index, slices):
        """True when this host owns every slice a bulk write touches —
        the serial path writes locally only for owned slices, so
        multi-node bulk writes must not land bits on non-owners."""
        if self.cluster is None or len(self.cluster.nodes) <= 1:
            return True
        return all(
            any(n.host == self.host
                for n in self.cluster.fragment_nodes(index, s))
            for s in set(slices))

    def _execute_bulk_set_bits(self, index, calls, opt, set_value=True):
        """All-SetBit queries vectorize into one bulk_set_bits per
        (frame, view), preserving per-call changed flags — serial
        set_bit semantics applied in order. None when ineligible:
        multi-node non-remote (per-bit replica fan-out), timestamps
        (time-quantum views), explicit view args, or any arg shape the
        serial path would reject with a specific error."""
        if (self.cluster is not None and len(self.cluster.nodes) > 1
                and not opt.remote and self.client is not None):
            return None
        idx = self.holder.index(index)
        per_frame = {}
        for k, call in enumerate(calls):
            if (call.args.get("view") or call.args.get("timestamp")
                    is not None):
                return None
            frame_name = call.args.get("frame")
            if not isinstance(frame_name, str):
                return None
            frame = idx.frame(frame_name)
            if frame is None:
                return None
            try:
                row_id, ok = call.uint_arg(frame.row_label)
                if not ok:
                    return None
                col_id, ok = call.uint_arg(idx.column_label)
                if not ok:
                    return None
            except ValueError:
                # Bad id (e.g. negative): the serial path applies the
                # valid prefix then raises, as the reference does.
                return None
            if row_id >= 2 ** 63 or col_id >= 2 ** 63:
                return None  # uint64 overflow: serial path
            per_frame.setdefault(frame_name, []).append((k, row_id, col_id))

        if not self._bulk_slices_owned(
                index, self._setbit_slices(idx, per_frame)):
            return None
        return self._apply_bulk_set_bits(idx, per_frame, len(calls), opt,
                                         set_value)

    def _execute_setbit_burst(self, index, burst, opt, set_value=True):
        """Regex-recognized SetBit storm → bulk apply without ever
        building an AST. None when ineligible (multi-node non-remote,
        unknown frame, or arg labels that aren't this frame's row label
        + the index's column label) — the caller then takes the full
        parse path, which reproduces the serial errors. On a multi-node
        cluster the coordinator fans grouped sub-bursts out to owners
        (_burst_fanout)."""
        if (self.cluster is not None and len(self.cluster.nodes) > 1
                and not opt.remote and self.client is not None):
            return self._burst_fanout(
                index, burst, opt, "SetBit" if set_value else "ClearBit",
                set_value)
        idx = self.holder.index(index)
        per_frame = {}
        for k, (frame_name, k1, v1, k2, v2) in enumerate(burst):
            frame = idx.frame(frame_name)
            if frame is None:
                return None
            if k1 == frame.row_label and k2 == idx.column_label:
                row_id, col_id = int(v1), int(v2)
            elif k2 == frame.row_label and k1 == idx.column_label:
                row_id, col_id = int(v2), int(v1)
            else:
                return None
            if not (0 <= row_id < 2 ** 63 and 0 <= col_id < 2 ** 63):
                return None  # negative / overflow ids: serial path
            per_frame.setdefault(frame_name, []).append((k, row_id, col_id))
        if not self._bulk_slices_owned(
                index, self._setbit_slices(idx, per_frame)):
            return None
        return self._apply_bulk_set_bits(idx, per_frame, len(burst), opt,
                                         set_value)

    def _execute_setfield_burst(self, index, burst, opt):
        """Regex-recognized SetFieldValue storm → vectorized plane
        writes per (frame, field). None when ineligible — multi-node
        non-remote / unowned slices, unknown frame/field, out-of-range
        values or ids (serial reproduces the reference's
        partial-apply-then-raise) — validated BEFORE any mutation so
        the serial fallback never double-applies. Duplicate columns are
        fine: import_value_bits applies last-write-wins in order. On a
        multi-node cluster the coordinator fans grouped sub-bursts out
        to owners (_burst_fanout)."""
        if (self.cluster is not None and len(self.cluster.nodes) > 1
                and not opt.remote and self.client is not None):
            return self._burst_fanout(index, burst, opt, "SetFieldValue")
        idx = self.holder.index(index)
        groups = {}
        for k, (frame_name, k1, v1, k2, v2) in enumerate(burst):
            frame = idx.frame(frame_name)
            if frame is None:
                return None
            if k1 == idx.column_label:
                col, fname, val = int(v1), k2, int(v2)
            elif k2 == idx.column_label:
                col, fname, val = int(v2), k1, int(v1)
            else:
                return None
            if col < 0 or col >= 2 ** 63:
                return None  # serial path reproduces the exact outcome
            try:
                field = frame.field(fname)
            except perr.ErrFieldNotFound:
                return None
            if val < field.min or val > field.max:
                return None
            groups.setdefault((frame_name, fname), []).append((k, col, val))

        # BSI writes touch only column-orientation slices (no inverse);
        # duplicate columns are fine — import_value_bits applies
        # last-write-wins in call order, matching serial.
        if not self._bulk_slices_owned(
                index, {c // SLICE_WIDTH for triples in groups.values()
                        for _, c, _ in triples}):
            return None

        for (frame_name, fname), triples in groups.items():
            idx.frame(frame_name).import_value(
                fname, [c for _, c, _ in triples],
                [v for _, _, v in triples])
        idx_stats = getattr(idx, "stats", None)
        if idx_stats is not None and not opt.remote:
            # per-call counter parity (_execute_call counts only on
            # the coordinator)
            idx_stats.count("SetFieldValue", len(burst))
        # The reference's SetFieldValue yields a nil result per call
        # (executeSetFieldValue executor.go:1091 returns only error).
        return [None] * len(burst)

    @staticmethod
    def _setbit_slices(idx, per_frame):
        """Slice set a bulk SetBit batch touches: column slices plus,
        for inverse-enabled frames, the inverse orientation's (row)
        slices."""
        slices = set()
        for frame_name, triples in per_frame.items():
            inverse = idx.frame(frame_name).inverse_enabled
            for _, row_id, col_id in triples:
                slices.add(col_id // SLICE_WIDTH)
                if inverse:
                    slices.add(row_id // SLICE_WIDTH)
        return slices

    def _apply_bulk_set_bits(self, idx, per_frame, n_calls, opt,
                             set_value=True):
        results = [False] * n_calls
        for frame_name, triples in per_frame.items():
            frame = idx.frame(frame_name)
            op = (frame.bulk_set_bits if set_value
                  else frame.bulk_clear_bits)
            ks = [t[0] for t in triples]
            rows = [t[1] for t in triples]
            cols = [t[2] for t in triples]
            changed = op(VIEW_STANDARD, rows, cols)
            if frame.inverse_enabled:
                changed = changed | op(VIEW_INVERSE, cols, rows)
            for k, ch in zip(ks, changed.tolist()):
                results[k] = bool(ch)
        idx_stats = getattr(idx, "stats", None)
        if idx_stats is not None and not opt.remote:
            # per-call counter parity (_execute_call counts only on
            # the coordinator)
            idx_stats.count("SetBit" if set_value else "ClearBit", n_calls)
        return results

    def _execute_set_bit(self, index, call, opt, set_value):
        """(ref: executeSetBit executor.go:985-1056, executeClearBit :891)."""
        verb = "SetBit" if set_value else "ClearBit"
        view = call.args.get("view") or ""
        frame_name = call.args.get("frame")
        if not isinstance(frame_name, str):
            raise ValueError(f"{verb}() field required: frame")
        idx = self.holder.index(index)
        frame = idx.frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()

        row_id, ok = call.uint_arg(frame.row_label)
        if not ok:
            raise ValueError(f"{verb}() row field '{frame.row_label}' required")
        col_id, ok = call.uint_arg(idx.column_label)
        if not ok:
            raise ValueError(
                f"{verb}() column field '{idx.column_label}' required")

        timestamp = None
        ts = call.args.get("timestamp")
        if isinstance(ts, str):
            try:
                timestamp = datetime.strptime(ts, TIME_FORMAT)
            except ValueError:
                raise ValueError(f"invalid date: {ts}")

        views = []
        if view == VIEW_STANDARD:
            views = [(VIEW_STANDARD, col_id, row_id)]
        elif view == VIEW_INVERSE:
            views = [(VIEW_INVERSE, row_id, col_id)]
        elif view == "":
            views = [(VIEW_STANDARD, col_id, row_id)]
            if frame.inverse_enabled:
                views.append((VIEW_INVERSE, row_id, col_id))
        else:
            raise perr.ErrInvalidView()

        changed = False
        for view_name, c, r in views:
            changed |= self._execute_set_bit_view(
                index, call, frame, view_name, c, r, timestamp, opt, set_value)
        return changed

    def _execute_set_bit_view(self, index, call, frame, view, col_id, row_id,
                              timestamp, opt, set_value):
        """Synchronous replica fan-out (ref: executeSetBitView
        executor.go:1059-1088)."""
        slice_num = col_id // SLICE_WIDTH
        changed = False
        nodes = (self.cluster.fragment_nodes(index, slice_num)
                 if self.cluster else [None])
        for node in nodes:
            if node is None or node.host == self.host or self.client is None:
                if set_value:
                    changed |= frame.set_bit(view, row_id, col_id, timestamp)
                else:
                    changed |= frame.clear_bit(view, row_id, col_id, timestamp)
                continue
            if opt.remote:
                continue
            if self._node_is_down(node) and self._hints_allowed():
                # DOWN replica: hint the write for replay on rejoin
                # (the reference fails the write instead). Mid-resize
                # the hint path is off — see _hints_allowed.
                self._hint(node, index, call)
                continue
            res = self.client.execute_query(node, index, Query([call]),
                                            remote=True)
            changed |= bool(res[0])
        return changed

    def _execute_set_field_value(self, index, call, opt):
        """(ref: executeSetFieldValue executor.go:1091-1161)."""
        frame_name = call.args.get("frame")
        if not isinstance(frame_name, str):
            raise ValueError("SetFieldValue() field required: frame")
        idx = self.holder.index(index)
        frame = idx.frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()
        col_id, ok = call.uint_arg(idx.column_label)
        if not ok:
            raise ValueError(
                f"SetFieldValue() column field '{idx.column_label}' required")
        fields = {k: v for k, v in call.args.items()
                  if k not in ("frame", idx.column_label)}
        if not fields:
            raise ValueError("SetFieldValue() at least one field "
                             "value is required")

        slice_num = col_id // SLICE_WIDTH
        nodes = (self.cluster.fragment_nodes(index, slice_num)
                 if self.cluster else [None])
        for node in nodes:
            if node is None or node.host == self.host or self.client is None:
                for fname, value in fields.items():
                    if isinstance(value, bool) or not isinstance(value, int):
                        raise perr.ErrInvalidFieldValueType()
                    frame.set_field_value(col_id, fname, value)
                continue
            if opt.remote:
                continue
            if self._node_is_down(node) and self._hints_allowed():
                self._hint(node, index, call)
                continue
            self.client.execute_query(node, index, Query([call]), remote=True)
        return None

    def _attrs_from_args(self, call, exclude):
        attrs = {}
        for k, v in call.args.items():
            if k in exclude:
                continue
            if isinstance(v, Condition):
                raise ValueError("attribute value cannot be a condition")
            attrs[k] = v
        return attrs

    def _broadcast_write(self, index, call, opt):
        """Replicate an attr write to every other node
        (ref: executeSetRowAttrs executor.go:1164-1220)."""
        if opt.remote or self.cluster is None or self.client is None:
            return
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            if self._node_is_down(node):
                self._hint(node, index, call)
                continue
            self.client.execute_query(node, index, Query([call]), remote=True)

    def _execute_set_row_attrs(self, index, call, opt):
        frame_name = call.args.get("frame")
        if not isinstance(frame_name, str):
            raise ValueError("SetRowAttrs() field required: frame")
        frame = self.holder.index(index).frame(frame_name)
        if frame is None:
            raise perr.ErrFrameNotFound()
        row_id, ok = call.uint_arg(frame.row_label)
        if not ok:
            raise ValueError(
                f"SetRowAttrs() row field '{frame.row_label}' required")
        attrs = self._attrs_from_args(call, ("frame", frame.row_label))
        frame.row_attr_store.set_attrs(row_id, attrs)
        self._broadcast_write(index, call, opt)
        return None

    def _execute_bulk_set_row_attrs(self, index, calls, opt):
        """Group SetRowAttrs calls by frame into one SetBulkAttrs per
        frame (ref: executeBulkSetRowAttrs executor.go:1222-1308)."""
        idx = self.holder.index(index)
        by_frame = {}
        for call in calls:
            frame_name = call.args.get("frame")
            if not isinstance(frame_name, str):
                raise ValueError("SetRowAttrs() field required: frame")
            frame = idx.frame(frame_name)
            if frame is None:
                raise perr.ErrFrameNotFound()
            row_id, ok = call.uint_arg(frame.row_label)
            if not ok:
                raise ValueError(
                    f"SetRowAttrs() row field '{frame.row_label}' required")
            attrs = self._attrs_from_args(call, ("frame", frame.row_label))
            by_frame.setdefault(frame_name, {}).setdefault(row_id, {}) \
                .update(attrs)
        for frame_name, attr_map in by_frame.items():
            idx.frame(frame_name).row_attr_store.set_bulk_attrs(attr_map)
        # Replicate the whole batch to each peer in one request
        # (ref: executor.go:1293-1306 sends the full query remotely).
        if not opt.remote and self.cluster is not None \
                and self.client is not None:
            for node in self.cluster.nodes:
                if node.host == self.host:
                    continue
                if self._node_is_down(node):
                    for call in calls:
                        self._hint(node, index, call)
                    continue
                self.client.execute_query(node, index, Query(list(calls)),
                                          remote=True)
        return [None] * len(calls)

    def _execute_set_column_attrs(self, index, call, opt):
        idx = self.holder.index(index)
        col_id, ok = call.uint_arg(idx.column_label)
        if not ok:
            raise ValueError(
                f"SetColumnAttrs() column field '{idx.column_label}' required")
        attrs = self._attrs_from_args(call, (idx.column_label, "frame"))
        idx.column_attr_store.set_attrs(col_id, attrs)
        self._broadcast_write(index, call, opt)
        return None
