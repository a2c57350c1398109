"""HTTP API handler (ref: handler.go:98-151 route table, ~40 routes).

stdlib ``ThreadingHTTPServer`` + a regex route table standing in for
gorilla/mux. JSON is the primary representation; the reference's
protobuf content negotiation (handler.go:1067-1162) is mirrored for the
query/import endpoints via ``pilosa_tpu.server.wireproto`` when the
client sends ``application/x-protobuf``.

Every request is wrapped in panic-recovery (ref: handler.go:157-194):
errors become JSON ``{"error": ...}`` bodies with appropriate status.
"""
import base64
import io
import json
import re
import threading
import time
import traceback
from datetime import datetime
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

from pilosa_tpu import SLICE_WIDTH, __version__
from pilosa_tpu import autopilot as autopilot_mod
from pilosa_tpu import errors as perr
from pilosa_tpu import faults as faults_mod
from pilosa_tpu import lockcheck
from pilosa_tpu import qos as qos_mod
from pilosa_tpu import querystats
from pilosa_tpu import stats as stats_mod
from pilosa_tpu import tracing
from pilosa_tpu.config import DEFAULT_MAX_BODY_SIZE
from pilosa_tpu.observe import costmodel as costmodel_mod
from pilosa_tpu.observe import devprof as devprof_mod
from pilosa_tpu.observe import events as events_mod
from pilosa_tpu.observe import explain as explain_mod
from pilosa_tpu.observe import heatmap as heatmap_mod
from pilosa_tpu.observe import kerneltime as kerneltime_mod
from pilosa_tpu.observe import profiler as profiler_mod
from pilosa_tpu.observe import replica as replica_mod
from pilosa_tpu.observe import slo as slo_mod
from pilosa_tpu.bitmap import Bitmap
from pilosa_tpu.cluster import hedge as hedge_mod
from pilosa_tpu.executor import ExecOptions, SumCount
from pilosa_tpu.pql.parser import ParseError
from pilosa_tpu.storage.frame import Field
from pilosa_tpu.storage.index import FrameOptions


def result_to_json(result):
    """QueryResult encoding (ref: QueryResult tagged union,
    internal/public.proto:60-70 + handler.go JSON path)."""
    if isinstance(result, Bitmap):
        return {"attrs": result.attrs, "bits": result.columns().tolist()}
    if isinstance(result, SumCount):
        return {"sum": result.sum, "count": result.count}
    if isinstance(result, list):  # pairs
        return [{"id": rid, "count": cnt} for rid, cnt in result]
    return result  # bool / int / None


def _decode_checksum(s):
    """Anti-entropy checksums are 8 bytes (xxhash64): Go-style base64
    is 12 chars with padding; round-1 in-house peers sent 16 hex chars.
    The shapes are disjoint, so both generations parse correctly."""
    if len(s) == 16:
        try:
            return bytes.fromhex(s)
        except ValueError:
            pass
    return base64.b64decode(s)


def _retry_after(seconds):
    """RFC 7231 delay-seconds is an INTEGER (1*DIGIT) — fractional
    values are unparseable to conforming clients (urllib3 Retry, Go
    net/http), which would silently drop the backoff hint."""
    import math

    return str(max(1, math.ceil(seconds)))


class HTTPError(Exception):
    """``headers`` (optional dict) ride the error response — how a
    shed carries its ``Retry-After`` hint."""

    def __init__(self, status, message, headers=None):
        self.status = status
        self.message = message
        self.headers = headers
        super().__init__(message)


class Handler:
    """Routing + endpoint logic, transport-independent."""

    def __init__(self, holder, executor, cluster=None, broadcaster=None,
                 local_host=None, version=__version__, tracer=None,
                 qos=None, histograms=None, epochs=None,
                 rebalancer=None, ingest=None, slo=None,
                 events=None, vitals=None, autopilot=None, hedger=None,
                 device_trace_dir=""):
        self.holder = holder
        self.executor = executor
        self.cluster = cluster
        self.broadcaster = broadcaster
        self.local_host = local_host
        self.version = version
        self.tracer = tracer or tracing.NOP
        # Distributed mutation-epoch registry (cluster/epochs.py) on
        # multi-node servers; None on single-node keeps every hook to
        # one attribute read and the wire format header-free.
        self.epochs = epochs
        # Elastic-topology rebalancer (cluster/rebalancer.py) on
        # multi-node servers: owns POST /cluster/resize,
        # GET /debug/rebalance, and the placement-state message.
        self.rebalancer = rebalancer
        # Streaming bulk-ingest pipeline (ingest/pipeline.py): owns
        # POST /index/<i>/ingest. None = route answers 501 ([ingest]
        # enabled = false, or a bare Handler).
        self.ingest = ingest
        # QoS tier (qos.py): admission gate + quotas + deadline
        # stamping on the heavy serving routes. The nop default keeps
        # the hot path to one `.enabled` attribute read.
        self.qos = qos or qos_mod.NOP
        # Runtime-telemetry histograms ([metrics] config) rendered on
        # /metrics; /cluster/metrics fan-out is gated by the server's
        # [metrics] cluster-aggregation flag.
        self.histograms = histograms or stats_mod.NOP_HISTOGRAMS
        # SLO tracker ([slo] config, observe/slo.py): fed one record
        # per query/ingest request from dispatch(); the nop default
        # keeps the request path to one attribute read.
        self.slo = slo or slo_mod.NOP
        # Control-plane flight recorder + replica vitals (observe/
        # events.py, observe/replica.py): /debug/events + /debug/
        # replicas surfaces and the pilosa_events_total /
        # pilosa_replica_* metric families. Nop defaults keep a bare
        # Handler (tests) to one `.enabled` attribute read.
        self.events = events or events_mod.NOP
        self.vitals = vitals or replica_mod.NOP
        # Heat-driven autopilot ([autopilot] config, autopilot/
        # controller.py): owns POST /cluster/autopilot/plan (dry-run
        # preview) and GET /debug/autopilot. The nop default keeps a
        # bare Handler to one `.enabled` attribute read.
        self.autopilot = autopilot or autopilot_mod.NOP
        # Tail-tolerant reads (cluster/hedge.py): owns GET
        # /debug/hedge and the pilosa_hedge_* metric family. The nop
        # default keeps a bare Handler to one `.enabled` read.
        self.hedger = hedger or hedge_mod.NOP
        # Default output directory for POST /debug/profile/device
        # trace captures ([profile] device-trace-dir); requests may
        # name their own via ?dir=.
        self.device_trace_dir = device_trace_dir
        self.cluster_metrics_enabled = True
        self._scrape_mu = lockcheck.register("handler.Handler._scrape_mu",
                                             threading.Lock())
        self._scrape_errors = {}  # peer host -> failed scrape count
        self._resp_cache = None  # enable_response_cache (master only)
        # Graceful drain (Server.close / SIGTERM): while _drain is
        # set, new work on the heavy serving routes sheds with 503 +
        # Retry-After and /status answers LEAVING; _inflight counts
        # requests currently inside dispatch so the drain loop knows
        # when the node is quiet. The counter is two uncontended lock
        # acquisitions per request — the price of close() being able
        # to wait for in-flight queries at all.
        self._inflight = 0
        self._inflight_mu = lockcheck.register(
            "handler.Handler._inflight_mu", threading.Lock())
        self._drain = None
        self._drain_shed_total = 0
        self.routes = self._build_routes()

    def enable_response_cache(self):
        """Master-side response replay (the worker ResponseCache, one
        tier deeper): identical read queries replay their exact
        response bytes while the index's mutation-epoch token stands —
        skipping parse, dispatch, execution, and JSON encoding
        entirely. Single-node validates against the process-local
        per-index epoch (attr writes bump it too, attrs.py);
        multi-node validates against the cluster epoch VECTOR
        (cluster/epochs.py — unknown/stale peers mean cold, never
        stale). OFF whenever the executor's result memos are off
        (PILOSA_TPU_RESULT_MEMO=0, cold benchmarks, pinned paths) so
        measurements never time dict lookups.
        PILOSA_TPU_RESPONSE_CACHE=0 disables independently."""
        import os as _os

        from pilosa_tpu.server.respcache import ResponseCache
        from pilosa_tpu.storage.fragment import mutation_epoch

        if _os.environ.get("PILOSA_TPU_RESPONSE_CACHE", "1") in (
                "0", "false", "no"):
            return
        if self.epochs is not None:
            self._resp_cache = ResponseCache(self._cluster_epoch_token)
        else:
            # Scoped to the query's index (path is /index/<i>/query,
            # guaranteed by cacheable()) so a write-heavy index no
            # longer flushes other indexes' replays.
            self._resp_cache = ResponseCache(
                lambda path: mutation_epoch(path.split("/", 3)[2]))

    def _cluster_epoch_token(self, path):
        """Multi-node replay validity: the epoch vector over every
        cluster node (a whole-index query reads slices from all of
        them under jump-hash placement — the conservative owner set),
        refreshed by probes when stale, PLUS the local slice-universe
        bounds. The universe term closes a restart hole: a rebooted
        node relearns peer max-slices via heartbeat WITHOUT any epoch
        movement, and an entry cached over the smaller universe would
        otherwise replay a stale partial count until the next write.
        None -> cold."""
        index = path.split("/", 3)[2]
        tok = self.epochs.ensure_fresh(
            index, [n.host for n in self.cluster.nodes])
        if tok is None:
            return None
        idx = self.holder.index(index)
        if idx is None:
            return tok
        # Via the plan cache's epoch-memoized universe (validation is
        # an O(1) token compare), NOT a per-request max_slice() walk
        # over every view of every frame — the replay tier must never
        # re-pay the walk PR 6 removed.
        std, inv = self.executor.plans.slice_universe(index, idx)
        return (tok, len(std), len(inv))

    def _build_routes(self):
        return [
            ("POST", r"^/index/(?P<index>[^/]+)/query$", self.post_query),
            ("GET", r"^/index/(?P<index>[^/]+)/query$",
             self.method_not_allowed),
            ("GET", r"^/index$", self.get_schema),
            ("GET", r"^/schema$", self.get_schema),
            ("POST", r"^/schema$", self.post_schema),
            ("GET", r"^/status$", self.get_status),
            ("GET", r"^/version$", self.get_version),
            ("GET", r"^/hosts$", self.get_hosts),
            ("GET", r"^/id$", self.get_id),
            ("GET", r"^/slices/max$", self.get_slices_max),
            ("GET", r"^/index/(?P<index>[^/]+)$", self.get_index),
            ("POST", r"^/index/(?P<index>[^/]+)$", self.post_index),
            ("DELETE", r"^/index/(?P<index>[^/]+)$", self.delete_index),
            ("PATCH", r"^/index/(?P<index>[^/]+)/time-quantum$",
             self.patch_index_time_quantum),
            ("POST", r"^/index/(?P<index>[^/]+)/attr/diff$",
             self.post_index_attr_diff),
            ("POST", r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)$",
             self.post_frame),
            ("DELETE", r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)$",
             self.delete_frame),
            ("PATCH",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/time-quantum$",
             self.patch_frame_time_quantum),
            ("POST",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/attr/diff$",
             self.post_frame_attr_diff),
            ("POST",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)"
             r"/field/(?P<field>[^/]+)$", self.post_field),
            ("DELETE",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)"
             r"/field/(?P<field>[^/]+)$", self.delete_field),
            ("GET", r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/fields$",
             self.get_fields),
            ("POST",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)"
             r"/views/(?P<view>[^/]+)$", self.post_view),
            ("GET", r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/views$",
             self.get_views),
            ("DELETE",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)"
             r"/view/(?P<view>[^/]+)$", self.delete_view),
            ("POST",
             r"^/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/restore$",
             self.post_frame_restore),
            ("POST", r"^/index/(?P<index>[^/]+)/input-definition/(?P<def>[^/]+)$",
             self.post_input_definition),
            ("GET", r"^/index/(?P<index>[^/]+)/input-definition/(?P<def>[^/]+)$",
             self.get_input_definition),
            ("DELETE",
             r"^/index/(?P<index>[^/]+)/input-definition/(?P<def>[^/]+)$",
             self.delete_input_definition),
            ("POST", r"^/index/(?P<index>[^/]+)/input/(?P<def>[^/]+)$",
             self.post_input),
            ("POST", r"^/index/(?P<index>[^/]+)/ingest$",
             self.post_ingest),
            ("POST", r"^/import$", self.post_import),
            ("POST", r"^/import-value$", self.post_import_value),
            ("GET", r"^/export$", self.get_export),
            ("GET", r"^/fragment/data$", self.get_fragment_data),
            ("POST", r"^/fragment/data$", self.post_fragment_data),
            ("GET", r"^/fragment/blocks$", self.get_fragment_blocks),
            ("GET", r"^/fragment/digest$", self.get_fragment_digest),
            ("GET", r"^/fragment/block/data$", self.get_fragment_block_data),
            ("GET", r"^/fragment/nodes$", self.get_fragment_nodes),
            ("POST", r"^/cluster/message$", self.post_cluster_message),
            ("POST", r"^/cluster/resize$", self.post_cluster_resize),
            ("POST", r"^/cluster/autopilot/plan$",
             self.post_cluster_autopilot_plan),
            ("GET", r"^/debug/rebalance$", self.get_debug_rebalance),
            ("GET", r"^/internal/probe$", self.get_internal_probe),
            ("GET", r"^/internal/epochs$", self.get_internal_epochs),
            ("POST", r"^/internal/heartbeat$",
             self.post_internal_heartbeat),
            ("POST", r"^/recalculate-caches$", self.post_recalculate_caches),
            ("GET", r"^/debug/vars$", self.get_debug_vars),
            ("GET", r"^/debug/traces$", self.get_debug_traces),
            ("GET", r"^/debug/qos$", self.get_debug_qos),
            ("GET", r"^/debug/lockcheck$", self.get_debug_lockcheck),
            ("GET", r"^/debug/drain$", self.get_debug_drain),
            ("GET", r"^/debug/faults$", self.get_debug_faults),
            ("POST", r"^/debug/faults$", self.post_debug_faults),
            ("GET", r"^/debug/memory$", self.get_debug_memory),
            ("GET", r"^/debug/epochs$", self.get_debug_epochs),
            ("GET", r"^/debug/plans$", self.get_debug_plans),
            ("GET", r"^/debug/mesh$", self.get_debug_mesh),
            ("GET", r"^/debug/kernels$", self.get_debug_kernels),
            ("GET", r"^/debug/profile$", self.get_debug_profile),
            ("POST", r"^/debug/profile/device$",
             self.post_profile_device),
            ("GET", r"^/debug/profile/device$",
             self.get_profile_device),
            ("GET", r"^/debug/heatmap$", self.get_debug_heatmap),
            ("GET", r"^/debug/slo$", self.get_debug_slo),
            ("GET", r"^/debug/costmodel$", self.get_debug_costmodel),
            ("GET", r"^/debug/events$", self.get_debug_events),
            ("GET", r"^/debug/replicas$", self.get_debug_replicas),
            ("GET", r"^/debug/autopilot$", self.get_debug_autopilot),
            ("GET", r"^/debug/hedge$", self.get_debug_hedge),
            ("GET", r"^/debug$", self.get_debug_index),
            ("GET", r"^/metrics$", self.get_metrics),
            ("GET", r"^/cluster/metrics$", self.get_cluster_metrics),
            ("GET", r"^/debug/worker$", self.get_debug_worker),
            ("GET", r"^/$", self.get_webui),
            ("GET", r"^/assets/(?P<file>[^/]+)$", self.get_asset),
        ]

    def dispatch(self, method, path, query_params, body, headers):
        """-> (status, content_type, payload bytes)."""
        with self._inflight_mu:
            self._inflight += 1
        slo = self.slo
        track = (slo.enabled and method == "POST"
                 and (path.endswith("/query")
                      or path.endswith("/ingest")))
        t0 = time.monotonic() if track else 0.0
        try:
            out = self._dispatch(method, path, query_params, body,
                                 headers)
        finally:
            with self._inflight_mu:
                self._inflight -= 1
        if track:
            # One SLO record per serving request, by admitted priority
            # class. 5xx (shed, fail-stop, expiry, crash) burns the
            # availability budget; the latency objective judges the
            # wall time of everything else — cache replays included,
            # they are answers the client waited for.
            prio = headers.get(qos_mod.PRIORITY_HEADER)
            if not prio and path.endswith("/ingest"):
                prio_cls = qos_mod.PRIO_INGEST
            else:
                prio_cls = qos_mod.parse_priority(prio)
            slo.record(qos_mod.priority_name(prio_cls),
                       time.monotonic() - t0, error=out[0] >= 500)
        ep = self.epochs
        if ep is not None:
            # Epoch piggyback (the ONE header pair per RPC): computed
            # AFTER the handler ran, so a write's own response carries
            # its bumped counter — the coordinator that relayed the
            # write observes it in-line, making read-your-writes
            # through any relaying coordinator strict. Memoized on the
            # process epoch total: steady state costs one int compare
            # + one dict copy.
            extra = dict(out[3]) if len(out) > 3 and out[3] else {}
            extra[ep.HEADER] = ep.header_value()
            out = out[:3] + (extra,)
        return out

    def _dispatch(self, method, path, query_params, body, headers):
        cache = self._resp_cache
        key = epoch = None
        if (cache is not None
                and not self.tracer.enabled
                and "profile" not in (query_params or ())
                and "explain" not in (query_params or ())
                and headers.get(querystats.COLLECT_HEADER) is None
                and not self.executor._result_memo_off
                and getattr(self.executor, "_force_path", None) is None
                and cache.cacheable(method, path, body)):
            key = cache.make_key(path, query_params, body, headers)
            hit = cache.get(key)
            if hit is not None:
                if self._drain is not None:
                    # A draining node stops answering queries even
                    # from cache — the client must move to a replica
                    # before the listener goes away.
                    return self._drain_response()
                shed = self._replay_shed(query_params, headers)
                if shed is not None:
                    return shed
                return hit + ({"X-Pilosa-Response-Cache": "hit"},)
            epoch = cache.pre_epoch(path)
        out = self._dispatch_route(method, path, query_params, body,
                                   headers)
        if key is not None:
            cache.put(key, epoch, out)
        return out

    def _dispatch_route(self, method, path, query_params, body, headers):
        for m, pattern, fn in self.routes:
            if m != method:
                continue
            match = re.match(pattern, path)
            if match:
                try:
                    return fn(match.groupdict(), query_params, body, headers)
                except HTTPError as e:
                    resp = (e.status, "application/json",
                            json.dumps({"error": e.message}).encode())
                    return resp + (e.headers,) if e.headers else resp
                except perr.ErrFragmentFailStop as e:
                    # A fail-stopped fragment is a node-health
                    # condition, not a caller mistake: 503 tells the
                    # client (and a coordinating peer) to retry
                    # against a replica while this fragment waits for
                    # operator attention / reopen.
                    return (503, "application/json",
                            json.dumps({"error": str(e)}).encode(),
                            {"Retry-After": "1"})
                except (perr.PilosaError, ParseError, ValueError) as e:
                    # Parse/validation errors only: a KeyError here
                    # used to map to 400 too, misreporting an internal
                    # missing-dict-key bug as the caller's fault —
                    # genuine handler bugs now surface as 500 with the
                    # traceback; request bodies are validated
                    # explicitly (_require) where missing keys ARE the
                    # caller's fault.
                    return (400, "application/json",
                            json.dumps({"error": str(e)}).encode())
                except Exception as e:  # panic recovery (handler.go:157-194)
                    traceback.print_exc()
                    return (500, "application/json",
                            json.dumps({"error": str(e)}).encode())
        return 404, "application/json", json.dumps({"error": "not found"}).encode()

    # ------------------------------------------------------------- drain

    def begin_drain(self, timeout):
        """Flip the node into the LEAVING state: every new request on
        a gated serving route (query/import/input — and cached
        replays) sheds with 503 + ``Retry-After`` so clients and
        coordinating peers move to replicas, while the in-flight ones
        run to completion. Idempotent."""
        with self._inflight_mu:
            if self._drain is None:
                # Wall "started" is the user-facing timestamp; the
                # monotonic twin is what elapsed arithmetic uses (an
                # admin clock step must not distort drain progress).
                self._drain = {"started": time.time(),
                               "started_mono": time.monotonic(),
                               "timeout": float(timeout)}

    def drain(self, timeout):
        """begin_drain + wait (up to ``timeout`` seconds) for every
        in-flight request to finish. Op-log writes flush synchronously
        inside their requests, so a quiet dispatch means durable
        state is settled too. Returns (seconds waited, drained?,
        requests still in flight at the deadline)."""
        self.begin_drain(timeout)
        t0 = time.monotonic()
        deadline = t0 + timeout
        while True:
            with self._inflight_mu:
                n = self._inflight
            if n <= 0 or time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        waited = time.monotonic() - t0
        with self._inflight_mu:
            self._drain["waited"] = waited
            self._drain["remaining"] = n
        return waited, n <= 0, n

    def _drain_response(self):
        """The 503 a draining node answers new serving work with."""
        with self._inflight_mu:
            self._drain_shed_total += 1
            retry = self._drain["timeout"] if self._drain else 1.0
        stats = getattr(self.executor.holder, "stats", None)
        if stats is not None:
            stats.count("drain_shed_total", 1)
        return (503, "application/json",
                json.dumps({"error": "node is draining"}).encode(),
                {"Retry-After": _retry_after(retry)})

    def get_debug_drain(self, params, qp, body, headers):
        """Drain introspection (mirrors /debug/qos): whether the node
        is leaving, how long it has been draining, what is still in
        flight (excluding this request), and how much new work was
        shed."""
        with self._inflight_mu:
            d = dict(self._drain) if self._drain else None
            inflight = max(0, self._inflight - 1)
            shed = self._drain_shed_total
        out = {"draining": d is not None, "inFlight": inflight,
               "shedTotal": shed}
        if d:
            out["startedAt"] = d["started"]
            out["drainTimeout"] = d["timeout"]
            out["elapsed"] = round(time.monotonic() - d["started_mono"], 3)
            if "waited" in d:
                out["waited"] = round(d["waited"], 3)
                out["remainingAtDeadline"] = d["remaining"]
        return 200, "application/json", json.dumps(out).encode()

    # -------------------------------------------------------- failpoints

    def get_debug_faults(self, params, qp, body, headers):
        """Failpoint snapshot — answers even when the subsystem is
        disabled ({"enabled": false}), like /debug/qos."""
        return (200, "application/json",
                json.dumps(faults_mod.ACTIVE.snapshot()).encode())

    def post_debug_faults(self, params, qp, body, headers):
        """Runtime failpoint control, test-only: 403 unless fault
        injection was enabled out-of-band (PILOSA_FAULTS env or the
        [faults] config table) — a production node must not grow a
        remote crash-me endpoint by default. Body:
        ``{"spec": "<point>=<action>...", "clear": true|"<point>"}``;
        clear runs first, so one call can swap armings."""
        if not faults_mod.ACTIVE.enabled:
            raise HTTPError(
                403, "fault injection disabled "
                     "(set PILOSA_FAULTS or [faults] enabled)")
        req = json.loads(body or b"{}")
        clear = req.get("clear")
        if clear:
            faults_mod.ACTIVE.clear(
                None if clear is True else str(clear))
        spec = req.get("spec")
        if spec:
            try:
                faults_mod.ACTIVE.configure(spec)
            except ValueError as e:
                raise HTTPError(400, str(e))
        return (200, "application/json",
                json.dumps(faults_mod.ACTIVE.snapshot()).encode())

    # --------------------------------------------------------------- qos

    def _replay_shed(self, qp, headers):
        """QoS checks a response-cache replay still owes: a replay
        skips _dispatch_route (and so _serve_qos), but a client's
        request-rate quota counts every request it issues — cached or
        not — and an already-expired deadline must 504 regardless of
        cache state (docs promise expiry semantics independent of it).
        The gate itself is deliberately skipped: a replay consumes no
        executor capacity. Returns an error response tuple to send,
        None to proceed with the replay."""
        q = self.qos
        if not q.enabled:
            return None
        try:
            deadline = q.request_deadline(qp, headers)
        except qos_mod.ShedError as e:
            return (e.status, "application/json",
                    json.dumps({"error": e.reason}).encode())
        if deadline is not None and time.monotonic() > deadline:
            q.note_deadline_expired()
            return (504, "application/json",
                    json.dumps({"error": "deadline exceeded"}).encode())
        if qos_mod.parse_priority(
                headers.get(qos_mod.PRIORITY_HEADER)) \
                == qos_mod.PRIO_INTERNAL:
            return None
        try:
            q.quotas.allow(headers.get(qos_mod.CLIENT_HEADER))
        except qos_mod.ShedError as e:
            q.note_shed(e.reason)
            return (e.status, "application/json",
                    json.dumps({"error": e.reason}).encode(),
                    {"Retry-After": _retry_after(e.retry_after)})
        return None

    def _gated(self, inner, params, qp, body, headers,
               default_priority=None):
        """Route a heavy serving endpoint through the QoS tier. The
        disabled path is one attribute read and a plain call — no
        closure is ever built (the nop-tracer discipline). A draining
        node sheds the request before either path: the same 503 +
        Retry-After contract as QoS overload, minus the gate.
        ``default_priority`` overrides the headerless default (the
        ingest route parks at qos.PRIO_INGEST, not interactive)."""
        if self._drain is not None:
            return self._drain_response()
        if not self.qos.enabled:
            return inner(params, qp, body, headers)
        return self._serve_qos(
            qp, headers, lambda: inner(params, qp, body, headers),
            default_priority=default_priority)

    def _serve_qos(self, qp, headers, fn, default_priority=None):
        """Run ``fn`` under the QoS tier: resolve the request deadline
        (X-Pilosa-Deadline header wins, else ?timeout=, else the
        configured default), quota-check the client, admit through the
        gate (priority-aware; internal fan-out never queues), install
        the deadline scope the executor checks mid-query, and map
        shed/expiry to 429/503 (+Retry-After) / 504. One attribute
        read when QoS is disabled — no locks, no allocations."""
        q = self.qos
        if not q.enabled:
            return fn()
        try:
            deadline = q.request_deadline(qp, headers)
        except qos_mod.ShedError as e:  # malformed deadline/timeout
            raise HTTPError(e.status, e.reason)
        if deadline is not None and time.monotonic() > deadline:
            q.note_deadline_expired()
            raise HTTPError(504, "deadline exceeded")
        prio_header = headers.get(qos_mod.PRIORITY_HEADER)
        if not prio_header and default_priority is not None:
            prio = default_priority
        else:
            prio = qos_mod.parse_priority(prio_header)
        client = headers.get(qos_mod.CLIENT_HEADER)
        try:
            with tracing.span("qos.admit",
                              priority=qos_mod.priority_name(prio)) as sp:
                waited = q.admit(prio, client, deadline)
                if waited:
                    sp.tag(queued_ms=round(waited * 1000, 3))
        except qos_mod.ShedError as e:
            raise HTTPError(
                e.status, e.reason,
                headers=({"Retry-After": _retry_after(e.retry_after)}
                         if e.retry_after else None))
        except qos_mod.DeadlineExceeded:
            raise HTTPError(504, "deadline exceeded")
        try:
            # The admitted priority rides a thread-local scope next to
            # the deadline: the executor's coalescer reads it so
            # interactive coalescees admit ahead of batch/ingest ones.
            with qos_mod.deadline_scope(deadline), \
                    qos_mod.priority_scope(prio):
                try:
                    return fn()
                except qos_mod.DeadlineExceeded:
                    q.note_deadline_expired()
                    raise HTTPError(504, "deadline exceeded")
        finally:
            q.release()

    def get_debug_qos(self, params, qp, body, headers):
        """QoS introspection, mirroring /debug/traces: gate occupancy
        and queue depth, shed counters by reason, per-client quota
        table size, and every peer breaker's state."""
        return (200, "application/json",
                json.dumps(self.qos.snapshot()).encode())

    def get_debug_lockcheck(self, params, qp, body, headers):
        """Lock-instrumentation report (PILOSA_LOCKCHECK): observed
        order-graph size, any cycles / locks held across io points,
        and per-lock held-duration histograms. {"enabled": false}
        when the instrumentation is off — the lockcheck-enabled
        acceptance tests assert ``cycles == []`` here."""
        return (200, "application/json",
                json.dumps(lockcheck.report()).encode())

    # ------------------------------------------------------------- query

    def post_query(self, params, qp, body, headers):
        """(ref: handlePostQuery handler.go:243-309). With tracing
        enabled (or ``?profile=true``) the whole serve runs under a
        root span: an incoming X-Pilosa-Trace-Id/X-Pilosa-Span-Id pair
        (coordinator fan-out) is adopted so this node's spans join the
        coordinator's trace; the trace id rides back on the response
        headers, and ``?profile=true`` inlines the span tree next to
        the results (the reference's Profile option that never
        shipped). ``?explain=true`` additionally inlines the query
        inspector's plan tree + observed tier attribution
        (observe/explain.py); ``?explain=only`` plans WITHOUT
        executing. Profile and explain compose — one query may return
        both blocks."""
        tracer = self.tracer
        profile = qp.get("profile", ["false"])[0] == "true"
        explain_mode = qp.get("explain", ["false"])[0]
        if explain_mode not in ("false", "true", "only"):
            raise HTTPError(400, "explain must be true, only or false")
        explain_on = explain_mode != "false"
        # A profiling coordinator asks fan-out targets to count their
        # side and return it in the stats footer header (querystats).
        collect = headers.get(querystats.COLLECT_HEADER) is not None
        if not (tracer.enabled or profile or collect or explain_on):
            return self._post_query(params, qp, body, headers)
        if not tracer.enabled:
            # Per-request profiling on a tracing-disabled server: an
            # ephemeral recorder, no ring/stats side effects.
            tracer = tracing.Tracer(ring_size=1, stats=None)
        trace_id = headers.get(tracing.TRACE_HEADER)
        parent_id = headers.get(tracing.SPAN_HEADER)
        arrived = tracing.take_arrival()
        root = tracer.start(
            "query.remote" if trace_id else "query",
            trace_id=trace_id, parent_id=parent_id,
            index=params["index"], host=self.local_host or "")
        qs = querystats.QueryStats()
        # Journal watermark BEFORE execution: any control-plane event
        # that fires during the query's lifetime (breaker flip, shed
        # onset, placement phase change...) gets its id stamped onto
        # the root span, so a slow-query ring entry names the cluster
        # transitions that overlapped it.
        ev_wm = self.events.last_id() if self.events.enabled else None
        with root, querystats.scope(qs):
            if explain_mode == "only":
                resp = self._explain_only(params, qp, body, headers)
            else:
                resp = self._post_query(params, qp, body, headers)
        # Resource counts ride with the trace into the recent/slow
        # rings (Trace.to_dict inlines them) — tier attribution tags
        # included, so the slow-query flight recorder answers "what
        # did it COST and which tier served it" next to "where did
        # the time go".
        root.trace.resources = qs.to_dict()
        if arrived is not None:
            # What no span can hold, because it runs before the root
            # exists: header parse, body read and routing, from the
            # request line's arrival to the root's start.
            root.tag(httpParseMs=round((root._t0 - arrived) * 1000, 3))
        if ev_wm is not None:
            ids = self.events.ids_since(ev_wm)
            if ids:
                root.tag(controlEvents=ids)
        status, ctype, payload = resp[:3]
        doc = None
        if (ctype == "application/json" and payload.startswith(b"{")
                and status == 200):
            if profile:
                doc = json.loads(payload)
                doc["profile"] = root.trace.to_dict()
            if explain_on and explain_mode == "true":
                # The explain-only path already inlined its block;
                # here the query EXECUTED — the static plan renders
                # next to the observed tier tags it predicted.
                q_string, q_slices = self._query_body(qp, body,
                                                      headers)
                if q_string:
                    if doc is None:
                        doc = json.loads(payload)
                    try:
                        doc["explain"] = explain_mod.explain_query(
                            self.executor, params["index"], q_string,
                            slices=q_slices, qs=qs, executed=True)
                    except Exception as e:  # noqa: BLE001; pilint: disable=swallow
                        # The query EXECUTED — a render failure (e.g.
                        # a DDL race mid-walk) must degrade to an
                        # inline error, never 500 computed results.
                        doc["explain"] = {"error": str(e)}
            if doc is not None:
                payload = json.dumps(doc).encode()
        extra = {tracing.TRACE_HEADER: root.trace.trace_id}
        if collect:
            # The footer a coordinating peer merges into its own
            # accumulator — this node's partial only (tier tags
            # included, so a coordinator's explain reports the union
            # of every node's serving decisions).
            extra[querystats.STATS_HEADER] = querystats.encode(
                qs.to_dict())
        return (status, ctype, payload, extra)

    @staticmethod
    def _query_body(qp, body, headers):
        """(PQL text, explicit slice restriction or None) from a
        query request — ONE decode for the explain surface (protobuf
        bodies carry both fields in the same QueryRequest; text
        bodies take slices from ``?slices=``). (None, None) when
        undecodable — explain is best-effort on exotic encodings,
        never a new failure mode for the query itself."""
        if headers.get("Content-Type") == "application/x-protobuf":
            from pilosa_tpu.server import wireproto

            try:
                req = wireproto.decode_query_request(body)
                return req["query"], req.get("slices") or None
            except Exception:  # noqa: BLE001 — best-effort decode
                return None, None
        try:
            q_string = body.decode()
        except UnicodeDecodeError:
            return None, None
        slices = None
        sl = qp.get("slices")
        if sl:
            try:
                slices = [int(s) for s in sl[0].split(",")
                          if s] or None
            except ValueError:
                slices = None
        return q_string, slices

    def _explain_only(self, params, qp, body, headers):
        """``?explain=only``: plan the query without executing it —
        no result memo, no plan-cache write, no device program (the
        read-only contract observe/explain.py documents and the tests
        assert). Runs through the same QoS gate as a real query: an
        overloaded node sheds inspection work too."""
        return self._gated(self._explain_only_inner, params, qp, body,
                           headers)

    def _explain_only_inner(self, params, qp, body, headers):
        q_string, q_slices = self._query_body(qp, body, headers)
        if not q_string:
            raise HTTPError(400, "query required")
        out = explain_mod.explain_query(
            self.executor, params["index"], q_string,
            slices=q_slices, executed=False)
        return (200, "application/json",
                json.dumps({"results": None, "explain": out}).encode())

    def _post_query(self, params, qp, body, headers):
        return self._gated(self._post_query_inner, params, qp, body,
                           headers)

    def _post_query_inner(self, params, qp, body, headers):
        index = params["index"]
        ctype = headers.get("Content-Type", "")
        if ctype == "application/x-protobuf":
            from pilosa_tpu.server import wireproto
            try:
                req = wireproto.decode_query_request(body)
            except HTTPError:
                raise
            except Exception:  # noqa: BLE001 — any undecodable body:
                # wrong wire types surface as AttributeError/TypeError,
                # truncation as IndexError, bad UTF-8 as ValueError
                # (ref: handler.go:252 "unmarshal body error" → 400).
                raise HTTPError(400, "unmarshal body error")
            q_string = req["query"]
            slices = req.get("slices") or None
            opt = ExecOptions(remote=req.get("remote", False),
                              exclude_attrs=req.get("exclude_attrs", False),
                              exclude_bits=req.get("exclude_bits", False))
        else:
            q_string = body.decode()
            slices = None
            sl = qp.get("slices")
            if sl:
                slices = [int(s) for s in sl[0].split(",") if s]
            opt = ExecOptions(
                remote=qp.get("remote", ["false"])[0] == "true",
                exclude_attrs=qp.get("excludeAttrs", ["false"])[0] == "true",
                exclude_bits=qp.get("excludeBits", ["false"])[0] == "true")
        if not q_string:
            raise HTTPError(400, "query required")

        try:
            # The raw string goes to the executor: it parses (same
            # ParseError surfaces) and can recognize SetBit bursts
            # without building an AST.
            results = self.executor.execute(index, q_string, slices=slices,
                                            opt=opt)
        except perr.ErrFragmentFailStop:
            # Node-health condition, not a query error: let the route
            # dispatcher map it to 503 + Retry-After.
            raise
        except (perr.PilosaError, ValueError) as e:
            if headers.get("Accept") == "application/x-protobuf" or \
                    ctype == "application/x-protobuf":
                from pilosa_tpu.server import wireproto
                return (400, "application/x-protobuf",
                        wireproto.encode_query_response([], error=str(e)))
            return (400, "application/json",
                    json.dumps({"error": str(e)}).encode())

        with tracing.span("encode"):
            if (headers.get("Accept") == "application/x-protobuf"
                    or ctype == "application/x-protobuf"):
                from pilosa_tpu.server import wireproto
                return (200, "application/x-protobuf",
                        wireproto.encode_query_response(results))
            return (200, "application/json", json.dumps(
                {"results": [result_to_json(r)
                             for r in results]}).encode())

    # ------------------------------------------------------------ schema

    def get_schema(self, params, qp, body, headers):
        return (200, "application/json",
                json.dumps({"indexes": self.holder.schema()}).encode())

    def post_schema(self, params, qp, body, headers):
        """Merge a remote schema into this holder."""
        schema = json.loads(body or b"{}")
        self.holder.apply_schema(schema.get("indexes", []))
        return 200, "application/json", b"{}"

    def get_status(self, params, qp, body, headers):
        if "protobuf" in headers.get("Accept", ""):
            # internal.NodeStatus bytes (private.proto:127-132) — what
            # the reference exchanges in gossip state push/pull
            # (gossip.go LocalState/MergeRemoteState).
            from pilosa_tpu.server import wireproto

            scheme = "http"
            if self.cluster and self.local_host:
                me = self.cluster.node_by_host(self.local_host)
                if me is not None:
                    scheme = me.scheme
            schema = self.holder.schema(include_meta=True)
            max_slices = self.holder.max_slices()
            for idx in schema:
                idx["maxSlice"] = max_slices.get(idx["name"], 0)
            ns = wireproto.encode_node_status({
                "host": self.local_host or "",
                "state": self._node_state(),
                "scheme": scheme,
                "indexes": schema,
            })
            return 200, "application/x-protobuf", ns
        status = {
            "state": self._node_state(),
            "nodes": (self.cluster.status()["nodes"] if self.cluster else []),
            "indexes": self.holder.schema(),
        }
        if self.cluster:
            states = self.cluster.node_states()
            status["nodeStates"] = states
            cluster_status = self.cluster.status()
            if "placement" in cluster_status:
                # Elastic topology: committed generation + phase +
                # per-node JOINING/LEAVING roles while a resize runs.
                status["placement"] = cluster_status["placement"]
            # Reference wire shape: Go json-marshals the ClusterStatus
            # proto struct, so ecosystem clients parse CAPITALIZED
            # keys — docs/getting-started.md:37 shows
            # {"status":{"Nodes":[{"Host":":10101","State":"UP"}]}}.
            # Served alongside the richer lowercase fields.
            status["Nodes"] = [
                {"Host": n.host, "State": states.get(n.host, "UP")}
                for n in self.cluster.nodes]
        return (200, "application/json",
                json.dumps({"status": status}).encode())

    def _node_state(self):
        """How this node announces itself: LEAVING while draining (the
        graceful-shutdown broadcast — peers and load balancers polling
        /status stop routing new work here), NORMAL otherwise."""
        return "LEAVING" if self._drain is not None else "NORMAL"

    def get_version(self, params, qp, body, headers):
        return (200, "application/json",
                json.dumps({"version": self.version}).encode())

    def get_hosts(self, params, qp, body, headers):
        hosts = (self.cluster.status()["nodes"] if self.cluster
                 else [{"host": self.local_host or "localhost"}])
        return 200, "application/json", json.dumps(hosts).encode()

    def get_id(self, params, qp, body, headers):
        return 200, "text/plain", (self.holder.local_id or "").encode()

    def get_slices_max(self, params, qp, body, headers):
        if qp.get("inverse", ["false"])[0] == "true":
            m = self.holder.max_inverse_slices()
        else:
            m = self.holder.max_slices()
        return (200, "application/json",
                json.dumps({"maxSlices": m}).encode())

    # ----------------------------------------------------------- indexes

    def _index(self, name):
        idx = self.holder.index(name)
        if idx is None:
            raise HTTPError(404, str(perr.ErrIndexNotFound()))
        return idx

    def get_index(self, params, qp, body, headers):
        idx = self._index(params["index"])
        return (200, "application/json", json.dumps({
            "index": {"name": idx.name, "columnLabel": idx.column_label,
                      "timeQuantum": idx.time_quantum}}).encode())

    def post_index(self, params, qp, body, headers):
        opts = json.loads(body or b"{}").get("options", {})
        try:
            self.holder.create_index(
                params["index"],
                column_label=opts.get("columnLabel", ""),
                time_quantum=opts.get("timeQuantum", ""))
        except perr.ErrIndexExists as e:
            raise HTTPError(409, str(e))
        self._broadcast({"type": "create-index", "index": params["index"],
                         "options": opts})
        return 200, "application/json", b"{}"

    def delete_index(self, params, qp, body, headers):
        # holder.on_index_drop releases the index's plan-cache state
        # (entries, universe memos, stats) on every removal path.
        self.holder.delete_index(params["index"])
        self._broadcast({"type": "delete-index", "index": params["index"]})
        return 200, "application/json", b"{}"

    def patch_index_time_quantum(self, params, qp, body, headers):
        q = json.loads(body or b"{}").get("timeQuantum", "")
        self._index(params["index"]).set_time_quantum(q)
        return 200, "application/json", b"{}"

    def _attr_blocks(self, req):
        """Validated (id, checksum) pairs from an attr-diff body — a
        malformed entry is the caller's 400, not a KeyError-500."""
        out = []
        for b in req.get("blocks", []):
            self._require(b, "id", "checksum")
            out.append((b["id"], _decode_checksum(b["checksum"])))
        return out

    def post_index_attr_diff(self, params, qp, body, headers):
        """(ref: handler.go:545 handlePostIndexAttrDiff)."""
        idx = self._index(params["index"])
        req = json.loads(body or b"{}")
        blocks = self._attr_blocks(req)
        diff_ids = idx.column_attr_store.blocks_diff(blocks)
        attrs = {}
        for block_id in diff_ids:
            for id_, m in idx.column_attr_store.block_data(block_id).items():
                attrs[str(id_)] = m
        return (200, "application/json",
                json.dumps({"attrs": attrs}).encode())

    # ------------------------------------------------------------ frames

    def _frame(self, index, frame):
        fr = self._index(index).frame(frame)
        if fr is None:
            raise HTTPError(404, str(perr.ErrFrameNotFound()))
        return fr

    def post_frame(self, params, qp, body, headers):
        opts = json.loads(body or b"{}").get("options", {})
        try:
            self._index(params["index"]).create_frame(
                params["frame"], FrameOptions.from_dict(opts))
        except perr.ErrFrameExists as e:
            raise HTTPError(409, str(e))
        self._broadcast({"type": "create-frame", "index": params["index"],
                         "frame": params["frame"], "options": opts})
        return 200, "application/json", b"{}"

    def delete_frame(self, params, qp, body, headers):
        self._index(params["index"]).delete_frame(params["frame"])
        self._broadcast({"type": "delete-frame", "index": params["index"],
                         "frame": params["frame"]})
        return 200, "application/json", b"{}"

    def patch_frame_time_quantum(self, params, qp, body, headers):
        q = json.loads(body or b"{}").get("timeQuantum", "")
        self._frame(params["index"], params["frame"]).set_time_quantum(q)
        return 200, "application/json", b"{}"

    def post_frame_attr_diff(self, params, qp, body, headers):
        fr = self._frame(params["index"], params["frame"])
        req = json.loads(body or b"{}")
        blocks = self._attr_blocks(req)
        diff_ids = fr.row_attr_store.blocks_diff(blocks)
        attrs = {}
        for block_id in diff_ids:
            for id_, m in fr.row_attr_store.block_data(block_id).items():
                attrs[str(id_)] = m
        return (200, "application/json",
                json.dumps({"attrs": attrs}).encode())

    def post_field(self, params, qp, body, headers):
        opts = json.loads(body or b"{}")
        field = Field(params["field"], opts.get("type", "int"),
                      opts.get("min", 0), opts.get("max", 0))
        self._frame(params["index"], params["frame"]).create_field(field)
        self._broadcast({"type": "create-field", "index": params["index"],
                         "frame": params["frame"],
                         "field": field.to_dict()})
        return 200, "application/json", b"{}"

    def delete_field(self, params, qp, body, headers):
        self._frame(params["index"], params["frame"]).delete_field(
            params["field"])
        self._broadcast({"type": "delete-field", "index": params["index"],
                         "frame": params["frame"], "field": params["field"]})
        return 200, "application/json", b"{}"

    def get_fields(self, params, qp, body, headers):
        fr = self._frame(params["index"], params["frame"])
        return (200, "application/json", json.dumps(
            {"fields": [f.to_dict() for f in fr.fields]}).encode())

    def post_view(self, params, qp, body, headers):
        self._frame(params["index"], params["frame"]).create_view_if_not_exists(
            params["view"])
        return 200, "application/json", b"{}"

    def get_views(self, params, qp, body, headers):
        fr = self._frame(params["index"], params["frame"])
        return (200, "application/json", json.dumps(
            {"views": sorted(fr.views)}).encode())

    # -------------------------------------------------- input definitions

    def post_input_definition(self, params, qp, body, headers):
        req = json.loads(body or b"{}")
        for fr in req.get("frames", []):
            # Malformed entries are the CALLER's fault (400) — without
            # this, the storage layer's fr["name"] KeyError would
            # surface as a 500 handler bug.
            self._require(fr, "name")
        self._index(params["index"]).create_input_definition(
            params["def"], req.get("frames", []), req.get("fields", []))
        return 200, "application/json", b"{}"

    def get_input_definition(self, params, qp, body, headers):
        idef = self._index(params["index"]).input_definition(params["def"])
        return (200, "application/json",
                json.dumps(idef.to_dict()).encode())

    def delete_input_definition(self, params, qp, body, headers):
        self._index(params["index"]).delete_input_definition(params["def"])
        return 200, "application/json", b"{}"

    def post_input(self, params, qp, body, headers):
        return self._gated(self._post_input_inner, params, qp, body,
                           headers)

    def _post_input_inner(self, params, qp, body, headers):
        """JSON records through an input definition
        (ref: handler.go:1907-2014)."""
        idx = self._index(params["index"])
        idef = idx.input_definition(params["def"])
        records = json.loads(body or b"[]")
        bits_by_frame = idef.parse_records(records)
        for frame, bits in bits_by_frame.items():
            idx.input_bits(frame, [
                (row, col,
                 datetime.fromtimestamp(t) if t is not None else None)
                for row, col, t in bits])
        return 200, "application/json", b"{}"

    # ------------------------------------------------------------ import

    @staticmethod
    def _require(req, *keys):
        """Explicit request-body validation: a missing field is the
        CALLER's fault (400) — since _dispatch_route stopped mapping
        KeyError to 400, bare ``req[...]`` on client input would
        misreport malformed bodies as handler bugs (500)."""
        for key in keys:
            if key not in req:
                raise HTTPError(400, f"missing field: {key}")

    def post_import(self, params, qp, body, headers):
        return self._gated(self._post_import_inner, params, qp, body,
                           headers)

    def _post_import_inner(self, params, qp, body, headers):
        """Bulk bit import (ref: handlePostImport handler.go:1164-1243).
        Body: protobuf ImportRequest or JSON {index, frame, slice,
        rowIDs, columnIDs, timestamps?}."""
        if headers.get("Content-Type") == "application/x-protobuf":
            from pilosa_tpu.server import wireproto
            req = wireproto.decode_import_request(body)
        else:
            req = json.loads(body)
        self._require(req, "index", "frame")
        index, frame = req["index"], req["frame"]
        fr = self._frame(index, frame)
        timestamps = req.get("timestamps")
        ts = None
        if timestamps and any(timestamps):
            ts = [datetime.fromtimestamp(t) if t else None for t in timestamps]
        if req.get("rowKeys") or req.get("columnKeys"):
            return self._post_import_keyed(index, fr, req, ts, body,
                                           headers)
        slice_num = int(req.get("slice", 0))
        self._check_slice_ownership(index, slice_num)
        self._require(req, "rowIDs", "columnIDs")
        # New-slice broadcast happens in View.create_fragment_if_not_exists
        # (once per genuinely new slice), so no per-request message here.
        fr.import_bits(req["rowIDs"], req["columnIDs"], ts)
        return 200, "application/json", b"{}"

    def _post_import_keyed(self, index, fr, req, ts, body, headers):
        """Keyed import: the reference carries RowKeys/ColumnKeys on the
        wire (public.proto:77-78, ImportK client.go:307) but its server
        never reads them; here the keys become dense IDs (row keys per
        frame, column keys per index) and the bits flow through the
        normal ownership-routed pipeline.

        Key→ID allocation must be a single authority or two nodes would
        mint conflicting IDs for the same key, so non-authority nodes
        proxy the request to the cluster's key authority (the lowest
        host — deterministic from static membership); the authority
        translates and fans the bits out to each slice's owners."""
        row_keys = req.get("rowKeys") or []
        col_keys = req.get("columnKeys") or []
        if len(row_keys) != len(col_keys):
            raise HTTPError(400, "row/column key length mismatch")
        if ts is not None and len(ts) != len(row_keys):
            raise HTTPError(400, "timestamp length mismatch")

        if self.cluster is not None and len(self.cluster.nodes) > 1:
            c = getattr(self.executor, "client", None)
            if c is None:
                # A multi-node keyed import needs the internal client
                # both to proxy to the authority and to fan translated
                # bits out to slice owners; translating locally instead
                # would mint conflicting key→ID allocations.
                raise HTTPError(
                    500, "no internal client for multi-node keyed import")
            authority = min(self.cluster.nodes, key=lambda n: n.host)
            if authority.host != self.local_host:
                from pilosa_tpu.cluster import client as cclient

                # Internal-plane hop: this node already holds its own
                # admission slot for the request, so the authority must
                # not queue (or quota-charge) the proxied leg behind
                # user traffic; the remaining deadline budget rides
                # along as header and caps the socket timeout (which
                # never exceeds the client's flat health timeout — a
                # generous budget must not disable dead-peer
                # detection, the execute_query discipline).
                fwd = {qos_mod.PRIORITY_HEADER: "internal"}
                timeout = None
                budget_bound = False
                dl = qos_mod.current_deadline()
                if dl is not None:
                    remaining = dl - time.monotonic()
                    if remaining <= 0:
                        raise HTTPError(504, "deadline exceeded")
                    fwd[qos_mod.DEADLINE_HEADER] = \
                        f"{qos_mod.wall_deadline(dl):.6f}"
                    timeout = min(c.timeout, remaining)
                    budget_bound = remaining < c.timeout
                try:
                    status, data, _ = c._do(
                        "POST", cclient._node_url(authority, "/import"),
                        body,
                        content_type=headers.get("Content-Type",
                                                 "application/json"),
                        extra_headers=fwd, timeout=timeout,
                        budget_timeout=budget_bound)
                except cclient.ClientError as e:
                    if e.timed_out and budget_bound:
                        raise HTTPError(504, "deadline exceeded")
                    raise
                return (status, "application/json",
                        data or b"{}")

        idx = self._index(index)
        row_ids = np.asarray(fr.row_key_store.translate(row_keys),
                             dtype=np.int64)
        col_ids = np.asarray(idx.column_key_store.translate(col_keys),
                             dtype=np.int64)
        if self.cluster is None or len(self.cluster.nodes) <= 1:
            # Frame.import_bits partitions by slice itself — and takes
            # the arrays NATIVELY (it np.asarray's its inputs): the
            # old .tolist() round-trip re-boxed every id into a Python
            # int just to re-vectorize it one frame deeper.
            fr.import_bits(row_ids, col_ids, ts)
            return 200, "application/json", b"{}"
        # Fan translated bits out to every slice owner through the
        # internal import path (same routing as the non-keyed client).
        slices = col_ids // SLICE_WIDTH
        order = np.argsort(slices, kind="stable")
        bounds = np.flatnonzero(np.diff(slices[order])) + 1
        for g in np.split(order, bounds):
            if not len(g):
                continue
            gts = ([int(ts[i].timestamp()) if ts[i] else 0 for i in g]
                   if ts else None)
            self.executor.client.import_bits(
                self.cluster, index, fr.name, int(slices[g[0]]),
                row_ids[g].tolist(), col_ids[g].tolist(), gts)
        return 200, "application/json", b"{}"

    def post_import_value(self, params, qp, body, headers):
        return self._gated(self._post_import_value_inner, params, qp,
                           body, headers)

    def _post_import_value_inner(self, params, qp, body, headers):
        """(ref: handler.go:1244+). Body: {index, frame, field, slice,
        columnIDs, values}."""
        if headers.get("Content-Type") == "application/x-protobuf":
            from pilosa_tpu.server import wireproto
            req = wireproto.decode_import_value_request(body)
        else:
            req = json.loads(body)
        self._require(req, "index", "frame", "field", "columnIDs",
                      "values")
        index = req["index"]
        self._check_slice_ownership(index, int(req.get("slice", 0)))
        fr = self._frame(index, req["frame"])
        fr.import_value(req["field"], req["columnIDs"], req["values"])
        return 200, "application/json", b"{}"

    # ------------------------------------------------------------ ingest

    def post_ingest(self, params, qp, body, headers):
        """Streaming bulk-ingest route (ingest/pipeline.py): large
        columnar (row, column[, timestamp]) or (column, value) batches
        in ONE request — binary columnar
        (``application/x-pilosa-ingest``, ingest/codec.py) or JSON —
        admitted at the dedicated ``ingest`` QoS priority so a
        saturated gate back-pressures bulk loads (503 + Retry-After)
        before they can crowd out serving reads. Chunked
        transfer-encoding is accepted (the streaming producer shape).
        ``?slice=`` marks a coordinator's slice-targeted fan-out leg:
        ownership-checked (412), installed locally."""
        return self._gated(self._post_ingest_inner, params, qp, body,
                           headers,
                           default_priority=qos_mod.PRIO_INGEST)

    def _post_ingest_inner(self, params, qp, body, headers):
        from pilosa_tpu.ingest import codec as ingest_codec
        from pilosa_tpu.ingest.pipeline import IngestError

        if self.ingest is None:
            raise HTTPError(
                501, "ingest pipeline disabled ([ingest] enabled)")
        index = params["index"]
        if headers.get("Content-Type") == ingest_codec.CONTENT_TYPE:
            try:
                req = ingest_codec.decode(body)
            except ingest_codec.CodecError as e:
                raise HTTPError(400, str(e))
        else:
            req = json.loads(body or b"{}")
        self._require(req, "frame")
        self._frame(index, req["frame"])  # 404 like the legacy import
        local = "slice" in qp
        if local:
            self._check_slice_ownership(index, int(qp["slice"][0]))
        try:
            if req.get("values") is not None:
                self._require(req, "field", "columns", "values")
                out = self.ingest.ingest_values(
                    index, req["frame"], req["field"], req["columns"],
                    req["values"], local=local)
            else:
                self._require(req, "rows", "columns")
                ts = req.get("timestamps")
                if ts is not None and isinstance(ts, list):
                    # JSON twin: null = no timestamp (0 on the wire).
                    ts = [int(t) if t else 0 for t in ts]
                out = self.ingest.ingest_bits(
                    index, req["frame"], req["rows"], req["columns"],
                    ts, local=local)
        except IngestError as e:
            raise HTTPError(e.status, str(e))
        return 200, "application/json", json.dumps(out).encode()

    def _check_slice_ownership(self, index, slice_num):
        """Precondition check (ref: handler.go:1199-1203)."""
        if self.cluster and self.local_host:
            if not self.cluster.owns_fragment(self.local_host, index,
                                              slice_num):
                raise HTTPError(412, "host does not own slice")

    def get_export(self, params, qp, body, headers):
        """CSV export of one view+slice (ref: handler.go:1314-1364)."""
        index = qp.get("index", [""])[0]
        frame = qp.get("frame", [""])[0]
        view = qp.get("view", ["standard"])[0]
        slice_num = int(qp.get("slice", ["0"])[0])
        frag = self.holder.fragment(index, frame, view, slice_num)
        out = io.StringIO()
        if frag is not None:
            for row_id in frag.rows():
                words = frag.row_words(row_id)
                bits = np.flatnonzero(np.unpackbits(
                    words.view(np.uint8), bitorder="little"))
                for col in bits:
                    out.write(f"{row_id},"
                              f"{int(col) + slice_num * SLICE_WIDTH}\n")
        return 200, "text/csv", out.getvalue().encode()

    # --------------------------------------------------------- fragments

    def _fragment_params(self, qp):
        return (qp.get("index", [""])[0], qp.get("frame", [""])[0],
                qp.get("view", ["standard"])[0],
                int(qp.get("slice", ["0"])[0]))

    def get_fragment_data(self, params, qp, body, headers):
        """Stream a fragment backup tar (ref: handler.go:1387-1414)."""
        index, frame, view, slice_num = self._fragment_params(qp)
        frag = self.holder.fragment(index, frame, view, slice_num)
        if frag is None:
            raise HTTPError(404, str(perr.ErrFragmentNotFound()))
        buf = io.BytesIO()
        frag.write_to(buf)
        return 200, "application/octet-stream", buf.getvalue()

    def post_fragment_data(self, params, qp, body, headers):
        """Restore a fragment from a backup tar (ref: handler.go:1416-1446).

        ``?merge=1`` (the elastic-rebalance install path) unions the
        snapshot's bits into the current fragment instead of replacing
        it — a replace would wipe dual writes applied to this replica
        while the snapshot was in flight."""
        index, frame, view, slice_num = self._fragment_params(qp)
        want = headers.get("X-Pilosa-Fragment-Checksum")
        if want:
            # Pre-apply transit verification (the rebalancer always
            # stamps it): a corrupted payload must be rejected BEFORE
            # it merges — merged garbage bits cannot be re-shipped
            # away.
            import hashlib

            got = hashlib.sha256(body or b"").hexdigest()
            if got != want.strip().lower():
                raise HTTPError(
                    422, f"fragment payload checksum mismatch "
                         f"(got {got[:16]}..., want {want[:16]}...)")
        fr = self._frame(index, frame)
        frag = fr.create_view_if_not_exists(view).create_fragment_if_not_exists(
            slice_num)
        if qp.get("merge", ["0"])[0] in ("1", "true"):
            frag.merge_from(io.BytesIO(body))
        else:
            frag.read_from(io.BytesIO(body))
        return 200, "application/json", b"{}"

    def get_fragment_blocks(self, params, qp, body, headers):
        """(ref: handler.go:1486). JSON with base64 checksums — Go
        marshals []byte as base64, so reference tooling parses this."""
        index, frame, view, slice_num = self._fragment_params(qp)
        frag = self.holder.fragment(index, frame, view, slice_num)
        if frag is None:
            raise HTTPError(404, str(perr.ErrFragmentNotFound()))
        blocks = [{"id": b, "checksum": base64.b64encode(cs).decode()}
                  for b, cs in frag.blocks()]
        return (200, "application/json",
                json.dumps({"blocks": blocks}).encode())

    def get_fragment_digest(self, params, qp, body, headers):
        """Fragment-level anti-entropy digest (beyond-ref: the
        reference walks block checksums unconditionally,
        fragment.go:1703-1782; this one value lets replicas agree in
        O(1) wire bytes). 404 when the fragment doesn't exist — the
        syncer maps that to the canonical empty digest."""
        index, frame, view, slice_num = self._fragment_params(qp)
        frag = self.holder.fragment(index, frame, view, slice_num)
        if frag is None:
            raise HTTPError(404, str(perr.ErrFragmentNotFound()))
        return (200, "application/json",
                json.dumps({"digest": frag.digest().hex()}).encode())

    def get_fragment_block_data(self, params, qp, body, headers):
        """(ref: handler.go:1448-1484): the reference protocol is a
        protobuf BlockDataRequest in the request BODY and a protobuf
        BlockDataResponse back. Query-param/JSON remains as a
        debugging convenience when no body is sent."""
        from pilosa_tpu.server import wireproto

        if body:
            try:
                req = wireproto.decode_block_data_request(body)
            except (ValueError, IndexError):
                raise HTTPError(400, "unmarshal body error")
            frag = self.holder.fragment(req["index"], req["frame"],
                                        req["view"], req["slice"])
            if frag is None:
                raise HTTPError(404, str(perr.ErrFragmentNotFound()))
            rows, cols = frag.block_data(req["block"])
            return (200, "application/protobuf",
                    wireproto.encode_block_data_response(
                        rows.tolist(), cols.tolist()))
        index, frame, view, slice_num = self._fragment_params(qp)
        block = int(qp.get("block", ["0"])[0])
        frag = self.holder.fragment(index, frame, view, slice_num)
        if frag is None:
            raise HTTPError(404, str(perr.ErrFragmentNotFound()))
        rows, cols = frag.block_data(block)
        return (200, "application/json", json.dumps({
            "rowIDs": rows.tolist(), "columnIDs": cols.tolist()}).encode())

    def get_fragment_nodes(self, params, qp, body, headers):
        """(ref: handler.go:1366)."""
        index = qp.get("index", [""])[0]
        slice_num = int(qp.get("slice", ["0"])[0])
        if self.cluster:
            nodes = [{"host": n.host, "scheme": n.scheme}
                     for n in self.cluster.fragment_nodes(index, slice_num)]
        else:
            nodes = [{"host": self.local_host or "localhost",
                      "scheme": "http"}]
        return 200, "application/json", json.dumps(nodes).encode()

    # ----------------------------------------------------------- cluster

    def post_cluster_message(self, params, qp, body, headers):
        """DDL broadcast receiver (ref: handler.go:2041,
        Server.ReceiveMessage server.go:359-442). The reference
        protocol is a 1-type-byte + protobuf envelope
        (broadcast.go:139-196); JSON bodies remain accepted for
        older in-house peers."""
        ctype = headers.get("Content-Type", "")
        if "protobuf" in ctype:
            from pilosa_tpu.server import wireproto

            try:
                msg = wireproto.decode_cluster_message(body)
            except (ValueError, IndexError):
                raise HTTPError(400, "unmarshal body error")
        else:
            msg = json.loads(body)
        self.receive_message(msg)
        return 200, "application/json", b"{}"

    def receive_message(self, msg):
        t = msg.get("type")
        if t == "create-index":
            try:
                opts = msg.get("options", {})
                self.holder.create_index(
                    msg["index"], column_label=opts.get("columnLabel", ""),
                    time_quantum=opts.get("timeQuantum", ""))
            except perr.ErrIndexExists:
                pass
        elif t == "delete-index":
            try:
                self.holder.delete_index(msg["index"])
            except perr.ErrIndexNotFound:
                pass
        elif t == "create-frame":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                try:
                    idx.create_frame(msg["frame"], FrameOptions.from_dict(
                        msg.get("options", {})))
                except perr.ErrFrameExists:
                    pass
        elif t == "delete-frame":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                idx.delete_frame(msg["frame"])
        elif t == "create-field":
            idx = self.holder.index(msg["index"])
            fr = idx.frame(msg["frame"]) if idx is not None else None
            if fr is not None:
                try:
                    fr.create_field(Field.from_dict(msg["field"]))
                except perr.ErrFieldExists:
                    pass
        elif t == "delete-field":
            idx = self.holder.index(msg["index"])
            fr = idx.frame(msg["frame"]) if idx is not None else None
            if fr is not None:
                fr.delete_field(msg["field"])
        elif t == "delete-view":
            idx = self.holder.index(msg["index"])
            fr = idx.frame(msg["frame"]) if idx is not None else None
            if fr is not None:
                try:
                    fr.delete_view(msg["view"])
                except perr.ErrInvalidView:
                    pass
        elif t == "create-slice":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                if msg.get("inverse"):
                    idx.set_remote_max_inverse_slice(msg["slice"])
                else:
                    idx.set_remote_max_slice(msg["slice"])
        elif t == "create-input-definition":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                d = msg["definition"]
                try:
                    idx.create_input_definition(
                        msg["name"], d.get("frames", []), d.get("fields", []))
                except perr.ErrInputDefinitionExists:
                    pass
        elif t == "delete-input-definition":
            idx = self.holder.index(msg["index"])
            if idx is not None:
                idx.delete_input_definition(msg["name"])
        elif t == "placement-state":
            # Elastic topology: a resize coordinator's full placement
            # state (begin/commit/cleanup/abort all ship the same
            # shape; seq-guarded, so re-delivery is a no-op). STRICT:
            # a stale sender or a local pending-hints veto answers an
            # error the coordinator must abort on, never a silent 200.
            if self.rebalancer is not None:
                from pilosa_tpu.cluster.rebalancer import RebalanceError

                try:
                    self.rebalancer.receive_state(msg.get("state"),
                                                  strict=True)
                except RebalanceError as e:
                    raise HTTPError(409, str(e))

    def post_internal_heartbeat(self, params, qp, body, headers):
        """Bidirectional NodeStatus exchange riding the membership
        probe (the memberlist push/pull analog, gossip.go
        LocalState/MergeRemoteState): merge the prober's compact
        status, reply with ours. Both merge operations are create-only
        /monotonic, so out-of-order or repeated exchanges are safe."""
        st = json.loads(body or b"{}")
        if st:
            if self.epochs is not None and isinstance(
                    st.get("epochs"), dict) and st.get("host"):
                # Epoch piggyback rides the heartbeat both directions
                # (the membership probe is the freshness backstop that
                # keeps the serving path from ever needing to probe).
                self.epochs.observe(st["host"], st["epochs"])
            if self.rebalancer is not None:
                # Placement piggyback, receive side: a peer that
                # missed a resize broadcast converges from the
                # prober's state (seq-guarded; re-application no-ops).
                self.rebalancer.merge_placement(st)
            try:
                self.holder.merge_remote_status(st)
            except Exception:  # noqa: BLE001 — a malformed peer status; pilint: disable=swallow
                pass           # must not fail the liveness exchange
        local = self.holder.node_status_compact(self.local_host or "")
        if self.epochs is not None:
            from pilosa_tpu.cluster import epochs as epochs_mod

            local["epochs"] = epochs_mod.local_epochs(self.holder)
        if self.cluster is not None and self.cluster.placement.active:
            # ...and ride our placement back so the PROBER converges
            # off our state too (its merge_fn applies the reply).
            local["placement"] = self.cluster.placement.wire_state()
        if (st.get("schemaDigest")
                and st.get("schemaDigest") == local.get("schemaDigest")):
            # The prober already holds an identical schema: reply with
            # digest + max-slice maps only (steady-state probes stay
            # tiny on the wire in both directions).
            local.pop("schema", None)
        return 200, "application/json", json.dumps(local).encode()

    def post_cluster_resize(self, params, qp, body, headers):
        """Begin an online resize: ``{"hosts": [...]}`` names the new
        generation's ordered host list (order matters — the jump hash
        is evaluated over it). Returns 202 with the migration summary;
        the stream runs in the background (GET /debug/rebalance).
        409 when a resize is already in flight, 400 on validation
        errors, 501 on single-node servers (no broadcast plane)."""
        from pilosa_tpu.cluster.rebalancer import RebalanceError

        if self.rebalancer is None:
            raise HTTPError(
                501, "resize requires a multi-node server "
                     "(configure [cluster] hosts)")
        try:
            req = json.loads(body or b"{}")
        except ValueError:
            raise HTTPError(400, "invalid JSON body")
        hosts = req.get("hosts")
        if not isinstance(hosts, list) or not hosts \
                or not all(isinstance(h, str) and h for h in hosts):
            raise HTTPError(
                400, 'body must be {"hosts": ["host:port", ...]}')
        try:
            out = self.rebalancer.resize(hosts)
        except RebalanceError as e:
            msg = str(e)
            status = 409 if ("already" in msg or "in flight" in msg) \
                else 400
            raise HTTPError(status, msg)
        return 202, "application/json", json.dumps(out).encode()

    def post_cluster_autopilot_plan(self, params, qp, body, headers):
        """Dry-run one autopilot control cycle NOW: sense, plan, and
        return the actions the controller WOULD take — with the full
        sensor evidence inline — without actuating anything, without
        journaling an apply, and without consuming a rate-limit
        token. The operator's preview before trusting a loop with the
        cluster. 400 when the autopilot is disabled."""
        ap = self.autopilot
        if not ap.enabled:
            raise HTTPError(
                400, "autopilot is disabled (configure [autopilot] "
                     "enabled = true or PILOSA_AUTOPILOT_ENABLED=1)")
        try:
            plan = ap.plan()
        except Exception as e:  # noqa: BLE001 — surface, don't 500-trace
            raise HTTPError(500, f"autopilot plan failed: {e}")
        out = {k: v for k, v in plan.items() if not k.startswith("_")}
        out["dryRun"] = True
        return 200, "application/json", json.dumps(out).encode()

    def get_debug_rebalance(self, params, qp, body, headers):
        """Migration introspection: placement generations/phase/roles,
        stream counters, per-peer transfer stats, last error. Serves a
        placement-only view on nodes without a rebalancer."""
        if self.rebalancer is not None:
            out = self.rebalancer.snapshot()
        elif self.cluster is not None:
            out = {"running": False,
                   "placement": self.cluster.placement.snapshot()}
        else:
            out = {"running": False, "placement": None}
        return 200, "application/json", json.dumps(out).encode()

    def get_internal_epochs(self, params, qp, body, headers):
        """Epoch probe target (cluster/epochs.py ensure_fresh): this
        node's per-index mutation counters. Answers on single-node
        servers too — a peer joining a rolling upgrade may probe
        before this node knows it is part of a cluster."""
        from pilosa_tpu.cluster import epochs as epochs_mod

        return (200, "application/json", json.dumps({
            "host": self.local_host or "",
            "epochs": epochs_mod.local_epochs(self.holder)}).encode())

    def get_debug_epochs(self, params, qp, body, headers):
        """Epoch-vector introspection (mirrors /debug/qos): local
        counters, every peer's last-observed vector with age and
        freshness verdict, probe/cold counters. ``{"enabled": false}``
        on single-node servers."""
        snap = (self.epochs.snapshot() if self.epochs is not None
                else {"enabled": False})
        return 200, "application/json", json.dumps(snap).encode()

    def get_debug_plans(self, params, qp, body, headers):
        """Slice-plan cache introspection (mirrors /debug/epochs):
        entry counts by kind, totals, per-index hit rates with the
        current validity epochs, and the slice-universe memo state.
        ``{"enabled": false}`` when [executor] plan-cache-entries=0.
        The ``planner`` block (planner.py) reports the adaptive
        planner's switches and decision counters — reorders,
        short-circuits by kind, tier overrides by from->to — whose
        memoized plans are the cache's ``planner`` entry kind."""
        snap = self.executor.plans.snapshot()
        snap["planner"] = self.executor.planner.snapshot()
        return 200, "application/json", json.dumps(snap).encode()

    def get_debug_mesh(self, params, qp, body, headers):
        """Collective data plane introspection (mirrors /debug/plans):
        peer-group membership with mesh coordinates, collective
        launches by kind, HTTP fallbacks by reason, and the staged
        sharded-stack cache. ``{"enabled": false}`` when [mesh] is
        off."""
        mp = getattr(self.executor, "meshplane", None)
        snap = mp.snapshot() if mp is not None else {"enabled": False}
        return 200, "application/json", json.dumps(snap).encode()

    def get_internal_probe(self, params, qp, body, headers):
        """SWIM-style indirect ping helper: probe the target's /id on
        behalf of a suspicious peer (the memberlist indirect-probe
        analog; membership.py suspicion path). The target must be a
        cluster member — this endpoint is NOT a general fetch proxy
        (scheme/URI come from our own membership record, never the
        request), so it cannot be used to scan internal networks."""
        host = qp.get("host", [""])[0]
        if not host:
            raise HTTPError(400, "host required")
        node = self.cluster.node_by_host(host) if self.cluster else None
        if node is None:
            raise HTTPError(400, "host is not a cluster member")
        client = getattr(self.executor, "client", None)
        if client is not None:
            ok = client.probe(node, timeout=3)
        else:  # single-node server asked to probe: best-effort plain GET
            import urllib.request

            try:
                with urllib.request.urlopen(f"{node.uri()}/id",
                                            timeout=3) as resp:
                    ok = resp.status == 200
            except OSError:
                ok = False
        return 200, "application/json", json.dumps({"ok": ok}).encode()

    def _broadcast(self, msg):
        if self.broadcaster:
            self.broadcaster.send_sync(msg)

    # -------------------------------------------------------------- misc

    def post_recalculate_caches(self, params, qp, body, headers):
        """(ref: handler.go:2016) — REBUILDS the TopN caches from
        storage (previously this only persisted them, so a crash that
        lost the cache sidecars left ranked TopN empty forever)."""
        self.holder.recalculate_caches()
        return 204, "application/json", b""

    def get_debug_worker(self, params, qp, body, headers):
        """Which process answered: worker frontends intercept this
        route locally with their cache counters (worker.py); a
        connection the kernel routed to the master gets this stub so
        the route never 404s mid-group."""
        import os as _os

        return (200, "application/json",
                json.dumps({"pid": _os.getpid(), "mode": "master",
                            "cache": None}).encode())

    def _stats_snapshot(self):
        """(expvar snapshot dict, governor) — shared by /debug/vars
        and /metrics so the two ops surfaces can't drift."""
        stats = getattr(self.executor.holder, "stats", None)
        snapshot = getattr(stats, "snapshot", None)
        return (snapshot() if snapshot else {},
                getattr(self.holder, "governor", None))

    def get_debug_vars(self, params, qp, body, headers):
        """expvar-style counters (ref: handler.go:1631), extended with
        the round-2 subsystems: host-memory governor gauges and the
        adaptive path model's per-shape choices."""
        data, gov = self._stats_snapshot()
        if gov is not None:
            data["hostMemGovernor"] = gov.snapshot()
        model = self.executor.path_model_snapshot()
        if model:
            data["pathModel"] = model
        # Always present (knobs + counters even before the first
        # round), like the qos/faults/memory groups below.
        data["countCoalescer"] = self.executor.coalesce_snapshot()
        rb = getattr(self.executor, "_rb_stats", None)
        if rb and rb.get("rounds"):
            data["remoteBatcher"] = dict(rb)
        if self._resp_cache is not None:
            data["responseCache"] = self._resp_cache.stats()
        data["widthWarmer"] = self.executor.warm_snapshot()
        data["oomFallbacks"] = self.executor.oom_fallbacks
        data.update(self.executor.leaf_memo)
        data.update(self.executor.topn_probe)
        data.update(self.executor.topn_select)
        data.update(self.executor.bsi_prelude)
        data.update(self.executor.range_cover)
        if self.tracer.enabled:
            data["tracing"] = self.tracer.summary()
        # One consistent snapshot: the qos/faults/memory groups answer
        # ALWAYS (disabled subsystems report {"enabled": false}-style
        # state) instead of ad-hoc counters appearing only when armed.
        data["qos"] = self.qos.snapshot()
        data["faults"] = faults_mod.ACTIVE.snapshot()
        data["memory"] = self._memory_snapshot()
        data["epochs"] = (self.epochs.snapshot()
                          if self.epochs is not None
                          else {"enabled": False})
        data["rebalance"] = (self.rebalancer.snapshot()
                             if self.rebalancer is not None
                             else {"running": False})
        data["ingest"] = (self.ingest.snapshot()
                          if self.ingest is not None
                          else {"enabled": False})
        data["planCache"] = self.executor.plans.snapshot()
        # Workload-observatory groups, always present like qos/faults
        # (disabled tiers answer {"enabled": false}).
        data["observe"] = {
            "kernels": kerneltime_mod.ACTIVE.enabled,
            "heatmap": heatmap_mod.ACTIVE.enabled,
            "sampleRate": kerneltime_mod.ACTIVE.sample_rate,
        }
        data["slo"] = self.slo.snapshot()
        data["costModel"] = costmodel_mod.ACTIVE.snapshot()
        data["autopilot"] = self.autopilot.snapshot()
        data["device"] = stats_mod.device_telemetry()
        if self.histograms.enabled:
            data["histograms"] = self.histograms.snapshot()
        return 200, "application/json", json.dumps(data).encode()

    def _memory_snapshot(self):
        """Holder memory rollup + the executor/handler cache tiers —
        shared by /debug/vars and GET /debug/memory. Shallow-copied:
        the holder memoizes its rollup, and the executor/cache keys
        added here must not leak into the shared memo."""
        mem = dict(self.holder.memory_stats())
        ex = self.executor
        mem["executor"] = {
            "stackCacheBytes": getattr(ex, "_stack_cache_bytes", 0),
            "stackCacheEntries": len(getattr(ex, "_stack_cache", ())),
            "resultMemoBytes": getattr(ex, "_result_memo_bytes", 0),
            "resultMemoEntries": len(getattr(ex, "_result_memo", ())),
        }
        if self._resp_cache is not None:
            mem["responseCache"] = self._resp_cache.stats()
        return mem

    def get_debug_memory(self, params, qp, body, headers):
        """Memory accounting rollup: per-index packed block bytes
        (host), device (HBM) mirror bytes, evicted-read memo bytes,
        disk bytes, cache occupancy; governor + executor cache tiers.
        The JSON twin of the /metrics ``pilosa_memory_*`` series."""
        return (200, "application/json",
                json.dumps(self._memory_snapshot()).encode())

    def get_debug_kernels(self, params, qp, body, headers):
        """Kernel-cost table (observe/kerneltime.py): per-(op,
        format-cell, shape-bucket) call counts and durations with
        compile-time separated from steady state, device-sampled
        means, jit cache sizes, and the transfer rollup — the measured
        cost model the planner (ROADMAP item 5) reads. {"enabled":
        false} when the observatory is off."""
        return (200, "application/json",
                json.dumps(kerneltime_mod.ACTIVE.snapshot()).encode())

    def get_debug_profile(self, params, qp, body, headers):
        """Continuous wall-clock profile (observe/profiler.py): the
        always-on stack sampler's subsystem shares and top stacks.
        Default is the standing two-generation window; ``?seconds=N``
        (cap 30) blocks that long and returns only stacks sampled
        during the wait; ``?format=folded`` renders flamegraph-ready
        collapsed-stack text instead of JSON. {"enabled": false} when
        [profile] sample-hz is 0."""
        prof = profiler_mod.ACTIVE
        fmt = qp.get("format", ["json"])[0]
        if fmt not in ("json", "folded"):
            raise HTTPError(400, "format must be json or folded")
        seconds = qp.get("seconds", [None])[0]
        if seconds is not None:
            try:
                seconds = float(seconds)
            except ValueError:
                raise HTTPError(400, "seconds must be a number")
            if seconds <= 0:
                raise HTTPError(400, "seconds must be > 0")
            out = prof.collect(min(seconds, 30.0))
            if fmt == "folded":
                lines = [f"{s['stack']} {s['samples']}"
                         for s in out.get("topStacks", ())]
                return (200, "text/plain; charset=utf-8",
                        ("\n".join(lines) + "\n").encode())
            return (200, "application/json",
                    json.dumps(out).encode())
        if fmt == "folded":
            return (200, "text/plain; charset=utf-8",
                    (prof.folded() + "\n").encode())
        return (200, "application/json",
                json.dumps(prof.snapshot()).encode())

    def post_profile_device(self, params, qp, body, headers):
        """Arm a bounded device-kernel trace capture (observe/
        devprof.py): starts a jax.profiler trace into ``?dir=`` (or
        the [profile] device-trace-dir default) and schedules its stop
        after ``?seconds=`` (cap 30) — view in TensorBoard or
        Perfetto, where the server's own spans sit beside the device's
        operations as ``pilosa:<span>`` annotations. The Python tracer
        is off. 501 when no profiling-capable backend is present, 409
        while a capture is armed or still being written."""
        trace_dir = (qp.get("dir", [None])[0]
                     or self.device_trace_dir
                     or "/tmp/pilosa_tpu_trace")
        try:
            seconds = float(qp.get("seconds", ["5"])[0])
        except ValueError:
            raise HTTPError(400, "seconds must be a number")
        try:
            out = devprof_mod.ACTIVE.device_capture(trace_dir, seconds)
        except devprof_mod.Unsupported as e:
            raise HTTPError(501, str(e))
        except RuntimeError as e:  # capture already armed
            raise HTTPError(409, str(e))
        return 200, "application/json", json.dumps(out).encode()

    def get_profile_device(self, params, qp, body, headers):
        """Where the device capture stands: ``state`` is idle, armed,
        stopping (the profiler is writing its file) or done, with the
        capture's ``dir`` and, once done, the ``file`` it wrote."""
        return (200, "application/json", json.dumps(
            devprof_mod.ACTIVE.capture_state()).encode())

    def get_debug_heatmap(self, params, qp, body, headers):
        """Decayed slice/row heat (observe/heatmap.py): the bounded
        top-K of both tables plus per-index query pressure and
        conversion churn. The JSON twin of the top-K-only
        ``pilosa_slice_heat``/``pilosa_row_heat`` series.
        ``?scope=cluster`` fans out to every reachable peer and merges
        the per-node tables into one cluster-wide heat map — the
        autopilot placement planner's sensor, served for operators
        too."""
        snap = heatmap_mod.ACTIVE.snapshot()
        if qp.get("scope", [None])[0] != "cluster":
            return (200, "application/json", json.dumps(snap).encode())

        # Cluster scope: same degraded-peer fan-out model as
        # /debug/events — skip breaker-open peers, budget each leg
        # against the request deadline, report unreachable peers in an
        # ``errors`` map instead of failing the merge.
        try:
            deadline = self.qos.request_deadline(qp, headers)
        except qos_mod.ShedError as e:
            raise HTTPError(e.status, e.reason)
        client = getattr(self.executor, "client", None)
        nodes = list(self.cluster.nodes) if self.cluster else []
        per_node = {}
        errors = {}
        for node in nodes or [None]:
            host = node.host if node is not None else (
                self.local_host or "localhost")
            if node is None or node.host == self.local_host:
                per_node[host] = snap
                continue
            if client is None:
                errors[host] = "no client"
                continue
            brk = getattr(client, "breakers", None)
            if brk is not None and brk.is_open(host):
                errors[host] = "breaker open"
                continue
            timeout = 5.0
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    errors[host] = "deadline exhausted"
                    continue
                timeout = min(timeout, remaining)
            try:
                per_node[host] = client.heatmap_json(node,
                                                     timeout=timeout)
            except Exception as e:  # noqa: BLE001 — degraded, not failed
                errors[host] = str(e) or type(e).__name__
        out = heatmap_mod.merge_snapshots(per_node)
        out["scope"] = "cluster"
        out["nodes"] = sorted(per_node)
        out["errors"] = errors
        return 200, "application/json", json.dumps(out).encode()

    def get_debug_slo(self, params, qp, body, headers):
        """SLO state (observe/slo.py): declared objectives, 5m/1h
        burn rates per priority class, and the advisory level the
        runbook maps to page/ticket."""
        return (200, "application/json",
                json.dumps(self.slo.snapshot()).encode())

    def get_debug_costmodel(self, params, qp, body, headers):
        """Cost-model calibration state (observe/costmodel.py):
        per-tier predicted-vs-measured medians over the recent sample
        ring, learned dispatch overheads, and the per-(tier, op,
        format-cell) sample table. The accuracy surface the ROADMAP-5
        planner calibration consumes. {"enabled": false} when the
        observatory is off."""
        return (200, "application/json",
                json.dumps(costmodel_mod.ACTIVE.snapshot()).encode())

    def get_debug_events(self, params, qp, body, headers):
        """Control-plane flight recorder (observe/events.py): the
        node's journal of membership/placement/rebalance/breaker/
        epoch/QoS/SLO/fault transitions. ``?kind=`` filters by exact
        kind or dotted prefix (comma list), ``?since=<id>`` returns
        only newer events, ``?limit=`` bounds the count, and
        ``?scope=cluster`` fans out to every reachable peer and merges
        the journals into one causally-ordered timeline.
        {"enabled": false} when the recorder is off."""
        rec = self.events
        if not rec.enabled:
            return (200, "application/json",
                    json.dumps({"enabled": False}).encode())
        kinds = qp.get("kind", [None])[0]
        kinds = ([k for k in kinds.split(",") if k]
                 if kinds else None)
        try:
            since = int(qp.get("since", ["0"])[0])
            limit = max(1, min(int(qp.get("limit", ["256"])[0]), 4096))
        except ValueError:
            raise HTTPError(400, "since and limit must be integers")
        out = rec.snapshot()
        if qp.get("scope", [None])[0] != "cluster":
            out["events"] = rec.recent(kinds=kinds, since=since,
                                       limit=limit)
            return 200, "application/json", json.dumps(out).encode()

        # Cluster scope: same degraded-peer fan-out model as
        # /cluster/metrics — skip breaker-open peers, budget each leg
        # against the request deadline, report unreachable peers
        # instead of failing the merge.
        try:
            deadline = self.qos.request_deadline(qp, headers)
        except qos_mod.ShedError as e:
            raise HTTPError(e.status, e.reason)
        client = getattr(self.executor, "client", None)
        nodes = list(self.cluster.nodes) if self.cluster else []
        per_node = {}
        errors = {}
        # A ``since`` watermark is per-node (ids are local sequence
        # numbers), so only the local leg honors it; peers get the
        # kind/limit filters only.
        params_out = {"limit": str(limit)}
        if kinds:
            params_out["kind"] = ",".join(kinds)
        for node in nodes or [None]:
            host = node.host if node is not None else (
                self.local_host or "localhost")
            if node is None or node.host == self.local_host:
                per_node[host] = rec.recent(kinds=kinds, since=since,
                                            limit=limit)
                continue
            if client is None:
                errors[host] = "no client"
                continue
            brk = getattr(client, "breakers", None)
            if brk is not None and brk.is_open(host):
                errors[host] = "breaker open"
                continue
            timeout = 5.0
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    errors[host] = "deadline exhausted"
                    continue
                timeout = min(timeout, remaining)
            try:
                peer = client.events_json(node, timeout=timeout,
                                          **params_out)
                per_node[host] = peer.get("events", [])
            except Exception as e:  # noqa: BLE001 — degraded, not failed
                errors[host] = str(e) or type(e).__name__
        out["scope"] = "cluster"
        out["nodes"] = sorted(per_node)
        out["errors"] = errors
        out["events"] = events_mod.merge_timelines(per_node)[-limit:]
        return 200, "application/json", json.dumps(out).encode()

    def get_debug_replicas(self, params, qp, body, headers):
        """Per-replica vitals (observe/replica.py): streaming latency
        quantiles per (peer, op-class, priority), EWMA error rates,
        live in-flight counts, epoch-probe staleness, the slow-replica
        watchdog's baseline/degraded state, and the rolled-up health
        score per peer. {"enabled": false} when vitals are off."""
        vt = self.vitals
        if vt.enabled:
            # Surface reads drive idle-window rotation so a peer that
            # went quiet still ages out of degraded state.
            vt.watchdog_tick()
        return (200, "application/json",
                json.dumps(vt.snapshot()).encode())

    def get_debug_autopilot(self, params, qp, body, headers):
        """Autopilot introspection (autopilot/controller.py): which
        loops are enabled, the hysteresis knobs, rate-limit budget
        state, per-loop dwell clocks, action/abort counters, and the
        recent plan ring with sensor evidence. {"enabled": false}
        when the controller is off."""
        return (200, "application/json",
                json.dumps(self.autopilot.snapshot()).encode())

    def get_debug_hedge(self, params, qp, body, headers):
        """Tail-tolerant read state (cluster/hedge.py): routing /
        hedging switches, delay and headroom knobs, the token-budget
        bucket (ratio/burst/live tokens), leg and win/cancel/error
        counters, live hedge in-flight gauge, and per-reason
        suppression counts. {"enabled": false} when hedging and
        replica routing are both off."""
        return (200, "application/json",
                json.dumps(self.hedger.snapshot()).encode())

    # Per-route enabled-state probes for the /debug catalog: routes
    # not listed here are unconditionally live. Lambdas read the SAME
    # state the handlers themselves serve, so the catalog can't drift
    # from the endpoints' own {"enabled": false} answers.
    def _debug_enabled_probes(self):
        return {
            "/debug/qos": lambda: self.qos.enabled,
            "/debug/traces": lambda: self.tracer.enabled,
            "/debug/faults": lambda: faults_mod.ACTIVE.enabled,
            "/debug/lockcheck": lambda: lockcheck.ACTIVE.enabled,
            "/debug/epochs": lambda: self.epochs is not None,
            "/debug/plans": lambda: self.executor.plans.capacity != 0,
            "/debug/mesh": lambda: getattr(
                self.executor, "meshplane", None) is not None,
            "/debug/kernels": lambda: kerneltime_mod.ACTIVE.enabled,
            "/debug/profile": lambda: profiler_mod.ACTIVE.enabled,
            "/debug/profile/device": lambda: devprof_mod.ACTIVE.enabled,
            "/debug/heatmap": lambda: heatmap_mod.ACTIVE.enabled,
            "/debug/slo": lambda: self.slo.enabled,
            "/debug/costmodel": lambda: costmodel_mod.ACTIVE.enabled,
            "/debug/rebalance": lambda: self.rebalancer is not None,
            "/debug/events": lambda: self.events.enabled,
            "/debug/replicas": lambda: self.vitals.enabled,
            "/debug/autopilot": lambda: self.autopilot.enabled,
            "/debug/hedge": lambda: self.hedger.enabled,
        }

    def get_debug_index(self, params, qp, body, headers):
        """Machine-readable catalog of every ``/debug/*`` endpoint:
        path, methods, one-line description (each handler's own
        docstring — the catalog is ROUTE-TABLE-DRIVEN, so a new debug
        route appears here by construction, asserted by test), and
        whether the backing subsystem is currently enabled."""
        probes = self._debug_enabled_probes()
        by_path = {}
        for method, pattern, fn in self.routes:
            path = pattern.strip("^$")
            if not path.startswith("/debug") or path == "/debug":
                continue
            ent = by_path.setdefault(path, {
                "path": path, "methods": [],
                "description": (fn.__doc__ or "").strip()
                .split("\n", 1)[0].rstrip(),
                "enabled": True,
            })
            if method not in ent["methods"]:
                ent["methods"].append(method)
            probe = probes.get(path)
            if probe is not None:
                try:
                    ent["enabled"] = bool(probe())
                except Exception:  # noqa: BLE001; pilint: disable=swallow
                    pass  # a probe racing subsystem teardown leaves
                    # the default True — the catalog row survives
        out = {"endpoints": sorted(by_path.values(),
                                   key=lambda e: e["path"])}
        return 200, "application/json", json.dumps(out).encode()

    def get_debug_traces(self, params, qp, body, headers):
        """Recent traces as JSON span trees (the trace-level analog of
        /debug/vars). ``?slow=true`` reads the slow-query ring,
        ``?traceId=`` filters (how a cross-node trace is gathered for
        stitching), ``?n=`` bounds the count."""
        try:
            n = max(1, min(int(qp.get("n", ["32"])[0]), 512))
        except ValueError:
            raise HTTPError(400, "n must be an integer")
        slow = qp.get("slow", ["false"])[0] == "true"
        trace_id = qp.get("traceId", [None])[0]
        tr = self.tracer
        out = {
            "enabled": tr.enabled,
            "slowThresholdMs": round(tr.slow_threshold * 1000, 3),
            "summary": tr.summary(),
            "traces": tr.recent(n, slow=slow, trace_id=trace_id),
        }
        return 200, "application/json", json.dumps(out).encode()

    def _metrics_text(self):
        """The node's full exposition text — /metrics body, and the
        local leg of /cluster/metrics."""
        from pilosa_tpu.stats import prometheus_exposition

        data, gov = self._stats_snapshot()
        groups = []
        if gov is not None:
            groups.append(("host_mem", gov.snapshot()))
        # pilosa_coalesce_* — micro-batching tick counters (rounds,
        # fused-by-tier, lane launches, declines by reason), always
        # present like plan_cache; the group-size distribution rides
        # the coalesce_group_size histogram family below.
        groups.append(("coalesce", self.executor.coalesce_metrics()))
        if self.qos.enabled:
            # pilosa_qos_shed_total, queue depth/in-flight gauges, and
            # pilosa_qos_breaker_state{peer=...} series.
            groups.append(("qos", self.qos.metrics()))
        if faults_mod.ACTIVE.enabled:
            # pilosa_faults_triggered_total (+ per-point series).
            groups.append(("faults", faults_mod.ACTIVE.metrics()))
        if self.epochs is not None:
            # pilosa_epoch_* — observation/probe/cold counters and the
            # cluster vector version (multi-node only).
            groups.append(("epoch", self.epochs.metrics()))
        if self.rebalancer is not None:
            # pilosa_rebalance_* — slices moved/pending, bytes
            # streamed, generation, per-peer stream totals.
            groups.append(("rebalance", self.rebalancer.metrics()))
        if self.ingest is not None:
            # pilosa_ingest_* — batches/bits/values ingested, slice
            # groups, fan-out posts, device pack passes, containers
            # seeded by format, rejects/errors.
            groups.append(("ingest", self.ingest.metrics()))
        # pilosa_plan_cache_{hits,misses,invalidations,entries} — the
        # slice-plan cache counters (plancache.py), present even when
        # the cache is disabled (entries/capacity report 0).
        groups.append(("plan_cache", self.executor.plans.metrics()))
        # pilosa_plan_{reorder,shortcircuit,tier_override}_total — the
        # adaptive planner's decision counters (planner.py): untagged
        # totals always present (zeroed from boot); kind= and from=/
        # to= tagged children appear with their first event.
        groups.append(("plan", self.executor.planner.metrics()))
        mp = getattr(self.executor, "meshplane", None)
        if mp is not None:
            # pilosa_mesh_* — collective data plane: launches by kind,
            # HTTP fallbacks by reason (pre-seeded so every series
            # exists from boot), staged-stack cache gauges.
            groups.append(("mesh", mp.metrics()))
        # Workload observatory: pilosa_kernel_* cost cells,
        # pilosa_slice_heat / pilosa_row_heat top-K series (bounded
        # cardinality by construction; /cluster/metrics merges them
        # with node= labels so the rebalancer sees cluster-wide heat),
        # pilosa_observe_* bookkeeping, pilosa_slo_* burn rates. All
        # empty (absent) when the respective tier is disabled.
        groups.append(("kernel", kerneltime_mod.ACTIVE.metrics()))
        # pilosa_profile_* — continuous-profiler bookkeeping: total/
        # per-subsystem sample counters, trie occupancy, generation
        # rotations, overflow. Absent entirely when sample-hz is 0.
        groups.append(("profile", profiler_mod.ACTIVE.metrics()))
        # pilosa_cost_model_* — predicted-vs-measured calibration
        # counters by (tier, op, format-cell); untagged totals always
        # present while the model is enabled. The error-ratio
        # distribution rides the cost_model_error histogram family.
        groups.append(("cost_model", costmodel_mod.ACTIVE.metrics()))
        hm = heatmap_mod.ACTIVE
        groups.append(("slice", hm.slice_metrics()))
        groups.append(("row", hm.row_metrics()))
        groups.append(("observe", hm.observe_metrics()))
        groups.append(("slo", self.slo.metrics()))
        if self.events.enabled:
            # pilosa_events_total{kind=...} — flight-recorder journal
            # counters (bounded cardinality: one series per event
            # kind actually emitted).
            groups.append(("events", self.events.metrics()))
        if self.vitals.enabled:
            # pilosa_replica_* — per-peer latency quantiles, in-flight
            # gauges, EWMA error rates, watchdog degraded flags, and
            # health scores (empty until the first fan-out call).
            groups.append(("replica", self.vitals.metrics()))
        if self.autopilot.enabled:
            # pilosa_autopilot_* — plans/actions/aborts/cooldown
            # counters, rate-limit budget gauge, per-loop enabled
            # flags (absent entirely when the controller is off).
            groups.append(("autopilot", self.autopilot.metrics()))
        if self.hedger.enabled:
            # pilosa_hedge_* — primary/hedge leg counters, armed/
            # fired/won/cancelled race outcomes, per-reason
            # suppression counts, the live hedge in-flight gauge,
            # and the token-budget level (absent when hedging and
            # replica routing are both off).
            groups.append(("hedge", self.hedger.metrics()))
        # pilosa_memory_fragment_bytes{index=...} & friends — the
        # HBM/host accounting rollup (holder.memory_metrics).
        groups.append(("memory", self.holder.memory_metrics()))
        hset = self.histograms if self.histograms.enabled else None
        return prometheus_exposition(data, groups, histograms=hset)

    def get_metrics(self, params, qp, body, headers):
        """Prometheus text exposition (beyond-ref; the reference
        offers expvar + statsd only, stats.go:87-165): the expvar
        snapshot with tags as labels, plus governor/coalescer/qos/
        faults/memory gauges and the tagged histogram families. Works
        when the server runs the expvar stats backend (the default);
        other backends expose what they have."""
        return (200, "text/plain; version=0.0.4; charset=utf-8",
                self._metrics_text().encode())

    def _note_scrape_error(self, host):
        # The handler dict is the ONLY home for this counter: it
        # renders as pilosa_cluster_scrape_errors_total{node="peer"}
        # in the merged payload. A parallel untagged expvar counter
        # would ride this node's own /metrics into the merge and come
        # back relabeled node="<coordinator>" — every failure counted
        # twice, half of it blaming the healthy coordinator.
        with self._scrape_mu:
            self._scrape_errors[host] = self._scrape_errors.get(
                host, 0) + 1

    def get_cluster_metrics(self, params, qp, body, headers):
        """Cluster-wide metrics aggregation: fan out to every peer's
        /metrics (breaker-aware — an open breaker's peer is skipped,
        not probed — and bounded by the request's deadline budget),
        merge same-named families with a ``node=`` label per sample,
        and degrade gracefully: an unreachable peer becomes a
        ``pilosa_cluster_scrape_errors_total{node=...}`` sample, never
        an HTTP error. One scrape target for the whole cluster."""
        if not self.cluster_metrics_enabled:
            raise HTTPError(
                403, "cluster metrics aggregation disabled "
                     "([metrics] cluster-aggregation)")
        try:
            deadline = self.qos.request_deadline(qp, headers)
        except qos_mod.ShedError as e:
            raise HTTPError(e.status, e.reason)
        client = getattr(self.executor, "client", None)
        nodes = list(self.cluster.nodes) if self.cluster else []
        texts = []
        for node in nodes or [None]:
            host = node.host if node is not None else (
                self.local_host or "localhost")
            if node is None or node.host == self.local_host:
                texts.append((host, self._metrics_text()))
                continue
            if client is None:
                self._note_scrape_error(host)
                continue
            brk = getattr(client, "breakers", None)
            if brk is not None and brk.is_open(host):
                # A breaker-open peer already proved dead moments ago;
                # scraping it would pay the timeout per poll (and a
                # metrics scrape must not consume the half-open probe
                # slot a real query deserves).
                self._note_scrape_error(host)
                continue
            timeout = 5.0
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._note_scrape_error(host)
                    continue
                timeout = min(timeout, remaining)
            try:
                texts.append((host, client.metrics_text(
                    node, timeout=timeout)))
            except Exception:  # noqa: BLE001 — degraded, not failed
                self._note_scrape_error(host)
        with self._scrape_mu:
            errors = dict(self._scrape_errors)
        merged = stats_mod.merge_expositions(texts,
                                             scrape_errors=errors)
        return (200, "text/plain; version=0.0.4; charset=utf-8",
                merged.encode())

    def get_webui(self, params, qp, body, headers):
        from pilosa_tpu.server.webui import INDEX_HTML
        return 200, "text/html", INDEX_HTML.encode()

    def get_asset(self, params, qp, body, headers):
        """Console assets (ref: /assets/{file} handler.go:101)."""
        from pilosa_tpu.server.webui import ASSETS
        asset = ASSETS.get(params["file"])
        if asset is None:
            raise HTTPError(404, "asset not found")
        ctype, content = asset
        return 200, ctype, content.encode()

    def method_not_allowed(self, params, qp, body, headers):
        """(ref: methodNotAllowedHandler handler.go:147)."""
        return 405, "application/json", b""

    def delete_view(self, params, qp, body, headers):
        """(ref: handleDeleteView handler.go:127; frame.DeleteView)."""
        fr = self._frame(params["index"], params["frame"])
        try:
            fr.delete_view(params["view"])
        except perr.ErrInvalidView:
            # Views do not exist on every node (slice distribution);
            # the reference ignores this error too.
            pass
        self._broadcast({"type": "delete-view", "index": params["index"],
                         "frame": params["frame"], "view": params["view"]})
        return 200, "application/json", b"{}"

    def post_frame_restore(self, params, qp, body, headers):
        """Pull every owned slice of a frame from a remote cluster host
        (ref: handlePostFrameRestore handler.go:121, :1680+)."""
        from pilosa_tpu.cluster.client import ClientError, InternalClient
        from pilosa_tpu.cluster.cluster import Node
        from pilosa_tpu.utils.uri import URI

        host = qp.get("host", [""])[0]
        if not host:
            raise HTTPError(400, "host required")
        index, frame = params["index"], params["frame"]
        fr = self._frame(index, frame)
        u = URI.parse(host)
        remote = Node(u.host_port(), scheme=u.scheme)
        # Reuse the executor's client so TLS skip-verify carries over
        # (ref: h.RemoteClient handler.go).
        client = getattr(self.executor, "client", None) or InternalClient()

        max_slices = client.max_slices(remote)
        max_inverse = client.max_slices(remote, inverse=True)
        views = client.frame_views(remote, index, frame)
        for view in views:
            # Inverse views span the inverse (row-derived) slice range,
            # which can exceed the standard one (ref: MaxInverseSlices
            # handler.go:323-337).
            inverse = view == "inverse" or view.startswith("inverse_")
            max_slice = (max_inverse if inverse else max_slices).get(index, 0)
            for slice_num in range(max_slice + 1):
                if (self.cluster is not None
                        and not self.cluster.owns_fragment(
                            self.local_host, index, slice_num)):
                    continue
                try:
                    tar = client.backup_fragment(
                        remote, index, frame, view, slice_num)
                except ClientError:
                    continue  # slice doesn't exist on the remote
                v = fr.create_view_if_not_exists(view)
                frag = v.create_fragment_if_not_exists(slice_num)
                frag.read_from(io.BytesIO(tar))
        return 200, "application/json", b"{}"


class _FastHeaders(dict):
    """Case-insensitive header mapping with Title-Case canonical keys
    (the cheap dict stand-in for email.Message in the fast parse
    path — handlers receive it via ``dict(self.headers)`` and look
    keys up in canonical form)."""

    def get(self, key, default=None):
        return dict.get(self, key.title(), default)

    def __contains__(self, key):
        return dict.__contains__(self, key.title())


def make_http_server(handler, bind="localhost:0", reuse_port=False,
                     max_body_size=DEFAULT_MAX_BODY_SIZE):
    """Wrap a Handler (or a bare ``dispatch(method, path, qp, body,
    headers) -> (status, ctype, payload[, extra_headers])`` callable —
    worker frontends pass one, see worker.py) in a
    ThreadingHTTPServer. ``reuse_port`` joins an SO_REUSEPORT group so
    worker processes can share the public port (see workers.py).
    Requests advertising a body larger than ``max_body_size`` are
    rejected with 413 BEFORE any body byte is buffered (0 disables
    the check)."""
    host, _, port = bind.rpartition(":")
    dispatch = handler.dispatch if hasattr(handler, "dispatch") \
        else handler

    class _Req(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # Headers and payload go out as separate writes; with Nagle on,
        # the payload segment waits out the peer's delayed ACK (~40 ms
        # per keep-alive request). Go's net/http sets TCP_NODELAY too.
        disable_nagle_algorithm = True

        def parse_request(self):
            """Fast request parse: the stdlib routes headers through
            email.feedparser (~130 µs/request — profiled at ~25% of a
            warm serve, paid again by every worker frontend and every
            internal-plane request). Plain `METHOD path HTTP/1.x`
            requests take a direct line loop into a case-insensitive
            dict; anything unusual in the REQUEST LINE delegates to
            the stdlib implementation before any header byte is
            consumed, so exotic protocol handling is unchanged. As a
            side effect header lookups become properly
            case-insensitive downstream (dict(email.Message) used to
            preserve client casing, missing lowercase senders)."""
            tracing.mark_arrival()
            line = str(self.raw_requestline, "iso-8859-1").rstrip("\r\n")
            words = line.split()
            if (len(words) != 3
                    or words[2] not in ("HTTP/1.1", "HTTP/1.0")):
                return super().parse_request()
            self.requestline = line
            self.command, self.path, self.request_version = words
            self.close_connection = words[2] == "HTTP/1.0"
            headers = _FastHeaders()
            last = None
            for _ in range(201):
                hline = self.rfile.readline(65537)
                if len(hline) > 65536:
                    self.send_error(431)  # header line too long
                    return False
                if hline in (b"\r\n", b"\n", b""):
                    break
                if hline[0] in (32, 9):
                    if last is not None:
                        # Obsolete line folding: append to the
                        # anchoring field's value.
                        headers[last] += " " + hline.strip().decode(
                            "iso-8859-1")
                    continue
                name, sep, value = hline.decode("iso-8859-1") \
                    .partition(":")
                if not sep or not name.strip():
                    last = None
                    continue  # junk line: tolerated, as email parser
                if name != name.strip():
                    # RFC 7230 §3.2.4: whitespace between field name
                    # and colon MUST be rejected — a proxy that drops
                    # such a field while we honored it is a
                    # request-smuggling differential.
                    self.send_error(400, "whitespace in header name")
                    return False
                key = name.title()
                value = value.strip()
                if key in headers:
                    if key == "Content-Length" \
                            and dict.get(headers, key) != value:
                        # Conflicting lengths desync body framing
                        # between parsers — reject outright.
                        self.send_error(400,
                                        "conflicting Content-Length")
                        return False
                    last = None  # duplicate: FIRST value wins, as
                    continue     # email.Message.get; folds dropped
                headers[key] = value
                last = key
            else:
                self.send_error(431)  # too many headers
                return False
            self.headers = headers
            conntype = headers.get("Connection", "").lower()
            if conntype == "close":
                self.close_connection = True
            elif conntype == "keep-alive":
                self.close_connection = False
            # The stdlib tail this path replaces: 100-continue must
            # be answered or body-bearing clients (curl >1 KB) stall
            # waiting for it while we block on rfile.read.
            if (headers.get("Expect", "").lower() == "100-continue"
                    and self.protocol_version >= "HTTP/1.1"
                    and self.request_version >= "HTTP/1.1"):
                if not self.handle_expect_100():
                    return False
            return True

        def _content_length(self):
            """Declared body length; None for an unparseable or
            negative header (the caller answers 400 — an uncaught
            ValueError would kill the connection with no response,
            and a negative length would reach ``rfile.read(-1)``,
            buffering until EOF past the 413 gate)."""
            try:
                length = int(self.headers.get("Content-Length") or 0)
            except ValueError:
                return None
            return None if length < 0 else length

        _INGEST_PATH = re.compile(r"^/index/[^/]+/ingest$")

        # Bulk-ingest bodies must not buffer unbounded (chunked OR
        # Content-Length): a hard sanity ceiling, far above any
        # configured batch bound ([ingest] max-batch-bits rejects
        # first in practice — this guard is the OOM backstop).
        _INGEST_HARD_CAP = 2 << 30

        def _body_cap(self, path):
            """Byte ceiling for this route's request body, 0 =
            uncapped. The 413 gate applies to every route except
            fragment restore and bulk ingest: POST /fragment/data
            legitimately carries multi-GB backup tars
            (storage/fragment.py write_to) on the intra-cluster plane
            and stays uncapped (pre-existing contract); the ingest
            route's whole point is batches far beyond the default cap,
            so it gets the hard sanity ceiling instead of the
            configured one."""
            if path == "/fragment/data":
                return 0
            if self._INGEST_PATH.match(path):
                return self._INGEST_HARD_CAP
            return max_body_size

        def _read_chunked(self, cap):
            """RFC 7230 §4.1 chunked-body decode with cumulative cap
            enforcement — the streaming-producer shape the ingest
            route accepts (a producer can start sending before it
            knows the batch size). ``cap`` 0 = uncapped, the same
            contract as the Content-Length path (POST /fragment/data
            legitimately streams multi-GB tars). Returns (body, None)
            or (None, error): "bad" = malformed framing (400),
            "too_large" = the cumulative size crossed ``cap`` (413)
            — detected mid-stream, before the rest buffers."""
            total = 0
            parts = []
            while True:
                line = self.rfile.readline(65537)
                if not line or len(line) > 65536:
                    return None, "bad"
                try:
                    size = int(line.split(b";")[0].strip(), 16)
                except ValueError:
                    return None, "bad"
                if size < 0:
                    return None, "bad"
                if size == 0:
                    while True:  # trailer section
                        t = self.rfile.readline(65537)
                        if t in (b"\r\n", b"\n", b""):
                            break
                    return b"".join(parts), None
                total += size
                if cap and total > cap:
                    return None, "too_large"
                data = self.rfile.read(size)
                if len(data) < size:
                    return None, "bad"
                parts.append(data)
                if self.rfile.read(2) != b"\r\n":
                    return None, "bad"

        def handle_expect_100(self):
            """Answer 413 instead of `100 Continue` when the declared
            body is oversized — an Expect-aware client then never
            sends the body at all."""
            length = self._content_length()
            if length is None:
                self.send_error(400, "bad Content-Length")
                return False
            cap = self._body_cap(urlparse(self.path).path)
            if cap and length > cap:
                self.send_error(413, "request body too large")
                return False
            return super().handle_expect_100()

        def _serve(self):
            parsed = urlparse(self.path)
            qp = parse_qs(parsed.query)
            te = (self.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                body, err = self._read_chunked(
                    self._body_cap(parsed.path))
                if err is not None:
                    # Mid-stream abort: the peer may still be sending,
                    # so the connection can't be reused either way.
                    self.close_connection = True
                    if err == "too_large":
                        self._reject_oversized()
                    else:
                        self.send_error(400, "bad chunked encoding")
                    return
                resp = dispatch(self.command, parsed.path, qp, body,
                                dict(self.headers))
                self._respond(resp)
                return
            length = self._content_length()
            if length is None:
                self.close_connection = True
                self.send_error(400, "bad Content-Length")
                return
            cap = self._body_cap(parsed.path)
            if cap and length > cap:
                # Reject BEFORE buffering: an arbitrarily large POST
                # must not pin server memory. The body is never read,
                # so the connection can't be reused — close it (the
                # client may still be blocked mid-send).
                self.close_connection = True
                self._reject_oversized()
                return
            body = self.rfile.read(length) if length else b""
            resp = dispatch(self.command, parsed.path, qp, body,
                            dict(self.headers))
            self._respond(resp)

        def _reject_oversized(self):
            payload = json.dumps(
                {"error": "request body too large"}).encode()
            self.send_response(413)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(payload)

        def _respond(self, resp):
            status, ctype, payload = resp[:3]
            extra = resp[3] if len(resp) > 3 else None
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(payload)))
            if extra:
                for k, v in extra.items():
                    self.send_header(k, v)
            # One sendall for headers + small payload (end_headers +
            # wfile.write would issue two): saves a syscall AND the
            # delayed-ACK interplay between the header segment and the
            # payload segment (~4x warm HTTP serving, measured). Large
            # bodies keep the separate zero-copy write — joining them
            # into the header buffer would memcpy the whole payload.
            # HTTP/0.9 has no _headers_buffer (stdlib skips buffering)
            # and takes the classic path too.
            if (len(payload) < 16384
                    and hasattr(self, "_headers_buffer")):
                self._headers_buffer.append(b"\r\n")
                self._headers_buffer.append(payload)
                self.flush_headers()
            else:
                self.end_headers()
                self.wfile.write(payload)

        do_GET = do_POST = do_DELETE = do_PATCH = _serve

        def setup(self):
            super().setup()
            self.server.track_conn(self.connection, True)

        def finish(self):
            self.server.track_conn(self.connection, False)
            super().finish()

        def log_message(self, fmt, *args):  # quiet test output
            pass

    class _Server(ThreadingHTTPServer):
        # Python's default listen backlog is 5 — a 32-client connect
        # burst gets connection-reset before a thread ever runs. The
        # reference's http.Serve inherits Go's default (SOMAXCONN).
        request_queue_size = 128
        daemon_threads = True

        def server_bind(self):
            if reuse_port:
                import socket as _socket

                self.socket.setsockopt(_socket.SOL_SOCKET,
                                       _socket.SO_REUSEPORT, 1)
            super().server_bind()

        # Established keep-alive connections outlive shutdown() —
        # ThreadingHTTPServer only stops the ACCEPT loop, while every
        # per-connection daemon thread keeps answering requests
        # against the closed server's (stale) state. A pooled internal
        # client would keep "succeeding" against a closed node — a
        # write acknowledged into state about to be discarded. Track
        # open connections and sever them in server_close(), as the
        # reference's http.Server.Close closes active conns.
        def __init__(self, *args, **kw):
            import threading as _threading

            from pilosa_tpu import lockcheck as _lockcheck

            self._open_conns = set()
            self._conns_mu = _lockcheck.register(
                "handler._Server._conns_mu", _threading.Lock())
            super().__init__(*args, **kw)

        def track_conn(self, sock, on):
            with self._conns_mu:
                if on:
                    self._open_conns.add(sock)
                else:
                    self._open_conns.discard(sock)

        def server_close(self):
            super().server_close()
            import socket as _socket

            with self._conns_mu:
                conns = list(self._open_conns)
                self._open_conns.clear()
            for sock in conns:
                try:
                    sock.shutdown(_socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    sock.close()
                except OSError:
                    pass

    return _Server((host or "localhost", int(port or 0)), _Req)
