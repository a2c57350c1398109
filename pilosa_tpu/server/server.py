"""Server assembly: holder + executor + handler + HTTP + background
monitors (ref: server.go:55-234, server/server.go:52-249).
"""
import logging
import threading
import time

from pilosa_tpu import __version__, tracing
from pilosa_tpu import faults as faults_mod
from pilosa_tpu import qos as qos_mod
from pilosa_tpu import stats as stats_mod
from pilosa_tpu.config import DEFAULT_MAX_BODY_SIZE
from pilosa_tpu.cluster.broadcast import HTTPBroadcaster, NopBroadcaster, StaticNodeSet
from pilosa_tpu.cluster.client import InternalClient
from pilosa_tpu.cluster.cluster import Cluster, Node
from pilosa_tpu.cluster.syncer import HolderSyncer
from pilosa_tpu.executor import Executor
from pilosa_tpu.server.handler import Handler, make_http_server
from pilosa_tpu.stats import new_stats_client
from pilosa_tpu.storage.holder import Holder
from pilosa_tpu.utils import compilecache

DEFAULT_ANTI_ENTROPY_INTERVAL = 600   # 10 min (ref: server.go:44)
DEFAULT_POLLING_INTERVAL = 60         # max-slice poll (ref: server.go:321)
DEFAULT_CACHE_FLUSH_INTERVAL = 600    # (ref: holder.go:340)
DEFAULT_DRAIN_TIMEOUT = 5.0           # close()/SIGTERM in-flight wait
# How long a LEAVING node's close() waits for the in-flight resize to
# finish handing its slices off before shutting down anyway.
DEFAULT_REBALANCE_DRAIN_TIMEOUT = 30.0

_LOG = logging.getLogger("pilosa_tpu.server")


class Server:
    def __init__(self, data_dir, bind="localhost:10101", cluster_hosts=None,
                 replica_n=1, max_writes_per_request=5000,
                 anti_entropy_interval=DEFAULT_ANTI_ENTROPY_INTERVAL,
                 polling_interval=DEFAULT_POLLING_INTERVAL,
                 metric_service="expvar", metric_host="127.0.0.1:8125",
                 long_query_time=None, tls_cert=None, tls_key=None,
                 tls_skip_verify=False, host_bytes=None, workers=None,
                 trace_enabled=None, trace_slow_threshold=None,
                 trace_ring_size=None, trace_slow_ring_size=None,
                 qos=None, max_body_size=None, faults=None,
                 drain_timeout=None, metrics=None, epoch_probe_ttl=None,
                 executor=None, storage=None, ingest=None, planner=None,
                 rebalance_stream_concurrency=None,
                 rebalance_bandwidth=None,
                 rebalance_drain_timeout=None,
                 observe=None, profile=None, slo=None, mesh=None,
                 autopilot=None, hedge=None):
        compilecache.enable()  # before the first jit of this process
        self.data_dir = data_dir
        self.bind = bind
        self.host = bind
        # TLS (ref: server.go:128-134 tls.NewListener; config.go TLS
        # {certificate, key, skip-verify}).
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        self.tls_skip_verify = tls_skip_verify
        self.scheme = "https" if tls_cert else "http"
        self.holder = Holder(data_dir, host_bytes=host_bytes or None)
        self.stats = new_stats_client(metric_service, metric_host)
        self.holder.stats = self.stats

        # Distributed query tracing (tracing.py): off by default — the
        # nop tracer keeps the serving path allocation-free, the same
        # pattern as NopStatsClient. PILOSA_TRACE_ENABLED=1 or the
        # [trace] config section turns it on.
        import os as _os

        if trace_enabled is None:
            trace_enabled = _os.environ.get(
                "PILOSA_TRACE_ENABLED", "").lower() in ("1", "true", "yes")
        if trace_slow_threshold is None:
            # Mirror config.py's documented env override for direct
            # Server() construction (tests, embedding) — the CLI path
            # already resolved it through Config._apply_env.
            env_thr = _os.environ.get("PILOSA_TRACE_SLOW_THRESHOLD")
            if env_thr:
                try:
                    trace_slow_threshold = float(env_thr)
                except ValueError:
                    pass
        if trace_enabled:
            self.tracer = tracing.Tracer(
                ring_size=trace_ring_size or tracing.DEFAULT_RING_SIZE,
                slow_threshold=(trace_slow_threshold
                                if trace_slow_threshold is not None
                                else tracing.DEFAULT_SLOW_THRESHOLD),
                slow_ring_size=(trace_slow_ring_size
                                or tracing.DEFAULT_SLOW_RING_SIZE),
                stats=self.stats)
        else:
            self.tracer = tracing.NOP

        # QoS & admission control (qos.py): off by default — the nop
        # tier keeps the serving path lock- and allocation-free, the
        # same pattern as the nop tracer. ``qos`` is the [qos] config
        # table (a plain dict; Python-underscore keys accepted too for
        # direct Server() construction); PILOSA_QOS_ENABLED=1 flips it
        # on with defaults.
        qcfg = {k.replace("_", "-"): v for k, v in (qos or {}).items()}
        qos_enabled = qcfg.get("enabled")
        if qos_enabled is None:
            qos_enabled = _os.environ.get(
                "PILOSA_QOS_ENABLED", "").lower() in ("1", "true", "yes")
        if qos_enabled:
            # Only keys actually present are forwarded — defaults live
            # in ONE place (qos.QoS.__init__), so a default change
            # can't drift between the config path and direct Server()
            # construction.
            key_map = {"max-concurrent": "max_concurrent",
                       "queue-length": "queue_length",
                       "queue-timeout": "queue_timeout",
                       "default-deadline": "default_deadline",
                       "client-qps": "client_qps",
                       "client-burst": "client_burst",
                       "quotas": "client_overrides",
                       "breaker-threshold": "breaker_threshold",
                       "breaker-cooldown": "breaker_cooldown"}
            self.qos = qos_mod.QoS(**{
                py: qcfg[k] for k, py in key_map.items() if k in qcfg})
        else:
            self.qos = qos_mod.NOP
        self.max_body_size = (max_body_size if max_body_size is not None
                              else int(_os.environ.get(
                                  "PILOSA_MAX_BODY_SIZE",
                                  DEFAULT_MAX_BODY_SIZE)))

        # Runtime telemetry ([metrics] config table): tagged histogram
        # families on /metrics, the process-telemetry collector, and
        # /cluster/metrics aggregation. Histograms default ON (an
        # observation is a bisect + three integer adds); disabling
        # restores the single-nop-attribute-read hot path — same
        # discipline as qos.NOP/faults, verified by test.
        mcfg = {k.replace("_", "-"): v for k, v in (metrics or {}).items()}
        hist_on = mcfg.get("histograms")
        if hist_on is None:
            env_h = _os.environ.get("PILOSA_METRICS_HISTOGRAMS")
            hist_on = (env_h.lower() in ("1", "true", "yes")
                       if env_h else True)
        if hist_on:
            self.histograms = stats_mod.HistogramSet(
                mcfg.get("histogram-buckets") or None)
        else:
            self.histograms = stats_mod.NOP_HISTOGRAMS
        collector = mcfg.get("collector-interval")
        if collector is None:
            collector = int(_os.environ.get(
                "PILOSA_METRICS_COLLECTOR_INTERVAL", "10"))
        self.collector_interval = int(collector)
        self.cluster_metrics_enabled = bool(
            mcfg.get("cluster-aggregation", True))
        # Monotonic: feeds uptime_seconds (a duration) via
        # stats.process_telemetry — never wall clock.
        self._started_at = time.monotonic()

        # Workload observatory ([observe] config table): kernel-cost
        # attribution + slice/row heatmaps, always-on by default.
        # kerneltime/heatmap are PROCESS-GLOBAL like the kernels they
        # instrument (see observe/__init__.py): installed only FOR a
        # real enable, so a later observe-disabled server in the same
        # process never downgrades an enabled one (the
        # set_dispatch_histogram discipline).
        from pilosa_tpu.observe import heatmap as heatmap_mod
        from pilosa_tpu.observe import kerneltime as kerneltime_mod
        from pilosa_tpu.observe import slo as slo_mod

        ocfg = {k.replace("_", "-"): v for k, v in (observe or {}).items()}
        observe_enabled = ocfg.get("enabled")
        if observe_enabled is None:
            env_o = _os.environ.get("PILOSA_OBSERVE_ENABLED")
            observe_enabled = (env_o.lower() in ("1", "true", "yes")
                               if env_o else True)
        self.observe_enabled = bool(observe_enabled)
        if self.observe_enabled:
            rate = ocfg.get("kernel-sample-rate")
            if rate is None:
                try:
                    rate = int(_os.environ.get(
                        "PILOSA_OBSERVE_KERNEL_SAMPLE_RATE", "0"))
                except ValueError:
                    rate = 0
            kerneltime_mod.enable(sample_rate=max(0, int(rate)))
            heatmap_mod.enable(
                half_life=float(ocfg.get("heatmap-half-life",
                                         heatmap_mod.DEFAULT_HALF_LIFE)),
                top_k=int(ocfg.get("heatmap-top-k",
                                   heatmap_mod.DEFAULT_TOP_K)))
            # Measured cost model (PR 15 query inspector): enabled
            # with the observatory — the kerneltime cells ARE its
            # measurement source. Predicted-vs-measured error ratios
            # ride the cost_model_error histogram family when
            # histograms are on.
            from pilosa_tpu.observe import costmodel as costmodel_mod

            cm = costmodel_mod.enable()
            if self.histograms.enabled:
                cm.set_histogram(self.histograms.histogram(
                    "cost_model_error",
                    buckets=(0.125, 0.25, 0.5, 0.8, 1.0, 1.25,
                             2.0, 4.0, 8.0)))
            # Analytic device-kernel attribution (observe/devprof.py):
            # enabled with the observatory — its captures fold into
            # the kerneltime cells and the cost model's fallbacks.
            from pilosa_tpu.observe import devprof as devprof_mod

            devprof_mod.enable()

        # Continuous profiler ([profile] config table): always-on
        # stack sampler, process-global like kerneltime (one sampler
        # thread serves every in-process server; sys._current_frames
        # is process-wide anyway). sample-hz 0 = off; a later
        # profile-disabled server never downgrades an enabled one.
        from pilosa_tpu.observe import profiler as profiler_mod

        pcfg = {k.replace("_", "-"): v for k, v in (profile or {}).items()}
        hz = pcfg.get("sample-hz")
        if hz is None:
            try:
                hz = float(_os.environ.get(
                    "PILOSA_PROFILE_SAMPLE_HZ",
                    profiler_mod.DEFAULT_HZ))
            except ValueError:
                hz = profiler_mod.DEFAULT_HZ
        if float(hz) > 0:
            profiler_mod.enable(sample_hz=float(hz))
        self.profile_trace_dir = str(
            pcfg.get("device-trace-dir")
            or _os.environ.get("PILOSA_PROFILE_DEVICE_TRACE_DIR", "")
            or "")

        # SLO tracker ([slo] config table): per-server (it is fed
        # only by this server's handler), advisory-only.
        slo_cfg = {k.replace("_", "-"): v for k, v in (slo or {}).items()}
        slo_enabled = slo_cfg.get("enabled")
        if slo_enabled is None:
            env_se = _os.environ.get("PILOSA_SLO_ENABLED")
            if env_se:
                slo_enabled = env_se.lower() in ("1", "true", "yes")
            else:
                # Declared objectives imply enabling — the same rule
                # as Config._apply_env, so the CLI and embedded
                # construction paths agree under identical env.
                slo_enabled = bool(
                    _os.environ.get("PILOSA_SLO_OBJECTIVES"))
        if slo_enabled:
            objectives = None
            if slo_cfg.get("objectives"):
                objectives = slo_mod.normalize_objectives(
                    slo_cfg["objectives"])
            elif _os.environ.get("PILOSA_SLO_OBJECTIVES"):
                objectives = slo_mod.parse_objectives(
                    _os.environ["PILOSA_SLO_OBJECTIVES"])
            self.slo = slo_mod.SLOTracker(objectives)
        else:
            self.slo = slo_mod.NOP

        # Fault injection ([faults] config table): the PILOSA_FAULTS
        # env is read once at faults-module import; the config path
        # installs/extends the same process-global registry (an
        # in-process ServerCluster shares it by design — see
        # faults.py). Off by default: injection sites cost one
        # attribute read on the shared nop object.
        fcfg = {k.replace("_", "-"): v for k, v in (faults or {}).items()}
        if fcfg.get("enabled"):
            faults_mod.enable(fcfg.get("spec") or None)
        # Graceful drain budget for close()/SIGTERM: how long in-flight
        # queries get to finish after the node flips to LEAVING.
        if drain_timeout is None:
            env_dt = _os.environ.get("PILOSA_DRAIN_TIMEOUT")
            drain_timeout = float(env_dt) if env_dt \
                else DEFAULT_DRAIN_TIMEOUT
        self.drain_timeout = float(drain_timeout)

        hosts = cluster_hosts or [bind]
        self.cluster = Cluster(
            nodes=[Node(h, scheme=self.scheme) for h in hosts],
            replica_n=replica_n,
            max_writes_per_request=max_writes_per_request,
            long_query_time=long_query_time)
        # Distributed mutation epochs (cluster/epochs.py): assigned
        # below for multi-node; None keeps the single-node hot paths
        # and wire format byte-identical to before.
        self.epochs = None
        if len(hosts) > 1:
            # Heartbeat membership with failure detection; a recovered
            # peer gets a schema push (the gossip state-exchange analog).
            from pilosa_tpu.cluster.membership import HTTPNodeSet

            self.cluster.node_set = HTTPNodeSet(
                self.cluster, bind,
                InternalClient(timeout=5, skip_verify=tls_skip_verify),
                on_rejoin=self._on_peer_rejoin,
                # Heartbeat piggyback: schema/max-slice/epoch state
                # rides every probe both directions, making the 60 s
                # max-slice poll a backstop rather than the mechanism.
                status_fn=self._heartbeat_status,
                merge_fn=self._merge_peer_status)
        else:
            self.cluster.node_set = StaticNodeSet(self.cluster.nodes)

        self.client = InternalClient(skip_verify=tls_skip_verify,
                                     breakers=self.qos.breakers)
        if len(hosts) > 1:
            from pilosa_tpu.cluster.epochs import (
                ClusterEpochs, DEFAULT_PROBE_TTL)

            if epoch_probe_ttl is None:
                env_ttl = _os.environ.get("PILOSA_EPOCH_PROBE_TTL")
                if env_ttl:
                    try:
                        epoch_probe_ttl = float(env_ttl)
                    except ValueError:
                        pass
            # 0/None = one heartbeat interval (the registry stays
            # fresh for free off the membership probes).
            ttl = float(epoch_probe_ttl or 0) or DEFAULT_PROBE_TTL
            self.epochs = ClusterEpochs(
                self.host, self.holder, cluster=self.cluster,
                client=self.client, ttl=ttl)
            # The internal client feeds every RPC response's piggyback
            # header into the registry — a relayed write's ack carries
            # the replica's bumped counter back inline.
            self.client.epochs = self.epochs
        # Shared breaker registry: the client records transport
        # outcomes, the executor/cluster consult state up front when
        # mapping slices, /status surfaces it.
        self.cluster.breakers = self.qos.breakers
        # Elastic topology (cluster/placement.py + rebalancer.py):
        # versioned slice placement with an online background migrator,
        # multi-node only — a single-node server has nothing to
        # stream and no broadcast plane to commit over.
        self.rebalancer = None
        if len(hosts) > 1:
            from pilosa_tpu.cluster.rebalancer import Rebalancer

            if rebalance_stream_concurrency is None:
                rebalance_stream_concurrency = int(_os.environ.get(
                    "PILOSA_REBALANCE_STREAM_CONCURRENCY", "2"))
            if rebalance_bandwidth is None:
                rebalance_bandwidth = int(_os.environ.get(
                    "PILOSA_REBALANCE_BANDWIDTH", "0"))
            self.rebalancer = Rebalancer(
                self.holder, self.cluster, self.host, self.client,
                stream_concurrency=rebalance_stream_concurrency,
                bandwidth=rebalance_bandwidth,
                tracer=self.tracer, stats=self.stats,
                pending_hints_fn=lambda: (
                    self.executor.pending_hint_hosts()))
        if rebalance_drain_timeout is None:
            env_rdt = _os.environ.get("PILOSA_REBALANCE_DRAIN_TIMEOUT")
            rebalance_drain_timeout = float(env_rdt) if env_rdt \
                else DEFAULT_REBALANCE_DRAIN_TIMEOUT
        self.rebalance_drain_timeout = float(rebalance_drain_timeout)

        # Control-plane flight recorder + per-replica vitals ([observe]
        # events/vitals keys, observe/events.py + observe/replica.py):
        # per-server like the SLO tracker — an in-process test cluster
        # must attribute each transition to the node that observed it.
        # Both default to the observatory switch; emitting subsystems
        # hold ``events = None`` when off (one attribute read).
        from pilosa_tpu.observe import events as events_mod
        from pilosa_tpu.observe import replica as replica_mod

        ev_on = ocfg.get("events")
        if ev_on is None:
            env_ev = _os.environ.get("PILOSA_OBSERVE_EVENTS")
            ev_on = (env_ev.lower() in ("1", "true", "yes")
                     if env_ev else self.observe_enabled)
        vt_on = ocfg.get("vitals")
        if vt_on is None:
            env_vt = _os.environ.get("PILOSA_OBSERVE_VITALS")
            vt_on = (env_vt.lower() in ("1", "true", "yes")
                     if env_vt else self.observe_enabled)
        if ev_on:
            pl = self.cluster.placement
            self.events = events_mod.EventRecorder(
                host=self.host,
                ring_size=int(ocfg.get("events-ring",
                                       events_mod.DEFAULT_RING)),
                gen_fn=lambda: pl.generation,
                sink_path=ocfg.get("events-sink") or None)
        else:
            self.events = events_mod.NOP
        self.vitals = replica_mod.NOP
        if vt_on:
            self.vitals = replica_mod.ReplicaVitals(
                window=float(ocfg.get("vitals-window", 30.0)),
                watchdog_factor=float(ocfg.get("watchdog-factor", 3.0)),
                watchdog_min=float(
                    ocfg.get("watchdog-min-ms", 50.0)) / 1e3)
            self.vitals.epochs = self.epochs
            self.client.vitals = self.vitals
        if self.events.enabled:
            rec = self.events
            self.cluster.placement.events = rec
            if self.qos.enabled:
                self.qos.events = rec
                self.qos.breakers.events = rec
            ns = self.cluster.node_set
            if hasattr(ns, "events"):   # HTTPNodeSet (multi-node only)
                ns.events = rec
            if self.epochs is not None:
                self.epochs.events = rec
            if self.rebalancer is not None:
                self.rebalancer.events = rec
            if self.slo.enabled:
                self.slo.events = rec
            if faults_mod.ACTIVE.enabled:
                # Process-global registry: in-process clusters journal
                # arm/clear on whichever server wired last — same
                # last-enable-wins contract as kerneltime/heatmap.
                faults_mod.ACTIVE.events = rec
            self.holder.events = rec
            self.holder.governor.events = rec
            if self.vitals.enabled:
                self.vitals.events = rec

        self.executor = Executor(
            self.holder, cluster=self.cluster, host=self.host,
            client=self.client,
            max_writes_per_request=max_writes_per_request)
        # Result-memo validity on clusters: the executor keys its
        # whole-result memos on the epoch vector of the owning nodes.
        self.executor.epochs = self.epochs
        # [executor] config table: the slice-plan cache entry budget
        # (plancache.py). The PlanCache constructor already honored
        # PILOSA_PLAN_CACHE_ENTRIES for bare construction; an explicit
        # config value wins (0 = off).
        ecfg = {k.replace("_", "-"): v for k, v in (executor or {}).items()}
        if ecfg.get("plan-cache-entries") is not None:
            self.executor.plans.set_capacity(
                int(ecfg["plan-cache-entries"]))
        # Cross-query micro-batching tick knobs. The executor resolves
        # PILOSA_COALESCE_* env itself for bare construction; explicit
        # config values win here (config.py already folded env into
        # them with env-over-file precedence).
        if any(ecfg.get(k) is not None for k in (
                "coalesce-max-wait-us", "coalesce-max-group",
                "coalesce-compressed", "coalesce-densify-bytes")):
            self.executor.set_coalesce_config(
                max_wait_us=ecfg.get("coalesce-max-wait-us"),
                max_group=ecfg.get("coalesce-max-group"),
                compressed=ecfg.get("coalesce-compressed"),
                densify_bytes=ecfg.get("coalesce-densify-bytes"))
        # [planner] config table: the adaptive cost-based planner
        # (planner.py). The Planner resolves PILOSA_PLANNER_* env
        # itself at construction for bare Executors; explicit config
        # values win here (config.py already folded env into them with
        # env-over-file precedence).
        pcfg = {k.replace("_", "-"): v for k, v in (planner or {}).items()}
        if pcfg:
            self.executor.planner.set_config(
                enabled=pcfg.get("enabled"),
                reorder=pcfg.get("reorder"),
                short_circuit=pcfg.get("short-circuit"),
                tier_select=pcfg.get("tier-select"),
                explore_stride=pcfg.get("explore-stride"))
        # [storage] config table: the compressed container tier
        # (ops/containers.py). The module read PILOSA_CONTAINER_FORMATS
        # at import for bare construction; an explicit config value
        # wins. Process-global like the kernels themselves — in-process
        # test clusters share one tier.
        scfg = {k.replace("_", "-"): v for k, v in (storage or {}).items()}
        if scfg.get("container-formats") is not None:
            from pilosa_tpu.ops import containers as containers_mod

            containers_mod.set_enabled(bool(scfg["container-formats"]))

        # Streaming bulk-ingest pipeline (ingest/pipeline.py): the
        # [ingest] config table. Default ON — disabling answers 501 on
        # the route and removes the pilosa_ingest_* metrics group.
        icfg = {k.replace("_", "-"): v for k, v in (ingest or {}).items()}
        ingest_enabled = icfg.get("enabled")
        if ingest_enabled is None:
            env_ie = _os.environ.get("PILOSA_INGEST_ENABLED")
            ingest_enabled = (env_ie.lower() in ("1", "true", "yes")
                              if env_ie else True)
        self.ingest = None
        if ingest_enabled:
            from pilosa_tpu.ingest import IngestPipeline
            from pilosa_tpu.ingest.pipeline import DEFAULT_MAX_BATCH_BITS

            max_batch_bits = icfg.get("max-batch-bits")
            if max_batch_bits is None:
                env_mb = _os.environ.get("PILOSA_INGEST_MAX_BATCH_BITS")
                if env_mb:
                    try:
                        max_batch_bits = int(env_mb)
                    except ValueError:
                        pass
            self.ingest = IngestPipeline(
                self.holder, cluster=self.cluster, client=self.client,
                max_batch_bits=max_batch_bits or DEFAULT_MAX_BATCH_BITS,
                stats=self.stats, tracer=self.tracer)

        # Collective data plane ([mesh] config table,
        # cluster/meshplane.py): within a mesh peer group — one JAX
        # process group sharing one device set — multi-node queries
        # compile to one shard_map + psum program instead of HTTP
        # fan-out. Off by default: it is a topology claim, not a
        # tuning knob. Constructed even single-node so the
        # pilosa_mesh_* metrics group and /debug/mesh are live
        # wherever the config says the plane is on.
        mshcfg = {k.replace("_", "-"): v for k, v in (mesh or {}).items()}
        mesh_enabled = mshcfg.get("enabled")
        if mesh_enabled is None:
            mesh_enabled = _os.environ.get(
                "PILOSA_MESH_ENABLED", "").lower() in ("1", "true",
                                                       "yes")
        self.meshplane = None
        if mesh_enabled:
            from pilosa_tpu.cluster.meshplane import (
                DEFAULT_STACK_BYTES, MeshPlane)

            group = mshcfg.get("group")
            if not group:
                group = _os.environ.get("PILOSA_MESH_GROUP") or None
            stack_bytes = mshcfg.get("stack-bytes")
            if stack_bytes is None:
                env_sb = _os.environ.get("PILOSA_MESH_STACK_BYTES")
                if env_sb:
                    try:
                        stack_bytes = int(env_sb)
                    except ValueError:
                        pass
            self.meshplane = MeshPlane(
                self.holder, self.cluster, self.host,
                group=group or None,
                stack_bytes=stack_bytes or DEFAULT_STACK_BYTES)
            self.meshplane.register()
            self.executor.meshplane = self.meshplane

        # Histogram wiring: executor latency + fan-out rounds, internal
        # client round trips, admission queue-wait, and per-kernel
        # dispatch time. The kernel hook is module-level (bitops) —
        # installed only for a REAL set, so a later nop-configured
        # server in the same process never downgrades an enabled one.
        self.executor.set_histograms(self.histograms)
        if self.ingest is not None and self.histograms.enabled:
            self.ingest.set_histograms(self.histograms)
        if self.histograms.enabled:
            self.client.set_histogram(
                self.histograms.histogram("client_request_seconds"))
            self.qos.set_histograms(self.histograms)
            from pilosa_tpu.ops import bitops

            bitops.set_dispatch_histogram(
                self.histograms.histogram("kernel_dispatch_seconds"))

        if len(self.cluster.nodes) > 1:
            self.broadcaster = HTTPBroadcaster(self.client, self.cluster,
                                               self.host)
        else:
            self.broadcaster = NopBroadcaster()

        # Heat-driven autopilot ([autopilot] config table,
        # autopilot/controller.py): the closed-loop controller that
        # operates the cluster itself. OFF by default — it is an
        # authority claim, not a tuning knob. Constructed after every
        # sensor/actuator it reads so the wiring below is one
        # straight-line install; NOP when disabled (the qos/tracer
        # pattern: one attribute read on every surface).
        from pilosa_tpu import autopilot as autopilot_mod

        apcfg = {k.replace("_", "-"): v
                 for k, v in (autopilot or {}).items()}
        ap_enabled = apcfg.get("enabled")
        if ap_enabled is None:
            ap_enabled = _os.environ.get(
                "PILOSA_AUTOPILOT_ENABLED", "").lower() in (
                    "1", "true", "yes")
        if ap_enabled:
            ap_key_map = {"interval": "interval",
                          "dry-run": "dry_run",
                          "placement": "placement_loop",
                          "memory": "memory_loop",
                          "slo": "slo_loop",
                          "min-dwell": "min_dwell",
                          "max-actions-per-window":
                              "max_actions_per_window",
                          "window": "window",
                          "heat-imbalance": "heat_imbalance",
                          "memory-headroom": "memory_headroom"}
            self.autopilot = autopilot_mod.Autopilot(
                local_host=self.host, **{
                    py: apcfg[k] for k, py in ap_key_map.items()
                    if k in apcfg})
            # Sensors + actuators: every one an EXISTING surface — the
            # autopilot adds no new mutation paths, it drives the same
            # levers an operator does.
            ap = self.autopilot
            ap.cluster = self.cluster
            ap.rebalancer = self.rebalancer
            ap.client = self.client
            ap.governor = self.holder.governor
            if self.qos.enabled:
                ap.qos = self.qos
            if self.vitals.enabled:
                ap.vitals = self.vitals
            if self.slo.enabled:
                ap.slo = self.slo
            if heatmap_mod.ACTIVE.enabled:
                ap.heat_fn = heatmap_mod.ACTIVE.snapshot
            if self.events.enabled:
                ap.events = self.events
        else:
            self.autopilot = autopilot_mod.NOP

        # Tail-tolerant reads ([cluster] hedge-* / replica-routing
        # keys, cluster/hedge.py): replica-aware routing + hedged
        # fan-out. OFF by default — the executor holds ``hedger =
        # None`` and the preferred-owner fan-out path is
        # byte-identical to pre-hedging behavior. Constructed after
        # vitals/qos/epochs/events so the wiring below is one
        # straight-line install (the autopilot pattern).
        from pilosa_tpu.cluster import hedge as hedge_mod

        hcfg = {k.replace("_", "-"): v for k, v in (hedge or {}).items()}
        if not hcfg:
            # Direct Server() construction (tests, embedding): mirror
            # config.py's documented PILOSA_HEDGE_* env overrides.
            hcfg = hedge_mod.env_config(_os.environ)
        if hcfg.get("hedge-reads") or hcfg.get("replica-routing"):
            self.hedger = hedge_mod.Hedger(hcfg)
            hg = self.hedger
            hg.local_host = self.host
            hg.epochs = self.epochs
            if self.vitals.enabled:
                hg.vitals = self.vitals
            if self.qos.enabled:
                hg.qos = self.qos
                hg.breakers = self.qos.breakers
            if self.events.enabled:
                hg.events = self.events
            self.executor.hedger = hg
        else:
            self.hedger = hedge_mod.NOP

        self.holder.broadcaster = self.broadcaster
        self.handler = Handler(self.holder, self.executor,
                               cluster=self.cluster,
                               broadcaster=self.broadcaster,
                               local_host=self.host, version=__version__,
                               tracer=self.tracer, qos=self.qos,
                               histograms=self.histograms,
                               epochs=self.epochs,
                               rebalancer=self.rebalancer,
                               ingest=self.ingest,
                               slo=self.slo,
                               events=self.events,
                               vitals=self.vitals,
                               autopilot=self.autopilot,
                               hedger=self.hedger,
                               device_trace_dir=self.profile_trace_dir)
        if self.rebalancer is not None and self.histograms.enabled:
            # pilosa_rebalance_stream_seconds{peer=...} — per-peer
            # migration stream durations.
            self.rebalancer.set_histogram(
                self.histograms.histogram("rebalance_stream_seconds"))
        self.handler.cluster_metrics_enabled = self.cluster_metrics_enabled
        self.syncer = HolderSyncer(self.holder, self.cluster, self.host,
                                   self.client)
        self.anti_entropy_interval = anti_entropy_interval
        self.polling_interval = polling_interval

        # Worker frontend processes (ref: goroutine-per-conn serving,
        # server.go:205-217; see server/workers.py for the design).
        import os as _os

        if workers is None:
            workers = int(_os.environ.get("PILOSA_TPU_WORKERS", "0"))
        self.workers = workers
        self.worker_pool = None
        self.plan_server = None

        self._httpd = None
        self._threads = []
        self._closing = threading.Event()

    # ------------------------------------------------------------ lifecycle

    def open(self):
        """(ref: Server.Open server.go:123-234)."""
        # Say what this process computes on before serving anything: a
        # CPU fallback must never pass for a chip (also /debug/vars).
        _LOG.info("device: %s", stats_mod.device_telemetry())
        self.holder.open()
        self._load_path_model()
        # Master response replay on EVERY topology: single-node
        # validates on the in-process epoch, multi-node on the
        # distributed epoch vector (cluster/epochs.py) — unknown or
        # stale peers mean cold, never stale.
        self.handler.enable_response_cache()
        self._httpd = make_http_server(self.handler, self.bind,
                                       reuse_port=self.workers > 0,
                                       max_body_size=self.max_body_size)
        if self.tls_cert:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.tls_cert, self.tls_key or None)
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True)
        port = self._httpd.server_address[1]
        host = self.bind.rsplit(":", 1)[0]
        self.host = f"{host}:{port}"
        self.handler.local_host = self.host
        self.executor.host = self.host
        if self.epochs is not None:
            self.epochs.local_host = self.host  # ":0" bind resolved
        # Re-point our own node entry at the real bound port (":0" case).
        node = self.cluster.node_by_host(self.bind)
        if node is not None:
            node.host = self.host
            self.cluster.topology_version += 1  # ownership cache epoch
            # Placement host lists must track the reachable name too.
            self.cluster.placement.rename_host(self.bind, self.host)
        if self.rebalancer is not None:
            self.rebalancer.local_host = self.host
        if self.autopilot.enabled:
            self.autopilot.local_host = self.host
        if self.meshplane is not None:
            self.meshplane.set_local_host(self.host)
        # The journal's host stamp must be the reachable name (":0"
        # binds resolve only here), so re-point it before the first
        # event a peer could ever merge.
        if self.events.enabled:
            self.events.host = self.host
            self.events.emit("server.start", bind=self.bind,
                             version=__version__)

        # Named for the profiler's serving seam (request threads get
        # Python's own "(process_request_thread)" suffix).
        t = threading.Thread(target=self._httpd.serve_forever,
                             daemon=True, name="http-serve")
        t.start()
        self._threads.append(t)

        if self.workers > 0:
            import os as _os

            from pilosa_tpu.server.workers import PlanServer, WorkerPool
            from pilosa_tpu.storage import fragment as fragment_mod

            # Unix socket paths cap at ~108 bytes; keep it short and
            # unique rather than inside a (possibly deep) data dir.
            # A freshly-created 0700 directory (not a predictable
            # world-writable /tmp name) means no other local user can
            # pre-plant an entry at the socket path or connect during
            # the bind window — the plan socket's dispatch surface is
            # reachable only by this uid.
            import tempfile

            self._plan_dir = tempfile.mkdtemp(prefix="pilosa_plan_")
            sock = _os.path.join(self._plan_dir, "plan.sock")
            if len(sock) > 100:  # deep $TMPDIR would overflow sun_path
                import shutil

                shutil.rmtree(self._plan_dir, ignore_errors=True)
                self._plan_dir = tempfile.mkdtemp(prefix="pilosa_plan_",
                                                  dir="/tmp")
                sock = _os.path.join(self._plan_dir, "plan.sock")
            self.plan_server = PlanServer(self.handler.dispatch,
                                          sock).open()
            # Worker-local read execution: default ON for the CPU
            # backend (each worker's replica executes on its own GIL —
            # the goroutine-across-cores analog) and OFF on an
            # accelerator, where the master's device does the math and
            # workers only shed the HTTP transport.
            exec_env = _os.environ.get("PILOSA_TPU_WORKER_EXEC")
            if exec_env is not None:
                exec_reads = exec_env == "1"
            else:
                import jax

                exec_reads = jax.default_backend() == "cpu"
            # SINGLE-NODE GATE for worker-local execution only: the
            # worker replica's executor has no cluster — on a
            # multi-node cluster local execution would return partial
            # (local-slice-only) results. The worker RESPONSE CACHE
            # runs on every topology: single-node it validates on the
            # published local epoch (word 0); multi-node it also
            # requires the published cluster epoch version (word 1,
            # fed by the epoch registry — 0 means cold, so a peer
            # visibility lapse degrades workers to relay, never to
            # stale replay).
            single_node = len(self.cluster.nodes) <= 1
            exec_reads = exec_reads and single_node
            fragment_mod.publish_epochs(
                _os.path.join(self.data_dir, ".mutation_epoch"))
            if self.epochs is not None:
                # Synchronous word-1 publication on every observed
                # change + a staleness monitor that flips it to 0
                # (cold) when a peer stops answering.
                self.epochs.attach_worker_publisher(
                    fragment_mod.publish_cluster_version)
                self._spawn(self._monitor_worker_epochs,
                            max(0.5, self.epochs.ttl / 2))
            self.worker_pool = WorkerPool(
                self.workers, self.host, sock,
                tls_cert=self.tls_cert, tls_key=self.tls_key,
                data_dir=self.data_dir,
                exec_reads=exec_reads,
                cluster_epochs=not single_node,
                trace_enabled=self.tracer.enabled,
                max_body_size=self.max_body_size,
                qos_active=self.qos.enabled,
                plan_cache_entries=self.executor.plans.capacity).open()

        from pilosa_tpu.cluster.membership import HTTPNodeSet

        if isinstance(self.cluster.node_set, HTTPNodeSet):
            self.cluster.node_set.local_host = self.host
            self.cluster.node_set.open()

        # Background monitors (ref: server.go:227-232).
        if self.anti_entropy_interval and len(self.cluster.nodes) > 1:
            self._spawn(self._monitor_anti_entropy,
                        self.anti_entropy_interval)
        if self.polling_interval and len(self.cluster.nodes) > 1:
            self._spawn(self._monitor_max_slices, self.polling_interval)
        self._spawn(self._monitor_cache_flush, DEFAULT_CACHE_FLUSH_INTERVAL)
        if self.collector_interval > 0:
            self._spawn(self._monitor_runtime, self.collector_interval)
        if self.autopilot.enabled and self.autopilot.interval > 0:
            # The control loop rides the monitor harness: crashes log
            # + count but never kill the thread, and the kill switch
            # (autopilot.disable()) makes every subsequent tick a
            # no-op even before close() stops the loop.
            self._spawn(self.autopilot.tick, self.autopilot.interval)
        return self

    def _heartbeat_status(self):
        """Compact NodeStatus for the membership probe piggyback:
        schema/max-slices from the holder plus (multi-node) this
        node's mutation-epoch counters."""
        st = self.holder.node_status_compact(self.host)
        if self.epochs is not None:
            from pilosa_tpu.cluster import epochs as epochs_mod

            st["epochs"] = epochs_mod.local_epochs(self.holder)
        if self.cluster.placement.active:
            # Placement convergence backstop: a peer that missed a
            # resize broadcast (rebalance.commit.partial, a transient
            # partition) learns the newest placement state within one
            # probe interval; the seq guard makes re-application a
            # no-op.
            st["placement"] = self.cluster.placement.wire_state()
        return st

    def _merge_peer_status(self, st):
        """Apply a heartbeat reply: epoch observation first (it must
        never be lost to a schema-merge hiccup), then placement
        convergence, then the holder's create-only schema/max-slice
        merge."""
        if self.epochs is not None and isinstance(
                st.get("epochs"), dict) and st.get("host"):
            self.epochs.observe(st["host"], st["epochs"])
        if self.rebalancer is not None:
            self.rebalancer.merge_placement(st)
        self.holder.merge_remote_status(st)

    def _on_peer_rejoin(self, node):
        """Reconcile a recovered peer: push full schema (options+fields)
        and replay writes hinted while it was down (the reference's
        gossip MergeRemoteState analog + hinted handoff)."""
        self.client.post_schema(node, self.holder.schema(include_meta=True))
        self.executor.replay_hints(node, self.client)

    def close(self):
        """Graceful drain, then teardown: announce LEAVING (new
        serving work sheds 503 + Retry-After, /status flips so peers
        stop routing here), wait up to ``drain_timeout`` for in-flight
        queries — whose op-log writes flush synchronously inside them
        — then close for real (the existing hard teardown, which also
        severs any straggler the deadline abandoned)."""
        first = not self._closing.is_set()
        self._closing.set()
        # Autopilot stands down FIRST: the kill switch makes any
        # mid-flight tick abort before its actuator call, so shutdown
        # never races a controller-initiated resize.
        self.autopilot.close()
        if first and self.meshplane is not None:
            # Leave the mesh peer group BEFORE draining: peers must
            # stop staging collective reads against this holder while
            # it can still serve their HTTP fallbacks.
            self.meshplane.close()
        if (first and self.rebalancer is not None
                and self.cluster.placement.is_leaving(self.host)):
            # A LEAVING node exits only after the resize that removes
            # it finishes handing its slices off (commit + cleanup —
            # every fragment has a verified copy on its new owner), up
            # to the rebalance drain budget. The handler keeps serving
            # migration reads meanwhile; the regular drain below then
            # sheds what remains.
            done = self.rebalancer.wait_handoff(
                self.rebalance_drain_timeout)
            if not done:
                self.stats.count("rebalance_handoff_timeout_total", 1)
                _LOG.warning(
                    "leaving node shutting down before handoff "
                    "completed (waited %.1fs); anti-entropy on the "
                    "surviving replicas is the backstop",
                    self.rebalance_drain_timeout)
        if first and self._httpd is not None:
            self.events.emit("drain.begin",
                             timeoutSeconds=self.drain_timeout)
            waited, drained, left = self.handler.drain(self.drain_timeout)
            self.events.emit("drain.end", waitedSeconds=round(waited, 3),
                             drained=drained, inflight=left)
            self.stats.timing("drain_duration_seconds", waited)
            if not drained:
                self.stats.count("drain_timeout_total", 1)
                _LOG.warning(
                    "drain timeout after %.3fs: %d request(s) still in "
                    "flight, closing anyway", waited, left)
        if first:
            self.events.emit("server.stop")
            # A device capture is the process's: stop an armed one and
            # wait for one that is being written, or the interpreter
            # exits under a profiler that is tearing down (exit -6).
            from pilosa_tpu.observe import devprof as devprof_mod

            if not devprof_mod.ACTIVE.finish_capture():
                _LOG.warning("device capture still being written "
                             "after %.0fs; closing anyway",
                             devprof_mod.FINISH_TIMEOUT)
        self._save_path_model()  # learned minima survive the restart
        if self.worker_pool is not None:
            self.worker_pool.close()
        if self.plan_server is not None:
            self.plan_server.close()
            import shutil

            shutil.rmtree(getattr(self, "_plan_dir", ""),
                          ignore_errors=True)
        if self.cluster.node_set is not None:
            self.cluster.node_set.close()
        if hasattr(self.broadcaster, "close"):
            self.broadcaster.close()
        self.syncer.close()
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
        # Fan-out thread pools: the executor's map/reduce pool and the
        # epoch registry's probe pool park daemon threads — release
        # them so long-lived processes churning servers (tests) don't
        # accumulate parked workers.
        self.executor.close()
        if self.ingest is not None:
            self.ingest.close()
        if self.epochs is not None:
            self.epochs.close()
        if self.rebalancer is not None:
            self.rebalancer.close()
        # Drop pooled keep-alive sockets (self.client is shared by the
        # executor, syncer, and broadcaster; the node set holds its
        # own probing client) — a closed server must not keep idle
        # connections parked against peers.
        self.client.close()
        ns_client = getattr(self.cluster.node_set, "client", None)
        if ns_client is not None and hasattr(ns_client, "close"):
            ns_client.close()
        self.holder.close()

    def _spawn(self, fn, interval):
        name = fn.__name__.lstrip("_").replace("monitor_", "")
        stats = self.stats.with_tags(f"monitor:{name}")

        def loop():
            while not self._closing.wait(interval):
                try:
                    fn()
                except Exception:  # noqa: BLE001 — monitors must not die
                    # ...but they must not die SILENTLY either: a
                    # permanently-crashing monitor (anti-entropy that
                    # can never finish, say) used to be invisible.
                    _LOG.warning("monitor %s crashed (will run again "
                                 "next interval)", name, exc_info=True)
                    stats.count("monitor_errors_total", 1)

        # bg- prefix: the continuous profiler's thread-name seam for
        # the background subsystem (observe/profiler.py).
        t = threading.Thread(target=loop, daemon=True,
                             name=f"bg-{name}")
        t.start()
        self._threads.append(t)

    # ------------------------------------------------------------- monitors

    def _monitor_worker_epochs(self):
        """Keep the worker-published cluster epoch honest: probe stale
        peers off the serving path; publish 0 (= cold) when any peer
        stays unreachable so worker caches degrade to relay."""
        self.epochs.publish_for_workers(probe=True)

    def _monitor_anti_entropy(self):
        """(ref: monitorAntiEntropy server.go:281-319)."""
        import time
        t0 = time.perf_counter()
        self.stats.count("AntiEntropy", 1)
        self.syncer.sync_holder()
        self.stats.timing("AntiEntropyDuration", time.perf_counter() - t0)

    def _monitor_max_slices(self):
        """Poll peers' max slices (ref: monitorMaxSlices server.go:321-357)."""
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            try:
                for index, max_slice in self.client.max_slices(node).items():
                    idx = self.holder.index(index)
                    if idx is not None:
                        idx.set_remote_max_slice(max_slice)
                for index, max_slice in self.client.max_slices(
                        node, inverse=True).items():
                    idx = self.holder.index(index)
                    if idx is not None:
                        idx.set_remote_max_inverse_slice(max_slice)
            except Exception:  # noqa: BLE001 — peer may be down; pilint: disable=swallow
                continue

    PATH_MODEL_FILE = ".path_model.json"

    def _path_model_path(self):
        import os as _os

        return _os.path.join(self.data_dir, self.PATH_MODEL_FILE)

    def _load_path_model(self):
        """Warm-start the executor's batched-vs-serial model from the
        previous process's learned minima (best-effort)."""
        import json as _json

        try:
            with open(self._path_model_path()) as f:
                self.executor.load_path_model(_json.load(f))
        except (OSError, ValueError):
            pass

    def _save_path_model(self):
        import json as _json
        import os as _os

        try:
            path = self._path_model_path()
            # Unique tmp per call: the flush monitor and close() can
            # save concurrently; a shared tmp name would interleave
            # their writes and install garbled JSON.
            tmp = f"{path}.{_os.getpid()}.{threading.get_ident()}.tmp"
            with open(tmp, "w") as f:
                _json.dump(self.executor.save_path_model(), f)
            _os.replace(tmp, path)
        except OSError:
            pass

    def _monitor_cache_flush(self):
        """(ref: monitorCacheFlush holder.go:340-376). Also persists
        the executor's learned path model — same sidecar-class,
        best-effort discipline as the rank caches."""
        self.holder.flush_caches()
        self._save_path_model()

    def _monitor_runtime(self):
        """Process-telemetry collector (ref: monitorRuntime
        server.go:632-675, open FDs via CountOpenFiles :701-723):
        gauges RSS, CPU seconds, per-generation GC counters, threads,
        open fds, and uptime into the stats client — rendered as
        ``pilosa_process_*`` on /metrics and folded into the hourly
        diagnostics JSONL. Interval (and the 0 = off switch) comes
        from ``[metrics] collector-interval``. The legacy RSS/Threads/
        Goroutines/OpenFiles gauge names are kept for older
        dashboards."""
        t = stats_mod.process_telemetry(self._started_at)
        for key, val in t.items():
            self.stats.gauge(f"process_{key}", val)
        if "rss_bytes" in t:
            self.stats.gauge("RSS", t["rss_bytes"] // 1024)
        self.stats.gauge("Threads", t["threads"])
        self.stats.gauge("Goroutines", t["threads"])
        if "open_fds" in t:
            self.stats.gauge("OpenFiles", t["open_fds"])
