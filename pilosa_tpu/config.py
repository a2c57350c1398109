"""Config: TOML file ⟵ env (PILOSA_*) ⟵ CLI flags (ref: config.go:44-130,
cmd/root.go:60-107 setAllConfig)."""
import os

import tomllib

DEFAULT_PORT = 10101        # ref: config.go:17-32
DEFAULT_BIND = f"localhost:{DEFAULT_PORT}"

# Reject request bodies larger than this with 413 before buffering
# (server/handler.py make_http_server). A few MiB comfortably covers
# the largest legitimate import batch (MaxWritesPerRequest bits) while
# bounding what one connection can pin; fragment restore
# (POST /fragment/data, multi-GB backup tars) is exempt from the cap.
DEFAULT_MAX_BODY_SIZE = 8 << 20


class Config:
    def __init__(self):
        self.data_dir = "~/.pilosa"
        self.bind = DEFAULT_BIND
        self.max_writes_per_request = 5000
        self.log_path = ""
        # Host-byte budget for resident fragment matrices; 0 =
        # unlimited. (TPU-build extension: the reference's mmap lets
        # the OS bound RSS by page eviction; the dense-matrix design
        # needs an explicit cap — storage/memgov.py.)
        self.host_bytes = 0
        self.cluster = {
            "replicas": 1,
            "type": "static",
            "hosts": [],
            "poll-interval": 60,
            "long-query-time": 60,
            # Distributed mutation-epoch freshness bound (seconds):
            # how stale a peer's last-observed epoch counter may be
            # before a cached replay must probe it (cluster/epochs.py).
            # 0 = one membership heartbeat interval. This is the
            # documented worst-case staleness of a warm replay against
            # a write this node never relayed; unknown/unprobeable
            # peers always mean cold, never stale.
            "epoch-probe-ttl": 0,
            # Elastic-topology rebalancer (cluster/rebalancer.py):
            # concurrent fragment streams per resize, bytes/sec pacing
            # across all streams (0 = unpaced), and how long a LEAVING
            # node's shutdown waits for its handoff to finish.
            "rebalance-stream-concurrency": 2,
            "rebalance-bandwidth": 0,
            "rebalance-drain-timeout": 30.0,
            # Tail-tolerant reads (cluster/hedge.py; defaults mirror
            # hedge.DEFAULTS). hedge-reads arms deadline-budgeted
            # hedged fan-out; replica-routing scores every slice leg's
            # owner by live replica vitals instead of first-healthy.
            # Hedges draw from a token bucket refilled hedge-ratio
            # per primary leg (capped at hedge-burst) — the ~15%
            # extra-backend-load metastability bound. The hedge timer
            # is max(hedge-delay-ms, predicted latency ×
            # hedge-delay-factor) clamped to hedge-headroom of the
            # remaining deadline; at most hedge-max-per-request
            # hedges per request.
            "hedge-reads": False,
            "replica-routing": False,
            "hedge-ratio": 0.10,
            "hedge-burst": 8.0,
            "hedge-delay-ms": 30.0,
            "hedge-delay-factor": 1.5,
            "hedge-headroom": 0.5,
            "hedge-max-per-request": 4,
        }
        self.anti_entropy = {"interval": 600}
        self.tls = {                # ref: config.go TLS section
            "certificate": "",
            "key": "",
            "skip-verify": False,
        }
        self.metric = {
            "service": "expvar",
            "host": "127.0.0.1:8125",
            "poll-interval": 10,
            "diagnostics": False,  # phone-home is opt-in here, unlike ref
        }
        # Runtime telemetry (stats.py histograms, process collector,
        # /cluster/metrics aggregation). Histograms default ON — an
        # observation is a bisect + three integer adds; turning them
        # off restores the single-nop-attribute-read hot path.
        self.metrics = {
            "histograms": True,
            "histogram-buckets": [],   # seconds; [] = built-in defaults
            "collector-interval": 10,  # process telemetry; 0 = off
            "cluster-aggregation": True,
        }
        # "" / "text" = plain logging; "json" = structured records
        # with trace_id/span_id stamped from the active trace context
        # (logfmt.py).
        self.log_format = ""
        self.trace = {
            # Distributed query tracing (tracing.py). Off by default:
            # the nop tracer keeps the hot path allocation-free.
            "enabled": False,
            "slow-threshold": 0.25,   # seconds; slower queries are
            "ring-size": 128,         # retained in the slow-query ring
            "slow-ring-size": 64,
        }
        self.max_body_size = DEFAULT_MAX_BODY_SIZE
        # Graceful-drain budget: how long close()/SIGTERM waits for
        # in-flight queries after flipping the node to LEAVING.
        self.drain_timeout = 5.0
        self.faults = {
            # Deterministic fault injection (faults.py). Off by
            # default; enabling also unlocks POST /debug/faults.
            "enabled": False,
            "spec": "",   # e.g. "fragment.append.fsync=error(ENOSPC)"
        }
        self.storage = {
            # Compressed device-resident containers (ops/containers.py):
            # per-row-block array/run formats chosen from density
            # stats, with the dense path as the hot-block fallback.
            # Default ON; off = every block dense = the pre-container
            # behavior, bit-identical results either way.
            "container-formats": True,
        }
        self.executor = {
            # Epoch-validated slice-plan cache (plancache.py): LRU
            # entry budget for memoized slice universes, batched
            # dispatch plans, prelude layouts, and owner-host sets.
            # 0 disables the cache (every query re-walks its slices);
            # the default matches plancache.DEFAULT_ENTRIES.
            "plan-cache-entries": 512,
            # Cross-query micro-batching tick (executor coalescer):
            # how long a tick leader holds its accumulation window
            # open for more arrivals (microseconds; 0 = dispatch
            # immediately — batching still grows with load because
            # arrivals park while a tick runs), how many requests one
            # tick admits (QoS priority order decides who when it
            # truncates), whether all-compressed plans fuse as
            # container lanes (false = the pre-PR decline: compressed
            # concurrency serves serially), and the per-group HBM
            # budget for densifying DEEP all-compressed trees (each
            # densified block ticks container_conversions_total).
            "coalesce-max-wait-us": 0,
            "coalesce-max-group": 64,
            "coalesce-compressed": True,
            "coalesce-densify-bytes": 64 << 20,
        }
        # Adaptive cost-based query planner (planner.py): selectivity
        # reordering of commutative Intersect/Union chains, static
        # short-circuiting of provably-empty subtrees, and learned
        # execution-tier selection from the cost model's per-tier
        # estimates. Default ON; off = the written operand order and
        # the fixed tier-consultation chain, byte-identical results
        # either way. explore-stride: every Nth warm use of a plan
        # serves the static tier and records, so a mispredicted
        # override self-corrects (0 = never explore).
        self.planner = {
            "enabled": True,
            "reorder": True,
            "short-circuit": True,
            "tier-select": True,
            "explore-stride": 64,
        }
        self.ingest = {
            # Streaming bulk-ingest pipeline (ingest/pipeline.py):
            # POST /index/<i>/ingest with device-side pack/classify.
            # Default ON; disabling answers 501 on the route.
            "enabled": True,
            # Per-request bit/value budget — bounds what one request
            # pins in host memory and how long one admission slot is
            # held; far above the legacy max-writes-per-request.
            "max-batch-bits": 8_000_000,
        }
        # Workload observatory (observe/): kernel-cost attribution +
        # slice/row heatmaps. Always-on by default — `make obscheck`
        # checks that its surfaces are live; what it costs a request is
        # not measured by any check; disabling restores the
        # one-nop-attribute-read hot path.
        self.observe = {
            "enabled": True,
            # 1-in-N kernel dispatches block_until_ready so TRUE
            # device time is sampled without stalling async dispatch
            # pipelining on the other N-1. 0 = never block (enqueue
            # time only).
            "kernel-sample-rate": 0,
            "heatmap-half-life": 300.0,  # seconds; heat decay rate
            "heatmap-top-k": 20,         # bounded /metrics exposition
        }
        # Continuous profiler (observe/profiler.py): always-on
        # wall-clock stack sampler over sys._current_frames with
        # subsystem attribution, served at /debug/profile. sample-hz
        # defaults to a prime so the sampler cannot phase-lock with
        # periodic work; 0 disables (the one-nop-attribute-read tier).
        self.profile = {
            "sample-hz": 19.0,
            # Where POST /debug/profile/device writes jax.profiler
            # traces when the request doesn't name a directory.
            "device-trace-dir": "",
        }
        # SLO tracker (observe/slo.py): per-QoS-priority latency/
        # availability objectives with 5m/1h burn rates. Off by
        # default (objectives are deployment policy, not a library
        # default); [slo.objectives.<priority>] tables declare them.
        self.slo = {
            "enabled": False,
            "objectives": {},
        }
        # Collective data plane (cluster/meshplane.py): within a
        # mesh peer group (one JAX process group sharing one device
        # set) multi-node queries compile to one shard_map + psum
        # program instead of HTTP fan-out. Off by default — it is a
        # topology claim (the group's nodes really do share devices),
        # not a tuning knob; HTTP remains the universal path.
        self.mesh = {
            "enabled": False,
            "group": "local",
            "stack-bytes": 1 << 30,  # staged sharded-stack LRU budget
        }
        self.qos = {
            # QoS & admission control (qos.py). Off by default: the
            # nop gate keeps the hot path lock- and allocation-free.
            "enabled": False,
            "max-concurrent": 64,      # admission gate capacity
            "queue-length": 128,       # bounded priority wait queue
            "queue-timeout": 1.0,      # max seconds queued before shed
            "default-deadline": 0.0,   # seconds; 0 = unbounded
            "client-qps": 0.0,         # default per-client rate; 0 = off
            "client-burst": 0.0,       # 0 = 2 * qps (floor 1 token)
            "quotas": {},              # client id -> qps override
            "breaker-threshold": 5,    # consecutive transport failures
            "breaker-cooldown": 10.0,  # seconds before a half-open probe
        }
        # Heat-driven autopilot (autopilot/controller.py): the
        # closed-loop controller. Off by default — operating the
        # cluster autonomously is deployment policy, not a library
        # default; `enabled = false` is also the kill switch.
        self.autopilot = {
            "enabled": False,
            "dry-run": False,            # plan + journal, never act
            "interval": 5.0,             # seconds between control passes
            "placement": True,           # heat-weighted placement loop
            "memory": True,              # pre-stage/demote tiering loop
            "slo": True,                 # SLO-burn responder loop
            "min-dwell": 60.0,           # seconds between same-loop actions
            "max-actions-per-window": 2,  # windowed action budget
            "window": 300.0,             # budget window seconds
            "heat-imbalance": 1.5,       # hottest-host/mean trigger ratio
            "memory-headroom": 0.85,     # governor pressure demote trigger
        }

    KNOWN_KEYS = {
        "data-dir", "bind", "max-writes-per-request", "log-path",
        "log-format", "host-bytes", "max-body-size", "drain-timeout",
        "cluster", "anti-entropy", "metric", "metrics", "tls", "trace",
        "qos", "faults", "executor", "storage", "ingest", "observe",
        "profile", "slo", "mesh", "autopilot", "planner",
    }

    @classmethod
    def load(cls, path=None, env=None, overrides=None):
        cfg = cls()
        if path:
            with open(path, "rb") as f:
                data = tomllib.load(f)
            unknown = set(data) - cls.KNOWN_KEYS
            if unknown:
                raise ValueError(
                    f"invalid config option(s): {sorted(unknown)}")
            cfg._apply(data)
        cfg._apply_env(env if env is not None else os.environ)
        if overrides:
            cfg._apply(overrides)
        cfg.validate()
        return cfg

    def _apply(self, data):
        if "data-dir" in data:
            self.data_dir = data["data-dir"]
        if "bind" in data:
            self.bind = data["bind"]
        if "max-writes-per-request" in data:
            self.max_writes_per_request = int(data["max-writes-per-request"])
        if "log-path" in data:
            self.log_path = data["log-path"]
        if "log-format" in data:
            self.log_format = data["log-format"]
        if "host-bytes" in data:
            self.host_bytes = int(data["host-bytes"])
        if "max-body-size" in data:
            self.max_body_size = int(data["max-body-size"])
        if "drain-timeout" in data:
            self.drain_timeout = float(data["drain-timeout"])
        for section in ("cluster", "anti-entropy", "metric", "metrics",
                        "tls", "trace", "qos", "faults", "executor",
                        "storage", "ingest", "observe", "profile",
                        "slo", "mesh", "autopilot", "planner"):
            if section in data:
                target = {"cluster": self.cluster,
                          "anti-entropy": self.anti_entropy,
                          "metric": self.metric,
                          "metrics": self.metrics,
                          "tls": self.tls,
                          "trace": self.trace,
                          "qos": self.qos,
                          "faults": self.faults,
                          "executor": self.executor,
                          "storage": self.storage,
                          "ingest": self.ingest,
                          "observe": self.observe,
                          "profile": self.profile,
                          "slo": self.slo,
                          "mesh": self.mesh,
                          "autopilot": self.autopilot,
                          "planner": self.planner}[section]
                target.update(data[section])

    def _apply_env(self, env):
        """PILOSA_* variables override file values (ref: cmd/root.go:73-90)."""
        if env.get("PILOSA_DATA_DIR"):
            self.data_dir = env["PILOSA_DATA_DIR"]
        if env.get("PILOSA_BIND"):
            self.bind = env["PILOSA_BIND"]
        if env.get("PILOSA_TPU_HOST_BYTES"):
            self.host_bytes = int(env["PILOSA_TPU_HOST_BYTES"])
        if env.get("PILOSA_CLUSTER_HOSTS"):
            self.cluster["hosts"] = [
                h.strip() for h in env["PILOSA_CLUSTER_HOSTS"].split(",") if h]
        if env.get("PILOSA_CLUSTER_REPLICAS"):
            self.cluster["replicas"] = int(env["PILOSA_CLUSTER_REPLICAS"])
        if env.get("PILOSA_EPOCH_PROBE_TTL"):
            self.cluster["epoch-probe-ttl"] = float(
                env["PILOSA_EPOCH_PROBE_TTL"])
        if env.get("PILOSA_REBALANCE_STREAM_CONCURRENCY"):
            self.cluster["rebalance-stream-concurrency"] = int(
                env["PILOSA_REBALANCE_STREAM_CONCURRENCY"])
        if env.get("PILOSA_REBALANCE_BANDWIDTH"):
            self.cluster["rebalance-bandwidth"] = int(
                env["PILOSA_REBALANCE_BANDWIDTH"])
        if env.get("PILOSA_REBALANCE_DRAIN_TIMEOUT"):
            self.cluster["rebalance-drain-timeout"] = float(
                env["PILOSA_REBALANCE_DRAIN_TIMEOUT"])
        # PILOSA_HEDGE_* (tail-tolerant reads): parsed by the hedge
        # module's OWN parser so config/env/server agree on one
        # grammar; malformed numeric values keep the defaults.
        from pilosa_tpu.cluster import hedge as _hedge

        self.cluster.update(_hedge.env_config(env))
        if env.get("PILOSA_METRIC_SERVICE"):
            self.metric["service"] = env["PILOSA_METRIC_SERVICE"]
        if env.get("PILOSA_TLS_CERTIFICATE"):
            self.tls["certificate"] = env["PILOSA_TLS_CERTIFICATE"]
        if env.get("PILOSA_TLS_KEY"):
            self.tls["key"] = env["PILOSA_TLS_KEY"]
        if env.get("PILOSA_TLS_SKIP_VERIFY"):
            self.tls["skip-verify"] = env[
                "PILOSA_TLS_SKIP_VERIFY"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_TRACE_ENABLED"):
            self.trace["enabled"] = env[
                "PILOSA_TRACE_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_TRACE_SLOW_THRESHOLD"):
            self.trace["slow-threshold"] = float(
                env["PILOSA_TRACE_SLOW_THRESHOLD"])
        if env.get("PILOSA_MAX_BODY_SIZE"):
            self.max_body_size = int(env["PILOSA_MAX_BODY_SIZE"])
        if env.get("PILOSA_QOS_ENABLED"):
            self.qos["enabled"] = env[
                "PILOSA_QOS_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_QOS_MAX_CONCURRENT"):
            self.qos["max-concurrent"] = int(
                env["PILOSA_QOS_MAX_CONCURRENT"])
        if env.get("PILOSA_QOS_CLIENT_QPS"):
            self.qos["client-qps"] = float(env["PILOSA_QOS_CLIENT_QPS"])
        if env.get("PILOSA_QOS_DEFAULT_DEADLINE"):
            self.qos["default-deadline"] = float(
                env["PILOSA_QOS_DEFAULT_DEADLINE"])
        if env.get("PILOSA_PLAN_CACHE_ENTRIES"):
            # plancache.py reads this env itself for bare Executor
            # construction (tests, embedding); mirrored here so the
            # config surface reports the truth. Malformed values keep
            # the default and negatives clamp to 0 (off), matching
            # PlanCache's own parse — the one knob must not no-op on
            # one path and crash on the other.
            try:
                self.executor["plan-cache-entries"] = max(
                    0, int(env["PILOSA_PLAN_CACHE_ENTRIES"]))
            except ValueError:
                pass
        if env.get("PILOSA_COALESCE_MAX_WAIT_US"):
            # The executor reads these envs itself for bare
            # construction (tests, embedding); mirrored here so the
            # config surface reports the truth. Malformed values keep
            # the default (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.executor["coalesce-max-wait-us"] = max(
                    0, int(env["PILOSA_COALESCE_MAX_WAIT_US"]))
            except ValueError:
                pass
        if env.get("PILOSA_COALESCE_MAX_GROUP"):
            try:
                self.executor["coalesce-max-group"] = max(
                    1, int(env["PILOSA_COALESCE_MAX_GROUP"]))
            except ValueError:
                pass
        if env.get("PILOSA_COALESCE_COMPRESSED"):
            # The executor's own parse accepts anything not in the
            # falsey set — same rule here so the two cannot drift.
            self.executor["coalesce-compressed"] = env[
                "PILOSA_COALESCE_COMPRESSED"].lower() not in (
                    "0", "false", "no", "off")
        if env.get("PILOSA_COALESCE_DENSIFY_BYTES"):
            try:
                self.executor["coalesce-densify-bytes"] = max(
                    0, int(env["PILOSA_COALESCE_DENSIFY_BYTES"]))
            except ValueError:
                pass
        # The planner reads these envs itself for bare Executor
        # construction (tests, embedding); mirrored here so the config
        # surface reports the truth — the planner's own parse accepts
        # anything not in the falsey set, same rule here.
        for var, key in (("PILOSA_PLANNER_ENABLED", "enabled"),
                         ("PILOSA_PLANNER_REORDER", "reorder"),
                         ("PILOSA_PLANNER_SHORT_CIRCUIT", "short-circuit"),
                         ("PILOSA_PLANNER_TIER_SELECT", "tier-select")):
            if env.get(var):
                self.planner[key] = env[var].lower() not in (
                    "0", "false", "no", "off")
        if env.get("PILOSA_PLANNER_EXPLORE_STRIDE"):
            try:
                self.planner["explore-stride"] = max(
                    0, int(env["PILOSA_PLANNER_EXPLORE_STRIDE"]))
            except ValueError:
                pass
        if env.get("PILOSA_INGEST_ENABLED"):
            self.ingest["enabled"] = env[
                "PILOSA_INGEST_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_INGEST_MAX_BATCH_BITS"):
            # Malformed values keep the default rather than crash the
            # boot (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.ingest["max-batch-bits"] = int(
                    env["PILOSA_INGEST_MAX_BATCH_BITS"])
            except ValueError:
                pass
        if env.get("PILOSA_CONTAINER_FORMATS"):
            # The containers module reads this env itself at import
            # (bare fragments/executors honor it); mirrored here via
            # the module's OWN parser so the config surface reports
            # the truth and the two rules cannot drift.
            from pilosa_tpu.ops import containers as containers_mod

            self.storage["container-formats"] = containers_mod.\
                parse_enabled(env["PILOSA_CONTAINER_FORMATS"])
        if env.get("PILOSA_OBSERVE_ENABLED"):
            self.observe["enabled"] = env[
                "PILOSA_OBSERVE_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_OBSERVE_KERNEL_SAMPLE_RATE"):
            # Malformed values keep the default rather than crash the
            # boot (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.observe["kernel-sample-rate"] = max(
                    0, int(env["PILOSA_OBSERVE_KERNEL_SAMPLE_RATE"]))
            except ValueError:
                pass
        if env.get("PILOSA_OBSERVE_HEATMAP_HALF_LIFE"):
            try:
                self.observe["heatmap-half-life"] = float(
                    env["PILOSA_OBSERVE_HEATMAP_HALF_LIFE"])
            except ValueError:
                pass
        if env.get("PILOSA_OBSERVE_HEATMAP_TOP_K"):
            try:
                self.observe["heatmap-top-k"] = max(
                    1, int(env["PILOSA_OBSERVE_HEATMAP_TOP_K"]))
            except ValueError:
                pass
        # Flight recorder + replica vitals: absent keys follow the
        # observatory master switch (server resolves the default), so
        # the env vars only materialize a key when set.
        if env.get("PILOSA_OBSERVE_EVENTS"):
            self.observe["events"] = env[
                "PILOSA_OBSERVE_EVENTS"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_OBSERVE_VITALS"):
            self.observe["vitals"] = env[
                "PILOSA_OBSERVE_VITALS"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_PROFILE_SAMPLE_HZ"):
            # Malformed values keep the default rather than crash the
            # boot (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.profile["sample-hz"] = max(
                    0.0, float(env["PILOSA_PROFILE_SAMPLE_HZ"]))
            except ValueError:
                pass
        if env.get("PILOSA_PROFILE_DEVICE_TRACE_DIR"):
            self.profile["device-trace-dir"] = env[
                "PILOSA_PROFILE_DEVICE_TRACE_DIR"].strip()
        if env.get("PILOSA_SLO_ENABLED"):
            self.slo["enabled"] = env[
                "PILOSA_SLO_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_SLO_OBJECTIVES"):
            # Compact spec grammar (prio=<n>ms@<percent>, comma
            # separated) parsed by the slo module's OWN parser so the
            # env surface and the tracker cannot drift; a malformed
            # spec fails the boot like a typo'd failpoint does.
            # Declaring objectives implies enabling the tracker —
            # UNLESS PILOSA_SLO_ENABLED explicitly said no (a
            # fleet-wide objectives var must stay overridable per
            # host); server.py's direct-construction path applies the
            # same rule.
            from pilosa_tpu.observe import slo as slo_mod

            objectives = slo_mod.parse_objectives(
                env["PILOSA_SLO_OBJECTIVES"])
            if not env.get("PILOSA_SLO_ENABLED"):
                self.slo["enabled"] = True
            self.slo["objectives"] = {
                prio: {"latency-ms": obj["latency"] * 1e3,
                       "target": obj["target"] * 100.0,
                       "availability": obj["availability"] * 100.0}
                for prio, obj in objectives.items()}
        if env.get("PILOSA_MESH_ENABLED"):
            self.mesh["enabled"] = env[
                "PILOSA_MESH_ENABLED"].lower() in ("1", "true", "yes")
        if env.get("PILOSA_MESH_GROUP"):
            self.mesh["group"] = env["PILOSA_MESH_GROUP"].strip()
        if env.get("PILOSA_MESH_STACK_BYTES"):
            # Malformed values keep the default rather than crash the
            # boot (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.mesh["stack-bytes"] = int(
                    env["PILOSA_MESH_STACK_BYTES"])
            except ValueError:
                pass
        if env.get("PILOSA_AUTOPILOT_ENABLED"):
            self.autopilot["enabled"] = env[
                "PILOSA_AUTOPILOT_ENABLED"].lower() in ("1", "true",
                                                        "yes")
        if env.get("PILOSA_AUTOPILOT_DRY_RUN"):
            self.autopilot["dry-run"] = env[
                "PILOSA_AUTOPILOT_DRY_RUN"].lower() in ("1", "true",
                                                        "yes")
        if env.get("PILOSA_AUTOPILOT_INTERVAL"):
            # Malformed values keep the default rather than crash the
            # boot (the PILOSA_PLAN_CACHE_ENTRIES discipline).
            try:
                self.autopilot["interval"] = float(
                    env["PILOSA_AUTOPILOT_INTERVAL"])
            except ValueError:
                pass
        if env.get("PILOSA_AUTOPILOT_MIN_DWELL"):
            try:
                self.autopilot["min-dwell"] = float(
                    env["PILOSA_AUTOPILOT_MIN_DWELL"])
            except ValueError:
                pass
        if env.get("PILOSA_AUTOPILOT_MAX_ACTIONS_PER_WINDOW"):
            try:
                self.autopilot["max-actions-per-window"] = int(
                    env["PILOSA_AUTOPILOT_MAX_ACTIONS_PER_WINDOW"])
            except ValueError:
                pass
        if env.get("PILOSA_AUTOPILOT_WINDOW"):
            try:
                self.autopilot["window"] = float(
                    env["PILOSA_AUTOPILOT_WINDOW"])
            except ValueError:
                pass
        if env.get("PILOSA_AUTOPILOT_HEAT_IMBALANCE"):
            try:
                self.autopilot["heat-imbalance"] = float(
                    env["PILOSA_AUTOPILOT_HEAT_IMBALANCE"])
            except ValueError:
                pass
        if env.get("PILOSA_DRAIN_TIMEOUT"):
            self.drain_timeout = float(env["PILOSA_DRAIN_TIMEOUT"])
        if env.get("PILOSA_LOG_FORMAT"):
            self.log_format = env["PILOSA_LOG_FORMAT"].strip().lower()
        if env.get("PILOSA_METRICS_HISTOGRAMS"):
            self.metrics["histograms"] = env[
                "PILOSA_METRICS_HISTOGRAMS"].lower() in ("1", "true",
                                                         "yes")
        if env.get("PILOSA_METRICS_COLLECTOR_INTERVAL"):
            self.metrics["collector-interval"] = int(
                env["PILOSA_METRICS_COLLECTOR_INTERVAL"])
        if env.get("PILOSA_METRICS_CLUSTER_AGGREGATION"):
            self.metrics["cluster-aggregation"] = env[
                "PILOSA_METRICS_CLUSTER_AGGREGATION"].lower() in (
                    "1", "true", "yes")
        spec = env.get("PILOSA_FAULTS", "")
        if spec and spec.lower() not in ("0", "false", "no", "off"):
            # The faults module reads this env itself at import (so
            # bare fragments/clients see it); mirrored here so the
            # config surface reports the truth.
            self.faults["enabled"] = True
            if spec.lower() not in ("1", "true", "yes"):
                self.faults["spec"] = spec

    def validate(self):
        if self.cluster.get("type") not in ("static", "http", "gossip"):
            raise ValueError(
                f"invalid cluster type: {self.cluster.get('type')}")
        if self.host_bytes < 0:
            raise ValueError(
                f"host-bytes must be >= 0 (0 = unlimited): "
                f"{self.host_bytes}")
        if float(self.cluster.get("epoch-probe-ttl", 0)) < 0:
            raise ValueError(
                f"cluster epoch-probe-ttl must be >= 0 (0 = one "
                f"heartbeat interval): {self.cluster['epoch-probe-ttl']}")
        if int(self.cluster.get("rebalance-stream-concurrency", 1)) < 1:
            raise ValueError(
                f"cluster rebalance-stream-concurrency must be >= 1: "
                f"{self.cluster['rebalance-stream-concurrency']}")
        if int(self.cluster.get("rebalance-bandwidth", 0)) < 0:
            raise ValueError(
                f"cluster rebalance-bandwidth must be >= 0 "
                f"(0 = unpaced): {self.cluster['rebalance-bandwidth']}")
        if float(self.cluster.get("rebalance-drain-timeout", 0)) < 0:
            raise ValueError(
                f"cluster rebalance-drain-timeout must be >= 0: "
                f"{self.cluster['rebalance-drain-timeout']}")
        ratio = float(self.cluster.get("hedge-ratio", 0.1))
        if not 0.0 < ratio <= 1.0:
            raise ValueError(
                f"cluster hedge-ratio must be in (0, 1]: {ratio}")
        if float(self.cluster.get("hedge-burst", 1)) < 1:
            raise ValueError(
                f"cluster hedge-burst must be >= 1: "
                f"{self.cluster['hedge-burst']}")
        if float(self.cluster.get("hedge-delay-ms", 0)) < 0:
            raise ValueError(
                f"cluster hedge-delay-ms must be >= 0: "
                f"{self.cluster['hedge-delay-ms']}")
        if float(self.cluster.get("hedge-delay-factor", 0)) < 0:
            raise ValueError(
                f"cluster hedge-delay-factor must be >= 0: "
                f"{self.cluster['hedge-delay-factor']}")
        headroom = float(self.cluster.get("hedge-headroom", 0.5))
        if not 0.0 < headroom <= 1.0:
            raise ValueError(
                f"cluster hedge-headroom must be in (0, 1]: {headroom}")
        if int(self.cluster.get("hedge-max-per-request", 1)) < 1:
            raise ValueError(
                f"cluster hedge-max-per-request must be >= 1: "
                f"{self.cluster['hedge-max-per-request']}")
        if float(self.trace["slow-threshold"]) < 0:
            raise ValueError(
                f"trace slow-threshold must be >= 0: "
                f"{self.trace['slow-threshold']}")
        if int(self.trace["ring-size"]) < 1 \
                or int(self.trace["slow-ring-size"]) < 1:
            raise ValueError("trace ring sizes must be >= 1")
        if self.max_body_size < 0:
            raise ValueError(
                f"max-body-size must be >= 0 (0 = unlimited): "
                f"{self.max_body_size}")
        if float(self.drain_timeout) < 0:
            raise ValueError(
                f"drain-timeout must be >= 0 (0 = close immediately): "
                f"{self.drain_timeout}")
        if self.log_format not in ("", "text", "json"):
            raise ValueError(
                f'log-format must be "text" or "json": '
                f"{self.log_format!r}")
        m = self.metrics
        if int(m["collector-interval"]) < 0:
            raise ValueError(
                f"metrics collector-interval must be >= 0 (0 = off): "
                f"{m['collector-interval']}")
        buckets = m.get("histogram-buckets") or []
        prev = 0.0
        for b in buckets:
            try:
                val = float(b)
            except (TypeError, ValueError):
                raise ValueError(
                    f"metrics histogram-buckets must be numbers: {b!r}")
            if val <= prev:
                # Strictly increasing positives: cumulative bucket
                # exposition is meaningless otherwise, and a zero or
                # repeated bound would emit duplicate le= series.
                raise ValueError(
                    "metrics histogram-buckets must be strictly "
                    f"increasing positive seconds: {buckets}")
            prev = val
        if self.faults.get("spec"):
            # Parse at startup so a typo'd failpoint fails the boot,
            # not the first fire.
            from pilosa_tpu import faults as faults_mod

            try:
                faults_mod.parse_spec(self.faults["spec"])
            except ValueError as e:
                raise ValueError(f"faults spec: {e}")
        if not isinstance(self.storage.get("container-formats", True),
                          bool):
            raise ValueError(
                f"storage container-formats must be a boolean: "
                f"{self.storage['container-formats']!r}")
        if int(self.executor.get("plan-cache-entries", 0)) < 0:
            raise ValueError(
                f"executor plan-cache-entries must be >= 0 (0 = off): "
                f"{self.executor['plan-cache-entries']}")
        if int(self.executor.get("coalesce-max-wait-us", 0)) < 0:
            raise ValueError(
                f"executor coalesce-max-wait-us must be >= 0 (0 = "
                f"dispatch immediately): "
                f"{self.executor['coalesce-max-wait-us']}")
        if int(self.executor.get("coalesce-max-group", 1)) < 1:
            raise ValueError(
                f"executor coalesce-max-group must be >= 1: "
                f"{self.executor['coalesce-max-group']}")
        if not isinstance(self.executor.get("coalesce-compressed", True),
                          bool):
            raise ValueError(
                f"executor coalesce-compressed must be a boolean: "
                f"{self.executor['coalesce-compressed']!r}")
        if int(self.executor.get("coalesce-densify-bytes", 0)) < 0:
            raise ValueError(
                f"executor coalesce-densify-bytes must be >= 0 (0 = "
                f"never densify): "
                f"{self.executor['coalesce-densify-bytes']}")
        for key in ("enabled", "reorder", "short-circuit",
                    "tier-select"):
            if not isinstance(self.planner.get(key, True), bool):
                raise ValueError(
                    f"planner {key} must be a boolean: "
                    f"{self.planner[key]!r}")
        if int(self.planner.get("explore-stride", 0)) < 0:
            raise ValueError(
                f"planner explore-stride must be >= 0 (0 = never "
                f"explore): {self.planner['explore-stride']}")
        if not isinstance(self.ingest.get("enabled", True), bool):
            raise ValueError(
                f"ingest enabled must be a boolean: "
                f"{self.ingest['enabled']!r}")
        if int(self.ingest.get("max-batch-bits", 1)) < 1:
            raise ValueError(
                f"ingest max-batch-bits must be >= 1: "
                f"{self.ingest['max-batch-bits']}")
        o = self.observe
        if not isinstance(o.get("enabled", True), bool):
            raise ValueError(
                f"observe enabled must be a boolean: {o['enabled']!r}")
        if int(o.get("kernel-sample-rate", 0)) < 0:
            raise ValueError(
                f"observe kernel-sample-rate must be >= 0 (0 = never "
                f"block): {o['kernel-sample-rate']}")
        if float(o.get("heatmap-half-life", 1)) <= 0:
            raise ValueError(
                f"observe heatmap-half-life must be > 0 seconds: "
                f"{o['heatmap-half-life']}")
        if int(o.get("heatmap-top-k", 1)) < 1:
            raise ValueError(
                f"observe heatmap-top-k must be >= 1: "
                f"{o['heatmap-top-k']}")
        for key in ("events", "vitals"):
            if key in o and not isinstance(o[key], bool):
                raise ValueError(
                    f"observe {key} must be a boolean: {o[key]!r}")
        if int(o.get("events-ring", 1)) < 1:
            raise ValueError(
                f"observe events-ring must be >= 1: {o['events-ring']}")
        if float(o.get("vitals-window", 1)) <= 0:
            raise ValueError(
                f"observe vitals-window must be > 0 seconds: "
                f"{o['vitals-window']}")
        if float(o.get("watchdog-factor", 2)) <= 1:
            raise ValueError(
                f"observe watchdog-factor must be > 1: "
                f"{o['watchdog-factor']}")
        if float(o.get("watchdog-min-ms", 0)) < 0:
            raise ValueError(
                f"observe watchdog-min-ms must be >= 0: "
                f"{o['watchdog-min-ms']}")
        if float(self.profile.get("sample-hz", 0)) < 0:
            raise ValueError(
                f"profile sample-hz must be >= 0 (0 = off): "
                f"{self.profile['sample-hz']}")
        if not isinstance(self.profile.get("device-trace-dir", ""),
                          str):
            raise ValueError(
                f"profile device-trace-dir must be a string: "
                f"{self.profile['device-trace-dir']!r}")
        if not isinstance(self.slo.get("enabled", False), bool):
            raise ValueError(
                f"slo enabled must be a boolean: "
                f"{self.slo['enabled']!r}")
        if self.slo.get("objectives"):
            # Normalized at startup so a typo'd objective fails the
            # boot, not the first burn-rate computation.
            from pilosa_tpu.observe import slo as slo_mod

            try:
                slo_mod.normalize_objectives(self.slo["objectives"])
            except (TypeError, ValueError) as e:
                raise ValueError(f"slo objectives: {e}")
        if not isinstance(self.mesh.get("enabled", False), bool):
            raise ValueError(
                f"mesh enabled must be a boolean: "
                f"{self.mesh['enabled']!r}")
        if not str(self.mesh.get("group", "local")):
            raise ValueError("mesh group must be a non-empty string")
        if int(self.mesh.get("stack-bytes", 1)) < 1:
            raise ValueError(
                f"mesh stack-bytes must be >= 1: "
                f"{self.mesh['stack-bytes']}")
        q = self.qos
        if int(q["max-concurrent"]) < 1:
            raise ValueError(
                f"qos max-concurrent must be >= 1: {q['max-concurrent']}")
        if int(q["queue-length"]) < 0:
            raise ValueError(
                f"qos queue-length must be >= 0: {q['queue-length']}")
        for key in ("queue-timeout", "default-deadline", "client-qps",
                    "client-burst", "breaker-cooldown"):
            if float(q[key]) < 0:
                raise ValueError(f"qos {key} must be >= 0: {q[key]}")
        if int(q["breaker-threshold"]) < 1:
            raise ValueError(
                f"qos breaker-threshold must be >= 1: "
                f"{q['breaker-threshold']}")
        ap = self.autopilot
        for key in ("enabled", "dry-run", "placement", "memory", "slo"):
            if not isinstance(ap.get(key, False), bool):
                raise ValueError(
                    f"autopilot {key} must be a boolean: {ap[key]!r}")
        if float(ap.get("interval", 1)) <= 0:
            raise ValueError(
                f"autopilot interval must be > 0 seconds: "
                f"{ap['interval']}")
        for key in ("min-dwell", "window"):
            if float(ap.get(key, 0)) < 0:
                raise ValueError(
                    f"autopilot {key} must be >= 0 seconds: {ap[key]}")
        if int(ap.get("max-actions-per-window", 1)) < 1:
            raise ValueError(
                f"autopilot max-actions-per-window must be >= 1: "
                f"{ap['max-actions-per-window']}")
        if float(ap.get("heat-imbalance", 1)) < 1:
            raise ValueError(
                f"autopilot heat-imbalance must be >= 1 (1 = any "
                f"skew triggers): {ap['heat-imbalance']}")
        if not 0 < float(ap.get("memory-headroom", 0.5)) <= 1:
            raise ValueError(
                f"autopilot memory-headroom must be in (0, 1]: "
                f"{ap['memory-headroom']}")
        for client, qps in (q.get("quotas") or {}).items():
            # Validated at startup like every other qos key — a bad
            # override must not surface as per-request errors, and a
            # negative one would silently mean UNLIMITED (qps <= 0 is
            # the documented off switch) for the one client the
            # operator meant to restrict.
            try:
                val = float(qps)
            except (TypeError, ValueError):
                raise ValueError(
                    f"qos quota for {client!r} must be a number: "
                    f"{qps!r}")
            if val < 0:
                raise ValueError(
                    f"qos quota for {client!r} must be >= 0 "
                    f"(0 = unlimited): {qps}")
        return self

    def to_toml(self):
        """(ref: ctl/generate_config.go:39-44)."""
        hosts = ", ".join(f'"{h}"' for h in (self.cluster["hosts"]
                                             or [self.bind]))
        buckets = ", ".join(
            str(float(b)) for b in self.metrics["histogram-buckets"])
        return f"""data-dir = "{self.data_dir}"
bind = "{self.bind}"
max-writes-per-request = {self.max_writes_per_request}
host-bytes = {self.host_bytes}
max-body-size = {self.max_body_size}
drain-timeout = {self.drain_timeout}
log-format = "{self.log_format}"

[cluster]
  poll-interval = {self.cluster['poll-interval']}
  replicas = {self.cluster['replicas']}
  hosts = [{hosts}]
  long-query-time = {self.cluster['long-query-time']}
  type = "{self.cluster['type']}"
  epoch-probe-ttl = {self.cluster['epoch-probe-ttl']}
  rebalance-stream-concurrency = {self.cluster['rebalance-stream-concurrency']}
  rebalance-bandwidth = {self.cluster['rebalance-bandwidth']}
  rebalance-drain-timeout = {self.cluster['rebalance-drain-timeout']}
  hedge-reads = {str(self.cluster['hedge-reads']).lower()}
  replica-routing = {str(self.cluster['replica-routing']).lower()}
  hedge-ratio = {self.cluster['hedge-ratio']}
  hedge-burst = {self.cluster['hedge-burst']}
  hedge-delay-ms = {self.cluster['hedge-delay-ms']}
  hedge-delay-factor = {self.cluster['hedge-delay-factor']}
  hedge-headroom = {self.cluster['hedge-headroom']}
  hedge-max-per-request = {self.cluster['hedge-max-per-request']}

[anti-entropy]
  interval = {self.anti_entropy['interval']}

[tls]
  certificate = "{self.tls['certificate']}"
  key = "{self.tls['key']}"
  skip-verify = {str(self.tls['skip-verify']).lower()}

[metric]
  service = "{self.metric['service']}"
  host = "{self.metric['host']}"
  poll-interval = {self.metric['poll-interval']}
  diagnostics = {str(self.metric['diagnostics']).lower()}

[metrics]
  histograms = {str(self.metrics['histograms']).lower()}
  histogram-buckets = [{buckets}]
  collector-interval = {self.metrics['collector-interval']}
  cluster-aggregation = {str(self.metrics['cluster-aggregation']).lower()}

[executor]
  plan-cache-entries = {self.executor['plan-cache-entries']}
  coalesce-max-wait-us = {self.executor['coalesce-max-wait-us']}
  coalesce-max-group = {self.executor['coalesce-max-group']}
  coalesce-compressed = {str(self.executor['coalesce-compressed']).lower()}
  coalesce-densify-bytes = {self.executor['coalesce-densify-bytes']}

[planner]
  enabled = {str(self.planner['enabled']).lower()}
  reorder = {str(self.planner['reorder']).lower()}
  short-circuit = {str(self.planner['short-circuit']).lower()}
  tier-select = {str(self.planner['tier-select']).lower()}
  explore-stride = {self.planner['explore-stride']}

[storage]
  container-formats = {str(self.storage['container-formats']).lower()}

[ingest]
  enabled = {str(self.ingest['enabled']).lower()}
  max-batch-bits = {self.ingest['max-batch-bits']}

[observe]
  enabled = {str(self.observe['enabled']).lower()}
  kernel-sample-rate = {self.observe['kernel-sample-rate']}
  heatmap-half-life = {self.observe['heatmap-half-life']}
  heatmap-top-k = {self.observe['heatmap-top-k']}

[profile]
  sample-hz = {self.profile['sample-hz']}
  device-trace-dir = "{self.profile['device-trace-dir']}"

[mesh]
  enabled = {str(self.mesh['enabled']).lower()}
  group = "{self.mesh['group']}"
  stack-bytes = {self.mesh['stack-bytes']}

[slo]
  enabled = {str(self.slo['enabled']).lower()}
""" + "".join(
            f"""
  [slo.objectives.{prio}]
    latency-ms = {float(obj['latency-ms'])}
    target = {float(obj.get('target', 99.9))}
    availability = {float(obj.get('availability',
                                  obj.get('target', 99.9)))}
"""
            for prio, obj in sorted(
                (self.slo.get("objectives") or {}).items())) + f"""
[trace]
  enabled = {str(self.trace['enabled']).lower()}
  slow-threshold = {self.trace['slow-threshold']}
  ring-size = {self.trace['ring-size']}
  slow-ring-size = {self.trace['slow-ring-size']}

[qos]
  enabled = {str(self.qos['enabled']).lower()}
  max-concurrent = {self.qos['max-concurrent']}
  queue-length = {self.qos['queue-length']}
  queue-timeout = {self.qos['queue-timeout']}
  default-deadline = {self.qos['default-deadline']}
  client-qps = {self.qos['client-qps']}
  client-burst = {self.qos['client-burst']}
  breaker-threshold = {self.qos['breaker-threshold']}
  breaker-cooldown = {self.qos['breaker-cooldown']}
""" + (("\n  [qos.quotas]\n" + "".join(
            f'  "{k}" = {float(v)}\n'
            for k, v in sorted(self.qos.get("quotas", {}).items())))
       if self.qos.get("quotas") else "") + f"""
[autopilot]
  enabled = {str(self.autopilot['enabled']).lower()}
  dry-run = {str(self.autopilot['dry-run']).lower()}
  interval = {self.autopilot['interval']}
  placement = {str(self.autopilot['placement']).lower()}
  memory = {str(self.autopilot['memory']).lower()}
  slo = {str(self.autopilot['slo']).lower()}
  min-dwell = {self.autopilot['min-dwell']}
  max-actions-per-window = {self.autopilot['max-actions-per-window']}
  window = {self.autopilot['window']}
  heat-imbalance = {self.autopilot['heat-imbalance']}
  memory-headroom = {self.autopilot['memory-headroom']}

[faults]
  enabled = {str(self.faults['enabled']).lower()}
  spec = "{self.faults['spec']}"
"""
