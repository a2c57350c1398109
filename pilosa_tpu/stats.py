"""Metrics clients (ref: stats.go:34-252, statsd/).

``StatsClient`` interface {count, gauge, histogram, set, timing,
with_tags}; implementations: nop, expvar-style in-memory (served at
/debug/vars), statsd UDP (DataDog tag extension), and a fan-out multi
client. Selected by ``metric.service`` config
(ref: server/server.go:281-300).

Beyond the reference's expvar/statsd pair this module also carries the
runtime-telemetry layer:

- ``Histogram``/``HistogramSet``: real tagged histograms (configurable
  bucket bounds, per-tag children via ``with_tags``, Prometheus
  ``_bucket``/``_sum``/``_count`` exposition with an explicit ``+Inf``
  bucket). Lock-cheap: one short per-child lock around three integer
  updates per observation; the disabled path is the shared
  ``NOP_HISTOGRAM`` whose ``enabled`` attribute is the only thing hot
  paths read (the NopStatsClient pattern).
- ``prometheus_exposition``: text exposition (version 0.0.4) with
  samples grouped per family, one ``# TYPE`` line per family, and
  NaN/Inf samples skipped.
- ``parse_exposition``/``merge_expositions``: the exposition-format
  reader behind ``GET /cluster/metrics`` — peer scrapes merge into one
  payload with a ``node=`` label per sample.
- ``process_telemetry``: RSS/CPU/GC/thread/fd/uptime gauges for the
  background collector and the diagnostics JSONL.
"""
import bisect
import math
import random
import re
import socket
import threading
import time

from pilosa_tpu import lockcheck


class NopStatsClient:
    def tags(self):
        return []

    def with_tags(self, *tags):
        return self

    def count(self, name, value=1, rate=1.0):
        pass

    def gauge(self, name, value, rate=1.0):
        pass

    def histogram(self, name, value, rate=1.0):
        pass

    def set(self, name, value, rate=1.0):
        pass

    def timing(self, name, seconds, rate=1.0):
        pass


NOP = NopStatsClient()  # shared default for storage objects


class ExpvarStatsClient(NopStatsClient):
    """In-memory counters/gauges, JSON-dumped at /debug/vars
    (ref: stats.go:87-165)."""

    def __init__(self, _tags=None, _root=None, _mu=None):
        self._tags = _tags or []
        self._data = _root if _root is not None else {}
        # The lock travels with the shared data dict so tagged children
        # and their root serialize against each other.
        self._mu = _mu if _mu is not None else lockcheck.register(
            "stats.ExpvarStatsClient._mu", threading.Lock())

    def _key(self, name):
        if self._tags:
            return f"{name};{','.join(sorted(self._tags))}"
        return name

    def tags(self):
        return list(self._tags)

    def with_tags(self, *tags):
        return ExpvarStatsClient(sorted(set(self._tags) | set(tags)),
                                 self._data, self._mu)

    def count(self, name, value=1, rate=1.0):
        with self._mu:
            k = self._key(name)
            self._data[k] = self._data.get(k, 0) + value

    def gauge(self, name, value, rate=1.0):
        with self._mu:
            self._data[self._key(name)] = value

    def histogram(self, name, value, rate=1.0):
        self.gauge(name, value, rate)

    def set(self, name, value, rate=1.0):
        with self._mu:
            self._data[self._key(name)] = value

    def timing(self, name, seconds, rate=1.0):
        self.gauge(name, seconds, rate)

    def snapshot(self):
        with self._mu:
            return dict(self._data)


class StatsdClient(NopStatsClient):
    """UDP statsd with DataDog-style |#tag lists
    (ref: statsd/statsd.go:42-139).

    ``rate`` is honored as CLIENT-SIDE sampling (statsd contract:
    a packet advertising ``|@0.1`` must be one-in-ten of the actual
    events, or the server's rate-correction math over-counts 10x).
    ``_rand`` is the deterministic seam — tests inject a fake."""

    def __init__(self, host="127.0.0.1", port=8125, tags=None, _sock=None,
                 _rand=None):
        self.addr = (host, port)
        self._tags = tags or []
        # Tagged children share the parent's socket (tags ride each
        # payload): one UDP fd per process, not one per storage object.
        self.sock = _sock or socket.socket(socket.AF_INET,
                                           socket.SOCK_DGRAM)
        self._rand = _rand or random.random

    def tags(self):
        return list(self._tags)

    def with_tags(self, *tags):
        return StatsdClient(self.addr[0], self.addr[1],
                            sorted(set(self._tags) | set(tags)),
                            _sock=self.sock, _rand=self._rand)

    def _sampled(self, rate):
        return rate >= 1.0 or self._rand() < rate

    def _send(self, payload):
        try:
            self.sock.sendto(payload.encode(), self.addr)
        except OSError:
            pass

    def _fmt(self, name, value, kind, rate):
        # ':' is meaningful in statsd; replace like the reference's
        # replaceColon (statsd/statsd.go end).
        name = name.replace(":", ".")
        msg = f"{name}:{value}|{kind}"
        if rate < 1.0:
            msg += f"|@{rate}"
        if self._tags:
            msg += "|#" + ",".join(self._tags)
        return msg

    def count(self, name, value=1, rate=1.0):
        if self._sampled(rate):
            self._send(self._fmt(name, value, "c", rate))

    def gauge(self, name, value, rate=1.0):
        if self._sampled(rate):
            self._send(self._fmt(name, value, "g", rate))

    def histogram(self, name, value, rate=1.0):
        if self._sampled(rate):
            self._send(self._fmt(name, value, "h", rate))

    def set(self, name, value, rate=1.0):
        if self._sampled(rate):
            self._send(self._fmt(name, value, "s", rate))

    def timing(self, name, seconds, rate=1.0):
        if self._sampled(rate):
            self._send(self._fmt(name, int(seconds * 1000), "ms", rate))


class MultiStatsClient(NopStatsClient):
    """Fan-out (ref: stats.go:167-252)."""

    def __init__(self, clients):
        self.clients = clients

    def with_tags(self, *tags):
        return MultiStatsClient([c.with_tags(*tags) for c in self.clients])

    def count(self, name, value=1, rate=1.0):
        for c in self.clients:
            c.count(name, value, rate)

    def gauge(self, name, value, rate=1.0):
        for c in self.clients:
            c.gauge(name, value, rate)

    def histogram(self, name, value, rate=1.0):
        for c in self.clients:
            c.histogram(name, value, rate)

    def set(self, name, value, rate=1.0):
        for c in self.clients:
            c.set(name, value, rate)

    def timing(self, name, seconds, rate=1.0):
        for c in self.clients:
            c.timing(name, seconds, rate)


def new_stats_client(service, host="127.0.0.1:8125"):
    """(ref: server/server.go:281-300)."""
    if service in ("expvar", "", None):
        return ExpvarStatsClient()
    if service == "statsd":
        h, _, p = host.rpartition(":")
        return StatsdClient(h or "127.0.0.1", int(p or 8125))
    if service in ("nop", "none"):
        return NopStatsClient()
    raise ValueError(f"unknown metric service: {service}")


class Timer:
    """Context manager emitting a timing histogram."""

    def __init__(self, stats, name):
        self.stats = stats
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.stats.timing(self.name, time.perf_counter() - self.t0)


def _prom_san(name):
    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_esc(value):
    """Label-value escaping per the exposition format: backslash,
    double quote, and newline."""
    return (str(value).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _prom_labels(tagstr):
    """``tag:v,tag2:v2`` -> exposition label list (may be empty)."""
    labels = []
    for tag in filter(None, tagstr.split(",")):
        k, _, v = tag.partition(":")
        labels.append(f'{_prom_san(k)}="{_prom_esc(v)}"')
    return labels


def _prom_render(metric, labels, val):
    return (f"{metric}{{{','.join(labels)}}} {val}"
            if labels else f"{metric} {val}")


def _prom_le(bound):
    return "+Inf" if math.isinf(bound) else str(float(bound))


def prometheus_exposition(snapshot, namespaced=(), histograms=None):
    """Render a flat expvar snapshot ({"Name;tag:v,tag2:v2": number})
    as Prometheus text exposition format (version 0.0.4) — the
    beyond-ref ops surface modern scrapers expect next to the
    reference's expvar/statsd pair (stats.go:87-165). Non-numeric and
    non-finite (NaN/Inf) values are skipped; tag lists become labels.
    ``namespaced`` adds (prefix, dict) groups (governor gauges,
    coalescer counters, QoS, memory); group keys use the same
    ``name;tag:v,...`` convention as snapshot keys, so e.g.
    ``breaker_state;peer:host1`` renders as
    ``pilosa_qos_breaker_state{peer="host1"}``. ``histograms`` is a
    HistogramSet (or iterable of Histogram family roots) rendered as
    real ``histogram``-typed families.

    Samples are grouped per family with exactly one ``# TYPE`` line
    each — tagged children never interleave another family between a
    parent and its labeled series (the exposition format's grouping
    rule, which scrapers like promtool enforce)."""
    # family name -> (type, [sample lines]); insertion-ordered so the
    # snapshot block renders first, then groups, then histograms.
    families = {}

    def fam(metric, kind):
        entry = families.get(metric)
        if entry is None:
            entry = families[metric] = (kind, [])
        return entry[1]

    def add_flat(prefix, data):
        for key in sorted(data or {}):
            val = data[key]
            if isinstance(val, bool) or not isinstance(val, (int, float)):
                continue
            if not math.isfinite(val):
                continue  # NaN/Inf are unparseable sample values
            name, _, tagstr = key.partition(";")
            metric = f"{prefix}{_prom_san(name)}"
            fam(metric, "untyped").append(
                _prom_render(metric, _prom_labels(tagstr), val))

    add_flat("pilosa_", snapshot)
    for prefix, group in namespaced:
        add_flat(f"pilosa_{_prom_san(prefix)}_", group)

    if histograms is not None:
        roots = (histograms.families()
                 if hasattr(histograms, "families") else histograms)
        for root in roots:
            metric = f"pilosa_{_prom_san(root.name)}"
            lines = fam(metric, "histogram")
            for child in root.children():
                lines.extend(child.exposition_lines(metric))

    out = []
    for metric, (kind, lines) in families.items():
        if not lines:
            continue
        out.append(f"# TYPE {metric} {kind}")
        out.extend(lines)
    return "\n".join(out) + "\n"


# ------------------------------------------------------- histograms

# Default bucket bounds (seconds): sub-millisecond kernel dispatches
# through multi-second fan-outs. +Inf is implicit (always emitted).
DEFAULT_HISTOGRAM_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class _NopTimer:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOP_TIMER = _NopTimer()


class NopHistogram:
    """Disabled histogram: hot paths read ``.enabled`` (one attribute)
    and skip; every surface still answers."""

    enabled = False
    __slots__ = ()
    name = "nop"

    def with_tags(self, *tags):
        return self

    def observe(self, value):
        pass

    def time(self):
        return _NOP_TIMER

    def children(self):
        return []

    def snapshot(self):
        return {}


NOP_HISTOGRAM = NopHistogram()


class _HistTimer:
    __slots__ = ("_h", "_t0")

    def __init__(self, h):
        self._h = h

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._h.observe(time.perf_counter() - self._t0)
        return False


class Histogram:
    """One tagged histogram family. The object you hold IS a child
    (the root child has no tags); ``with_tags`` returns the sibling
    for that tag set, creating it once — children share the family's
    bucket bounds, so ``_bucket`` series align across tags.

    ``observe`` is lock-cheap: a bisect over the (immutable) bounds
    outside the lock, then three integer updates inside a per-child
    lock — no allocation, no shared family lock on the hot path."""

    enabled = True
    __slots__ = ("name", "bounds", "_tags", "_family", "_mu",
                 "_counts", "_sum", "_count")

    def __init__(self, name, buckets=DEFAULT_HISTOGRAM_BUCKETS,
                 _tags=(), _family=None):
        self.name = name
        self._tags = tuple(_tags)
        if _family is None:
            bounds = tuple(sorted({float(b) for b in buckets
                                   if math.isfinite(b)}))
            _family = {"bounds": bounds,
                       "mu": lockcheck.register(
                           "stats.Histogram.family_mu",
                           threading.Lock()),
                       "children": {}}
            _family["children"][self._tags] = self
        self._family = _family
        self.bounds = _family["bounds"]
        self._mu = lockcheck.register("stats.Histogram._mu",
                                      threading.Lock())
        # One slot per finite bound + the +Inf overflow slot.
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0
        self._count = 0

    def with_tags(self, *tags):
        key = tuple(sorted(set(self._tags) | set(tags)))
        fam = self._family
        with fam["mu"]:
            child = fam["children"].get(key)
            if child is None:
                child = Histogram(self.name, _tags=key, _family=fam)
                fam["children"][key] = child
        return child

    def observe(self, value):
        v = float(value)
        if v != v:  # NaN would land in an arbitrary bucket
            return
        i = bisect.bisect_left(self.bounds, v)
        with self._mu:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def time(self):
        """Context manager observing elapsed seconds."""
        return _HistTimer(self)

    def children(self):
        """Every child of this family (root first), for exposition."""
        fam = self._family
        with fam["mu"]:
            return [fam["children"][k]
                    for k in sorted(fam["children"], key=str)]

    def _read(self):
        with self._mu:
            return list(self._counts), self._sum, self._count

    def exposition_lines(self, metric):
        """This child's ``_bucket``/``_sum``/``_count`` sample lines
        (cumulative buckets, explicit ``+Inf`` — histogram_quantile()
        returns NaN without it)."""
        counts, total, n = self._read()
        tag_labels = _prom_labels(",".join(self._tags))
        lines = []
        cum = 0
        for bound, c in zip(self.bounds + (math.inf,), counts):
            cum += c
            lines.append(_prom_render(
                f"{metric}_bucket",
                tag_labels + [f'le="{_prom_le(bound)}"'], cum))
        lines.append(_prom_render(f"{metric}_sum", tag_labels,
                                  round(total, 9)))
        lines.append(_prom_render(f"{metric}_count", tag_labels, n))
        return lines

    def snapshot(self):
        """Compact JSON summary for /debug/vars."""
        counts, total, n = self._read()
        return {"tags": list(self._tags), "count": n,
                "sumSeconds": round(total, 6)}


class HistogramSet:
    """Registry of histogram families — one per server, handed to the
    executor/handler/client/qos so /metrics renders every family in
    one place. ``histogram`` is get-or-create by name."""

    enabled = True

    def __init__(self, buckets=None):
        self.default_buckets = (tuple(float(b) for b in buckets)
                                if buckets else DEFAULT_HISTOGRAM_BUCKETS)
        self._mu = lockcheck.register("stats.HistogramSet._mu",
                                      threading.Lock())
        self._fams = {}

    def histogram(self, name, buckets=None):
        with self._mu:
            h = self._fams.get(name)
            if h is None:
                h = self._fams[name] = Histogram(
                    name, buckets or self.default_buckets)
            return h

    def families(self):
        with self._mu:
            return [self._fams[k] for k in sorted(self._fams)]

    def snapshot(self):
        out = {}
        for root in self.families():
            out[root.name] = [c.snapshot() for c in root.children()]
        return out


class NopHistogramSet:
    """Disabled registry: every lookup returns the shared nop child,
    so wiring code never branches."""

    enabled = False

    def histogram(self, name, buckets=None):
        return NOP_HISTOGRAM

    def families(self):
        return []

    def snapshot(self):
        return {}


NOP_HISTOGRAMS = NopHistogramSet()


class WindowedCounts:
    """Multi-dimension counters bucketed per minute over a bounded
    ring — the windowed complement to the cumulative Histogram above
    (cumulative counters cannot answer "in the last 5 minutes"; SLO
    burn rates need exactly that). ``add`` increments named counters
    in the current minute bucket; ``window(seconds)`` sums the last N
    whole minutes. The ring holds one hour plus the in-progress
    minute, so 5m/1h windows both read from one structure.

    Lock-free by the GIL-atomic-increment discipline (kerneltime):
    a lost update under extreme contention costs one count."""

    RING_MINUTES = 61

    __slots__ = ("_clock", "_ring")

    def __init__(self, _clock=time.monotonic):
        self._clock = _clock
        # minute index -> {name: count}; pruned on write.
        self._ring = {}

    def add(self, counts):
        minute = int(self._clock() // 60)
        bucket = self._ring.get(minute)
        if bucket is None:
            bucket = self._ring.setdefault(minute, {})
            if len(self._ring) > self.RING_MINUTES:
                floor = minute - self.RING_MINUTES
                for m in [m for m in self._ring if m < floor]:
                    self._ring.pop(m, None)
        for name, n in counts.items():
            bucket[name] = bucket.get(name, 0) + n

    def window(self, seconds):
        """Summed counters over the trailing ``seconds`` (whole
        minutes, current in-progress minute included)."""
        minute = int(self._clock() // 60)
        lo = minute - max(1, int(seconds // 60)) + 1
        out = {}
        for m, bucket in list(self._ring.items()):
            if lo <= m <= minute:
                for name, n in list(bucket.items()):
                    out[name] = out.get(name, 0) + n
        return out


class QuantileDigest:
    """Streaming latency quantile digest: log2 octaves × 8 linear
    sub-buckets over microseconds, so p50/p95/p99 are readable at any
    instant with ≤~6% relative quantization error and O(1) memory —
    the dependency-free sibling of the cumulative Histogram above for
    surfaces that need *windowed* quantiles (replica vitals), where
    cumulative buckets would never forget an incident.

    Two-generation decay: samples land in the current window; every
    ``window`` seconds the current generation rotates to previous and
    the old previous is dropped. A quantile read merges both, so it
    always covers between one and two windows of traffic and a
    regression fully dominates the read within one rotation — exactly
    the "surface fast, forget fast" contract the slow-replica
    watchdog needs.

    Writes are lock-free by the GIL-atomic list-slot-increment
    discipline (kerneltime, WindowedCounts): a lost update under
    extreme contention costs one sample. Only rotation takes the
    (tiny, leaf) lock, and only once per window."""

    SUB = 8                      # linear sub-buckets per octave
    MAX_OCTAVE = 40              # 2^40 us ≈ 12.7 days — cap, not limit
    SLOTS = (MAX_OCTAVE + 1) * SUB

    __slots__ = ("window", "_clock", "_mu", "_cur", "_prev",
                 "_rotate_at")

    def __init__(self, window=30.0, _clock=time.monotonic):
        self.window = float(window)
        self._clock = _clock
        self._mu = threading.Lock()   # rotation only; unregistered leaf
        self._cur = [0] * self.SLOTS
        self._prev = [0] * self.SLOTS
        self._rotate_at = self._clock() + self.window

    @classmethod
    def _index(cls, seconds):
        us = int(seconds * 1e6)
        if us < 1:
            return 0
        e = us.bit_length() - 1
        if e > cls.MAX_OCTAVE:
            return cls.SLOTS - 1
        sub = ((us - (1 << e)) * cls.SUB) >> e
        return e * cls.SUB + sub

    @classmethod
    def _value(cls, idx):
        """Representative seconds for a slot (sub-bucket midpoint)."""
        e, sub = divmod(idx, cls.SUB)
        lo = (1 << e) * (1.0 + sub / cls.SUB)
        return lo * (1.0 + 0.5 / cls.SUB) / 1e6

    def observe(self, seconds):
        # GIL-atomic slot increment; only rotation swaps the list
        # under the lock.  pilint: disable=guarded-state
        self._cur[self._index(seconds)] += 1

    def maybe_rotate(self, now=None):
        """Rotate generations when the window has elapsed. Returns the
        closed window's ``{"n", "p50", "p99"}`` summary (the
        watchdog's baseline feed), or None when no rotation was due."""
        now = self._clock() if now is None else now
        if now < self._rotate_at:
            return None
        with self._mu:
            if now < self._rotate_at:
                return None
            closed = self._cur
            self._prev = closed
            self._cur = [0] * self.SLOTS
            self._rotate_at = now + self.window
        n = sum(closed)
        return {"n": n,
                "p50": self._quantile_of(closed, n, 0.5),
                "p99": self._quantile_of(closed, n, 0.99)}

    @classmethod
    def _quantile_of(cls, counts, n, q):
        if n <= 0:
            return 0.0
        rank = max(1, math.ceil(q * n))
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                return cls._value(i)
        return cls._value(cls.SLOTS - 1)

    def quantile(self, q):
        """Quantile over the merged current+previous generations."""
        cur, prev = self._cur, self._prev
        counts = [a + b for a, b in zip(cur, prev)]
        return self._quantile_of(counts, sum(counts), q)

    def snapshot(self):
        cur, prev = self._cur, self._prev
        counts = [a + b for a, b in zip(cur, prev)]
        n = sum(counts)
        return {"n": n,
                "p50": self._quantile_of(counts, n, 0.5),
                "p95": self._quantile_of(counts, n, 0.95),
                "p99": self._quantile_of(counts, n, 0.99)}


# -------------------------------------- exposition parsing / merging

# A sample line: name, optional {labels}, value, optional timestamp.
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(-?[0-9.eE+\-]+|NaN|[+-]Inf)"
    r"(?:\s+-?\d+)?\s*$")
_TYPE_RE = re.compile(r"^#\s*TYPE\s+(\S+)\s+(\S+)\s*$")
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def parse_exposition(text):
    """Parse exposition text into an ordered ``{family: {"type": str
    or None, "samples": [(name, labels-or-None, value-str)]}}`` map.
    Histogram sample suffixes fold into their declared family. Raises
    ValueError on an unparseable line — the contract promlint and the
    /cluster/metrics merge rely on."""
    families = {}
    declared = {}

    def fam(name):
        entry = families.get(name)
        if entry is None:
            entry = families[name] = {"type": declared.get(name),
                                      "samples": []}
        return entry

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            m = _TYPE_RE.match(line)
            if m:
                name, kind = m.group(1), m.group(2)
                declared[name] = kind
                fam(name)["type"] = kind
            continue  # HELP/comments pass through unparsed
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {lineno}: unparseable sample: "
                             f"{line!r}")
        name, labels, value = m.group(1), m.group(2), m.group(3)
        base = name
        for suffix in _HIST_SUFFIXES:
            if (name.endswith(suffix)
                    and declared.get(name[:-len(suffix)])
                    in ("histogram", "summary")):
                base = name[:-len(suffix)]
                break
        fam(base)["samples"].append((name, labels, value))
    return families


def merge_expositions(per_node, scrape_errors=None):
    """Merge ``[(node_host, exposition_text), ...]`` into one payload:
    every sample gains a ``node="host"`` label, same-named families
    from different nodes collapse under one ``# TYPE`` line, and
    ``scrape_errors`` ({host: count}) renders as
    ``pilosa_cluster_scrape_errors_total`` so a degraded peer is
    visible in the scrape itself rather than as an HTTP error."""
    merged = {}

    def fam(name, kind):
        entry = merged.get(name)
        if entry is None:
            entry = merged[name] = {"type": kind, "samples": []}
        elif entry["type"] is None:
            entry["type"] = kind
        return entry

    for host, text in per_node:
        node_label = f'node="{_prom_esc(host)}"'
        for name, info in parse_exposition(text).items():
            entry = fam(name, info["type"])
            for sname, labels, value in info["samples"]:
                inner = labels[1:-1] if labels else ""
                tagged = (f"{sname}{{{node_label}"
                          + (f",{inner}" if inner else "") + f"}} {value}")
                entry["samples"].append(tagged)
    for host in sorted(scrape_errors or {}):
        entry = fam("pilosa_cluster_scrape_errors_total", "counter")
        entry["samples"].append(
            f'pilosa_cluster_scrape_errors_total{{node="'
            f'{_prom_esc(host)}"}} {scrape_errors[host]}')

    out = []
    for name, info in merged.items():
        if not info["samples"]:
            continue
        out.append(f"# TYPE {name} {info['type'] or 'untyped'}")
        out.extend(info["samples"])
    return "\n".join(out) + "\n"


# ------------------------------------------------- process telemetry

_PROCESS_START = time.monotonic()


def process_telemetry(started_at=None):
    """Flat process gauges for the background collector (server.py)
    and the diagnostics JSONL: RSS, CPU seconds, GC per-generation
    collection counters, thread count, open fds, uptime. Keys use the
    ``name;tag:v`` convention so the exposition renders labels.
    Best-effort everywhere — a non-procfs platform simply omits fds.
    ``started_at`` is a ``time.monotonic()`` instant: uptime is a
    DURATION — computed from the wall clock it silently jumped with
    every NTP step (a pilint deadline-clock finding)."""
    import gc
    import os
    import sys

    out = {"uptime_seconds": round(
        time.monotonic() - (started_at or _PROCESS_START), 3)}
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        scale = 1 if sys.platform == "darwin" else 1024  # ru_maxrss unit
        out["rss_bytes"] = int(usage.ru_maxrss) * scale
        out["cpu_user_seconds_total"] = round(usage.ru_utime, 3)
        out["cpu_system_seconds_total"] = round(usage.ru_stime, 3)
    except (ImportError, OSError):
        pass
    out["threads"] = threading.active_count()
    for gen, st in enumerate(gc.get_stats()):
        out[f"gc_collections_total;generation:{gen}"] = st.get(
            "collections", 0)
        out[f"gc_collected_total;generation:{gen}"] = st.get(
            "collected", 0)
    try:
        out["open_fds"] = len(os.listdir("/proc/self/fd"))
    except OSError:
        pass
    return out


def device_telemetry():
    """What this process computes on, as JAX reports it: platform,
    device kind and count, per-device ``memory_stats()`` (None where
    the backend keeps none, e.g. CPU), whether the native host runtime
    loaded, and where the persistent compile cache lives. Logged at
    server boot and served as the ``device`` block of /debug/vars, so a
    client can always tell a chip from a CPU fallback."""
    import jax

    from pilosa_tpu import native

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "deviceKind": devs[0].device_kind,
        "deviceCount": len(devs),
        "memoryStats": [d.memory_stats() for d in devs],
        "nativeLoaded": native.available(),
        "compileCacheDir": jax.config.jax_compilation_cache_dir,
    }
