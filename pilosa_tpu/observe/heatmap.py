"""Slice/row access heatmaps: exponentially-decayed heat per slice
and per (frame, row), with bounded top-K exposition.

Fed from the places that touch INDIVIDUAL slices and rows — the
executor's per-slice serial loop, fragment row reads (dense and
compressed serving tiers), and container conversions. The batched
warm path (stack-cache hit, one fused program over every slice)
touches no individual slice and records one per-index query count
instead: uniform access carries no skew signal, and a per-slice loop
there would re-grow exactly the per-query walk PR 6 killed.

Heat is an exponentially-decayed score: on each touch the previous
score decays by ``0.5 ** (elapsed / half_life)`` and the touch's
weight is added — recent access dominates, old heat fades to zero
without a sweeper thread. Maps are bounded (lowest-score halves are
pruned on overflow) and the EXPOSITION is top-K only: a 100B-column
index must not mint a Prometheus series per row
(``pilosa_slice_heat``/``pilosa_row_heat`` stay ≤ K series each; the
full bounded table is JSON at ``GET /debug/heatmap``).

Cluster view: the top-K series ride this node's /metrics, so the
existing ``/cluster/metrics`` fan-out merges every node's hot spots
with ``node=`` labels — the rebalancer reads cluster-wide heat from
one scrape.

Writes are GIL-atomic dict/list updates (the kerneltime discipline):
no lock on the touch path.
"""
import time

DEFAULT_HALF_LIFE = 300.0
DEFAULT_TOP_K = 20
MAX_ENTRIES = 8192
# Stride for the per-row-read touch paths when server-enabled: the
# fragment read layer records 1-in-N reads with weight N (the statsd
# |@rate idiom) so the hottest serving loops pay one counter
# increment per read, not decay math — heat converges to the same
# scores, just at N-read granularity. The deterministic counter
# guarantees a sample every N touches (no sampling droughts).
DEFAULT_STRIDE = 16
# Every read below this tick samples exactly (weight 1): small
# workloads (tests, fresh boots) see heat immediately; the stride
# kicks in once the process is genuinely busy.
WARM_TOUCHES = 64


class _HeatTable:
    """One decayed-score map: key -> [score, weight_score, last]."""

    __slots__ = ("half_life", "_clock", "_t")

    def __init__(self, half_life, clock):
        self.half_life = half_life
        self._clock = clock
        self._t = {}

    def __len__(self):
        return len(self._t)

    def touch(self, key, n=1, weight=0):
        now = self._clock()
        e = self._t.get(key)
        if e is None:
            if len(self._t) >= MAX_ENTRIES:
                self._prune(now)
            self._t.setdefault(key, [float(n), float(weight), now])
            return
        decay = 0.5 ** ((now - e[2]) / self.half_life)
        e[0] = e[0] * decay + n
        e[1] = e[1] * decay + weight
        e[2] = now

    def _prune(self, now):
        """Halve the table, keeping the hottest (decayed) entries —
        amortized O(n log n) only on overflow, never on the touch
        path steady state."""
        scored = sorted(self._t.items(),
                        key=lambda kv: self._score(kv[1], now),
                        reverse=True)
        self._t = dict(scored[: MAX_ENTRIES // 2])

    def _score(self, e, now):
        return e[0] * (0.5 ** ((now - e[2]) / self.half_life))

    def top(self, k):
        now = self._clock()
        scored = [(key, self._score(e, now),
                   e[1] * (0.5 ** ((now - e[2]) / self.half_life)))
                  for key, e in list(self._t.items())]
        scored.sort(key=lambda t: -t[1])
        return scored[:k], len(scored)


class Heatmap:
    """Process-wide heat tier: per-slice and per-(frame, row) tables
    plus flat per-index query/conversion counters."""

    enabled = True

    def __init__(self, half_life=DEFAULT_HALF_LIFE, top_k=DEFAULT_TOP_K,
                 stride=1, _clock=time.monotonic):
        self.top_k = max(1, int(top_k))
        self.half_life = max(1e-9, float(half_life))
        self.stride = max(1, int(stride))
        self._tick = 0
        self._slices = _HeatTable(self.half_life, _clock)
        self._rows = _HeatTable(self.half_life, _clock)
        self._queries = {}      # index -> queries observed (undecayed)
        self._conversions = {}  # (index, frame) -> conversions

    def touch_read(self, index, frame, row_id, slice_num, weight=0):
        """ONE stride-sampled hook for the fragment read layer: row
        and slice heat from a single method call (the hot serving
        loops' hook, so one call layer a read). ``weight`` is the
        UNSCALED bytes of one
        read; sampling scales it. The first WARM_TOUCHES reads sample
        exactly, so a fresh process shows heat before the stride
        engages."""
        self._tick = t = self._tick + 1
        if t > WARM_TOUCHES and t % self.stride:
            return
        w = self.stride if t > WARM_TOUCHES else 1
        self._rows.touch((index, frame, row_id), w, weight * w)
        self._slices.touch((index, slice_num), w, weight * w)

    def touch_slice(self, index, slice_num, n=1, weight=0):
        """Accesses touching an individual slice; ``weight`` is bytes
        touched when the caller knows it (both pre-scaled by the
        caller when stride-sampled)."""
        self._slices.touch((index, slice_num), n, weight)

    def touch_row(self, index, frame, row_id, n=1, weight=0):
        """Row-block reads (dense words or a compressed container)."""
        self._rows.touch((index, frame, row_id), n, weight)

    def note_query(self, index, n_slices):
        """One uniform batched query over ``n_slices`` slices — the
        warm-path aggregate (no per-slice skew to record)."""
        self._queries[index] = self._queries.get(index, 0) + 1

    def note_conversion(self, index, frame, n=1):
        """Container format churn, attributed to its (index, frame)."""
        key = (index, frame)
        self._conversions[key] = self._conversions.get(key, 0) + n

    # ------------------------------------------------- read surfaces

    def snapshot(self):
        """/debug/heatmap: decayed top-K of both tables + the flat
        counters."""
        slices, n_slices = self._slices.top(self.top_k)
        rows, n_rows = self._rows.top(self.top_k)
        return {
            "enabled": True,
            "halfLifeSeconds": self.half_life,
            "topK": self.top_k,
            "slices": [
                {"index": k[0], "slice": k[1],
                 "heat": round(score, 3), "bytesHeat": round(w, 1)}
                for k, score, w in slices],
            "rows": [
                {"index": k[0], "frame": k[1], "row": k[2],
                 "heat": round(score, 3), "bytesHeat": round(w, 1)}
                for k, score, w in rows],
            "sliceEntries": n_slices,
            "rowEntries": n_rows,
            "queries": dict(self._queries),
            "conversions": {f"{i}/{f}": n for (i, f), n
                            in list(self._conversions.items())},
        }

    def slice_metrics(self):
        """``pilosa_slice_heat{index=,slice=}`` — top-K ONLY (bounded
        cardinality by construction)."""
        top, _ = self._slices.top(self.top_k)
        out = {}
        for (index, snum), score, w in top:
            out[f"heat;index:{index},slice:{snum}"] = round(score, 3)
            if w:
                out[f"heat_bytes;index:{index},slice:{snum}"] = round(w, 1)
        return out

    def row_metrics(self):
        """``pilosa_row_heat{index=,frame=,row=}`` — top-K ONLY."""
        top, _ = self._rows.top(self.top_k)
        out = {}
        for (index, frame, row), score, w in top:
            tags = f"index:{index},frame:{frame},row:{row}"
            out[f"heat;{tags}"] = round(score, 3)
            if w:
                out[f"heat_bytes;{tags}"] = round(w, 1)
        return out

    def observe_metrics(self):
        """Bookkeeping gauges for the ``pilosa_observe_*`` group."""
        out = {"heatmap_slice_entries": len(self._slices),
               "heatmap_row_entries": len(self._rows)}
        # list() copies: note_query/note_conversion insert new keys
        # lock-free from query threads mid-scrape.
        for index, n in list(self._queries.items()):
            out[f"heatmap_queries_total;index:{index}"] = n
        for (index, frame), n in list(self._conversions.items()):
            out[f"heatmap_conversions_total;index:{index},"
                f"frame:{frame}"] = n
        return out


class NopHeatmap:
    """Disabled tier: one attribute read on every touch path."""

    enabled = False

    def touch_read(self, index, frame, row_id, slice_num, weight=0):
        pass

    def touch_slice(self, index, slice_num, n=1, weight=0):
        pass

    def touch_row(self, index, frame, row_id, n=1, weight=0):
        pass

    def note_query(self, index, n_slices):
        pass

    def note_conversion(self, index, frame, n=1):
        pass

    def snapshot(self):
        return {"enabled": False}

    def slice_metrics(self):
        return {}

    def row_metrics(self):
        return {}

    def observe_metrics(self):
        return {}


NOP = NopHeatmap()
ACTIVE = NOP


def enable(half_life=DEFAULT_HALF_LIFE, top_k=DEFAULT_TOP_K,
           stride=DEFAULT_STRIDE):
    """Install a fresh process-global heatmap (server wiring; never
    downgraded by a later observe-disabled server)."""
    global ACTIVE
    ACTIVE = Heatmap(half_life=half_life, top_k=top_k, stride=stride)
    return ACTIVE


def disable():
    global ACTIVE
    ACTIVE = NOP


def merge_snapshots(per_node):
    """Merge per-node ``snapshot()`` JSON into one cluster view:
    decayed heat sums per (index, slice) and per (index, frame, row),
    query counters add, and the merged lists re-sort by total heat.
    Consumed by ``GET /debug/heatmap?scope=cluster`` and the
    autopilot's placement sensor — structured JSON instead of
    re-parsing the /cluster/metrics Prometheus text. Disabled or
    empty per-node snapshots contribute nothing (the merge reports
    which nodes did under ``"nodes"``)."""
    slices, rows, queries = {}, {}, {}
    nodes = []
    half_life = None
    top_k = 0
    for host, snap in sorted(per_node.items()):
        if not snap or not snap.get("enabled"):
            continue
        nodes.append(host)
        half_life = snap.get("halfLifeSeconds", half_life)
        top_k = max(top_k, snap.get("topK") or 0)
        for index, n in (snap.get("queries") or {}).items():
            queries[index] = queries.get(index, 0) + n
        for ent in snap.get("slices") or []:
            key = (ent["index"], ent["slice"])
            cur = slices.setdefault(
                key, {"index": ent["index"], "slice": ent["slice"],
                      "heat": 0.0, "bytesHeat": 0.0, "nodes": 0})
            cur["heat"] += ent.get("heat") or 0.0
            cur["bytesHeat"] += ent.get("bytesHeat") or 0.0
            cur["nodes"] += 1
        for ent in snap.get("rows") or []:
            key = (ent["index"], ent["frame"], ent["row"])
            cur = rows.setdefault(
                key, {"index": ent["index"], "frame": ent["frame"],
                      "row": ent["row"], "heat": 0.0, "bytesHeat": 0.0,
                      "nodes": 0})
            cur["heat"] += ent.get("heat") or 0.0
            cur["bytesHeat"] += ent.get("bytesHeat") or 0.0
            cur["nodes"] += 1
    out_slices = sorted(slices.values(), key=lambda e: -e["heat"])
    out_rows = sorted(rows.values(), key=lambda e: -e["heat"])
    if top_k:
        out_slices = out_slices[:top_k]
        out_rows = out_rows[:top_k]
    for ent in out_slices + out_rows:
        ent["heat"] = round(ent["heat"], 3)
        ent["bytesHeat"] = round(ent["bytesHeat"], 1)
    return {
        "enabled": bool(nodes),
        "halfLifeSeconds": half_life,
        "topK": top_k,
        "mergedNodes": nodes,
        "slices": out_slices,
        "rows": out_rows,
        "queries": queries,
    }
