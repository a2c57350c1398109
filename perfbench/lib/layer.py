"""What the per-layer metrics' readers share: each reader is a file of
its own under ``perfbench/metrics/`` and stays a few lines."""
import statistics

from . import bytes_model, peaks, pql


def profiles(ctx):
    """The ``?profile=true`` blocks of the window's answered requests."""
    return [(r, r["profile"]) for r in ctx.log
            if r.get("ok") and r.get("profile")]


def median_or_none(values):
    values = list(values)
    return statistics.median(values) if values else None


def root_span(profile):
    """The span of a ``?profile=true`` block that has no parent: the
    list is flat, children first, each naming its ``parentId``."""
    return next((s for s in profile.get("spans", ())
                 if s.get("parentId") is None), None)


def self_seconds(log):
    """{span name: seconds of self time} over the profiled requests: a
    span's duration minus that of the spans that name it as parent."""
    out = {}
    for r in log:
        spans = (r.get("profile") or {}).get("spans", ())
        kids = {}
        for s in spans:
            kids[s.get("parentId")] = (kids.get(s.get("parentId"), 0.0)
                                       + s["durationMs"])
        for s in spans:
            own = max(0.0, s["durationMs"] - kids.get(s["spanId"], 0.0))
            out[s["name"]] = out.get(s["name"], 0.0) + own / 1000.0
    return out


def http_outside_ms(ctx):
    """Client latency minus the server's root span: socket, HTTP parse,
    routing, JSON encode and the client's own read."""
    return median_or_none(
        (r["t1"] - r["t0"]) * 1000.0 - root_span(p)["durationMs"]
        for r, p in profiles(ctx) if root_span(p))


def plan_ms(ctx):
    return median_or_none(p["resources"]["planMs"]
                          for _, p in profiles(ctx))


def counter_delta(ctx, key):
    return ctx.after[key] - ctx.before[key]


def error_hops(ctx):
    got = profiles(ctx)
    if not got:
        return None
    return sum(1 for _, p in got
               if any(h.endswith(":error")
                      for h in p["resources"]["fallbackChain"]))


def serial_share_pct(ctx):
    """Of the tier notes (``servedBy``) of the profiled requests, the
    share that say ``serial``: the per-slice path, which the engine's
    path model falls back to or parks on."""
    notes = {}
    for _, p in profiles(ctx):
        for tier, n in (p["resources"].get("servedBy") or {}).items():
            notes[tier] = notes.get(tier, 0) + n
    total = sum(notes.values())
    return 100.0 * notes.get("serial", 0) / total if total else None


def device_idle_pct(ctx):
    t = ctx.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


EDGE_PS = int(0.5e12)


def traced_interval(ctx):
    """(from, to) on the trace's clock: the span of the device's events
    less half a second at each end. The trace's clock starts when the
    capture does, inside the POST that arms it, and the moment that
    POST returned (``ctx.trace_t0``, the client's clock) stands for its
    zero: off by less than the POST took. Away from the ends that shift
    swaps requests of one pace for each other; at the very end it would
    swap the traced pace for the untraced one, nine times as fast."""
    t = ctx.trace
    if not t or ctx.trace_t0 is None:
        return None
    first, last = t["span_ps"]
    if last - first > 4 * EDGE_PS:
        first, last = first + EDGE_PS, last - EDGE_PS
    return first, last


def traced_requests(ctx):
    """The answered requests whose middle lies inside the interval."""
    span = traced_interval(ctx)
    if span is None:
        return None
    first, last = (ctx.trace_t0 + x / 1e12 for x in span)
    return [r for r in ctx.log if r.get("ok")
            and first <= (r["t0"] + r["t1"]) / 2 <= last]


def roofline_pct(ctx, bytes_of):
    """The least time HBM could take for the bytes that the requests of
    the interval need, over the device time of every program launched
    in it, on every chip (each chip scans its share of the bytes). The
    bound is bandwidth: a popcount scan does one operation a word."""
    reqs = traced_requests(ctx)
    if not reqs:
        return None
    first, last = traced_interval(ctx)
    device_s = sum(min(s + d, last) - max(s, first)
                   for s, d in ctx.trace["launches"]
                   if s < last and s + d > first) / 1e12
    if not device_s:
        return None
    bw = peaks.peaks_for(ctx.device["deviceKind"])["hbm_bytes_per_s"]
    return 100.0 * sum(bytes_of(r["pql"]) for r in reqs) / bw / device_s


def count_bytes_of(ctx):
    n_slices = ctx.config["shape"]["slices"]
    return lambda q: bytes_model.count_bytes(pql.parse(q).children[0],
                                             n_slices)
