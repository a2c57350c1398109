"""What the span-level metrics' readers share (PR 24).

The server's ``?profile=true`` block is a flat list of spans, each with
``name``, ``spanId``, ``parentId``, ``durationMs`` and ``tags``. While a
device capture is armed a span also carries ``startNs``, its start on
the server's monotonic clock, and the block carries ``capture``:
``{"dir", "id"}``. The capture's host plane then holds one annotation
``pilosa:anchor:<id>:<ns>`` whose name is that clock's reading as it
opened, and one ``pilosa:<span name>`` per span. The anchor's own start
on the trace's clock minus the reading in its name is the offset that
puts every span on the host plane's clock. The device's program
launches (``ctx.trace["launches"]``) keep the device's clock, which
``aligned`` brings onto the host's by causality.

Every reader returns None where what it reads is absent: a program
without these spans, a run without a trace, a trace without an anchor.
"""
import bisect
import json
import sys

from . import layer, xplane

ANCHOR = "pilosa:anchor:"
COUNT_PROGRAMS = "jit_pilosa_count_batched"
PS_PER_MS = 1e9


# ------------------------------------------------- spans and counters

def span_ms(profile, names):
    """Summed duration of one request's spans of these names; None
    when it has none."""
    got = [s["durationMs"] for s in profile.get("spans", ())
           if s["name"] in names and s.get("durationMs") is not None]
    return sum(got) if got else None


def median_span_ms(ctx, names):
    return layer.median_or_none(
        ms for ms in (span_ms(p, names) for _, p in layer.profiles(ctx))
        if ms is not None)


def median_root_tag(ctx, tag):
    roots = (layer.root_span(p) for _, p in layer.profiles(ctx))
    return layer.median_or_none(
        r["tags"][tag] for r in roots if r and tag in r.get("tags", {}))


def resources_sum(ctx, key):
    """A per-query counter summed over the window's profiled requests;
    None where no profile has the key (an older program)."""
    got = [p["resources"][key] for _, p in layer.profiles(ctx)
           if key in p.get("resources", {})]
    return sum(got) if got else None


def module_ms(ctx, prefix):
    """Device milliseconds a launch of the programs whose name starts
    with ``prefix``, from the trace's ``XLA Modules`` line."""
    if not ctx.trace:
        return None
    rows = [v for k, v in ctx.trace["modules"].items()
            if k.startswith(prefix)]
    launches = sum(n for _, n in rows)
    return 1000.0 * sum(s for s, _ in rows) / launches if launches else None


# ------------------------------------------------------ the one clock

def anchor_offset_ps(host_planes, capture_id):
    """Trace clock minus the server's monotonic clock, in picoseconds,
    from the anchor of this capture; None without one."""
    want = f"{ANCHOR}{capture_id}:"
    for plane in host_planes:
        for line in plane["lines"]:
            for name, start_ps, _ in line["events"]:
                if name.startswith(want):
                    return start_ps - int(name[len(want):]) * 1000
    return None


def annotations(host_planes, name):
    """Starts (ps, trace clock) of the host annotations of one name."""
    return sorted(start for plane in host_planes for line in plane["lines"]
                  for n, start, _ in line["events"] if n == name)


def placed(ctx):
    """The profiled requests whose spans carry ``startNs``, each as a
    list of spans with ``t0`` and ``t1`` in picoseconds on the trace's
    clock; None without a device trace, a capture block or an anchor.
    Read once a run (``ctx`` keeps it): the host plane is decoded in
    plain Python."""
    if hasattr(ctx, "_placed_spans"):
        return ctx._placed_spans
    ctx._placed_spans = ctx._host_planes = None
    capture = next((p["capture"] for _, p in layer.profiles(ctx)
                    if p.get("capture")), None)
    if not ctx.trace or not capture:
        return None
    path = xplane.find_xplane(capture["dir"])
    if not path:
        return None
    planes = xplane.read_planes(path, prefix="/host:")
    offset = anchor_offset_ps(planes, capture["id"])
    if offset is None:
        return None
    out = []
    for _, p in layer.profiles(ctx):
        if (p.get("capture") or {}).get("id") != capture["id"]:
            continue
        spans = [dict(s, t0=s["startNs"] * 1000 + offset,
                      t1=s["startNs"] * 1000 + offset
                      + int(s["durationMs"] * PS_PER_MS))
                 for s in p["spans"] if "startNs" in s]
        if spans:
            out.append(spans)
    ctx._host_planes = planes
    ctx._placed_spans = out or None
    return ctx._placed_spans


def _named(spans, name):
    return next((s for s in spans if s["name"] == name), None)


# The device planes of a trace keep the device's clock, which the
# profiler does not bring onto the host plane's: on a v5e it read 1.9 ms
# early in one capture and 54.6 ms early in the next (PR 24). The reader
# aligns the two by causality: a program cannot start on the device
# before the ``kernel.dispatch`` that launches it has begun, and
# ``kernel.wait`` cannot return before it has ended. Over all requests
# of a capture those give the shift a lower and an upper limit.
PAIR_TOLERANCE_PS = 10 ** 9      # 1 ms: how far a coarse shift may be off


def _paired(wins, launches, starts, shift):
    """{launch index: (dispatch start - launch start, the request)} for
    the requests that have a launch starting, once shifted, between
    1 ms before their dispatch and the end of their wait."""
    out = {}
    for win in wins:
        d0, w1, _ = win
        lo = bisect.bisect_left(starts, d0 - shift - PAIR_TOLERANCE_PS)
        hi = bisect.bisect_right(starts, w1 - shift)
        if lo < hi:
            # Where several lie in reach, the one nearest the shift.
            i = min(range(lo, hi), key=lambda j: abs(d0 - starts[j] - shift))
            gap = d0 - starts[i]
            if i not in out or abs(gap - shift) < abs(out[i][0] - shift):
                out[i] = (gap, win)
    return out


def aligned(ctx):
    """(chains, launches, slack_ps, shift_ps) with the device's
    launches shifted onto the trace's host clock, or None. ``chains``
    is per request (start of ``kernel.dispatch``, start and end of the
    launch it caused, end of ``kernel.fetch``).

    The shift is found in two steps. Coarse: of the shifts that put
    some launch at the start of the middle request's dispatch, the one
    under which most requests have a launch between their dispatch and
    the end of their wait. Fine: the smallest shift that lets no paired
    launch start before its dispatch. So the fastest launch of the
    capture reads a delay of 0 and the others what they took beyond it;
    ``slack_ps`` is how much later the upper limit would allow (every
    delay could be that much longer and every readback that much
    shorter)."""
    if hasattr(ctx, "_aligned"):
        return ctx._aligned
    ctx._aligned = None
    reqs = placed(ctx)
    launches = ctx.trace["launches"] if reqs else None
    if not launches:
        return None
    starts = [s for s, _ in launches]
    wins = []
    for spans in reqs:
        dispatch, wait, fetch = (_named(spans, "kernel." + n)
                                 for n in ("dispatch", "wait", "fetch"))
        if dispatch and wait and fetch:
            wins.append((dispatch["t0"], wait["t1"], fetch["t1"]))
    if not wins:
        return None
    middle = wins[len(wins) // 2][0]
    coarse = max((middle - s for s in starts),
                 key=lambda shift: len(_paired(wins, launches, starts,
                                               shift)))
    pairs = _paired(wins, launches, starts, coarse)
    if not pairs:
        return None
    shift = max(gap for gap, _ in pairs.values())
    upper = min(w1 - sum(launches[i])
                for i, (_, (_, w1, _)) in pairs.items())
    chains = [(d0, launches[i][0] + shift, sum(launches[i]) + shift, f1)
              for i, (_, (d0, _, f1)) in sorted(pairs.items())]
    ctx._aligned = (chains, [(s + shift, d) for s, d in launches],
                    max(0, upper - shift), shift)
    return ctx._aligned


def launch_delay_ms(ctx):
    got = aligned(ctx)
    return got and layer.median_or_none(
        (s - d0) / PS_PER_MS for d0, s, _, _ in got[0])


def readback_ms(ctx):
    got = aligned(ctx)
    return got and layer.median_or_none(
        (f1 - e) / PS_PER_MS for _, _, e, f1 in got[0])


# ------------------------------------------- idle time against spans

def merged(intervals):
    """Sorted disjoint (start, end) covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def overlap_ps(a, b):
    """Length of the intersection of two sorted disjoint lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(launches, first, last):
    """The device's idle intervals inside [first, last]."""
    busy = merged((max(s, first), min(s + d, last))
                  for s, d in launches if s < last and s + d > first)
    out, at = [], first
    for a, b in busy:
        if a > at:
            out.append([at, a])
        at = max(at, b)
    if last > at:
        out.append([at, last])
    return out


def innermost_segments(spans):
    """(name, start, end, is_leaf) pieces that tile one request's
    spans: a leaf whole, a parent where no child of it runs."""
    kids = {}
    for s in spans:
        kids.setdefault(s["parentId"], []).append(s)
    out = []
    for s in spans:
        mine = kids.get(s["spanId"])
        if not mine:
            out.append((s["name"], s["t0"], s["t1"], True))
            continue
        at = s["t0"]
        for c in sorted(mine, key=lambda c: c["t0"]):
            if c["t0"] > at:
                out.append((s["name"], at, min(c["t0"], s["t1"]), False))
            at = max(at, c["t1"])
        if s["t1"] > at:
            out.append((s["name"], at, s["t1"], False))
    return out


def idle_outside_spans_pct(ctx):
    """Of the device's idle time in the traced interval, the share that
    no leaf span of a profiled request covers: between requests, or in
    the self time of a span that has children. Notes on stderr the idle
    seconds by innermost span, and how far the join can be trusted."""
    got = aligned(ctx)
    span = layer.traced_interval(ctx)
    if not got or span is None:
        return None
    _, launches, _, shift = got
    idle = idle_gaps(launches, span[0] + shift, span[1] + shift)
    idle_total = sum(b - a for a, b in idle)
    if not idle_total:
        return None
    pieces, leaves = {}, []
    for spans in placed(ctx):
        for name, a, b, leaf in innermost_segments(spans):
            pieces.setdefault(name if leaf else name + " (self)",
                              []).append((a, b))
            if leaf:
                leaves.append((a, b))
    by_name = {k: overlap_ps(idle, merged(v)) for k, v in pieces.items()}
    by_name["(between requests)"] = idle_total - sum(by_name.values())
    covered = overlap_ps(idle, merged(leaves))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:12]
    print(json.dumps({"phase": "idle_by_span", "idle_s": idle_total / 1e12,
                      "by_innermost_span_s": {k: round(v / 1e12, 6)
                                              for k, v in top},
                      **join_quality(ctx)}), file=sys.stderr, flush=True)
    return 100.0 * (1.0 - covered / idle_total)


def join_quality(ctx):
    """For the run's notes: the distance between each root span's
    mapped start and its own ``pilosa:query`` annotation (both name one
    moment: what the anchor is good to), the self time of spans that
    have children as a share of the roots' total, the device clock's
    shift with the slack its upper limit leaves, and the
    dispatch-to-fetch interval that delay, scan and readback split."""
    reqs = placed(ctx)
    marks = annotations(ctx._host_planes, "pilosa:query")
    skews, unmatched = [], 0
    for spans in reqs:
        root = _named(spans, "query")
        if not root:
            continue
        i = bisect.bisect_left(marks, root["t0"])
        near = [abs(m - root["t0"]) for m in marks[max(0, i - 1):i + 1]]
        if near and min(near) < PAIR_TOLERANCE_PS:
            skews.append(min(near) / 1e6)
        else:
            # A root that was open when the capture stopped: the
            # profiler keeps no annotation that ends after it.
            unmatched += 1
    total = parents = 0
    for spans in reqs:
        parents += sum(b - a for _, a, b, leaf in innermost_segments(spans)
                       if not leaf)
        total += sum(s["t1"] - s["t0"] for s in spans
                     if s["parentId"] is None)
    chains, _, slack, shift = aligned(ctx)
    return {
        "requests_placed": len(reqs),
        "anchor_skew_us": {"median": layer.median_or_none(skews),
                           "max": max(skews) if skews else None,
                           "roots_without_annotation": unmatched},
        "parent_self_share_pct": 100.0 * parents / total if total else None,
        "device_clock_shift_ms": shift / PS_PER_MS,
        "device_clock_slack_ms": slack / PS_PER_MS,
        "dispatch_to_fetch_ms": layer.median_or_none(
            (f1 - d0) / PS_PER_MS for d0, _, _, f1 in chains),
    }
