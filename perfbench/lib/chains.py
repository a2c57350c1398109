"""A request's device chain, paired by the program's name (PR 35).

``spans.aligned`` pairs a request's ``kernel.dispatch`` with the nearest
launch of ANY program, and knows one family of span names. Here a
launch-site span (``kernel.dispatch`` or ``top.kernel``) says which
program it enqueued (tag ``program``: the jitted function's name, so
``jit_<program>`` is the launch's name on the device's ``XLA Modules``
line up to its ``(``), and a request is paired with the launch of THAT
name inside its own window. A chain is

    (d0, L0, L1, w1, f1)

``d0`` the launch-site span's start, ``L0`` and ``L1`` the launch's
start and end on the device, ``w1`` the end of the wait span
(``block_until_ready`` returned), ``f1`` the end of the fetch span
(``np.asarray`` returned): picoseconds on the trace's host clock.

Left out and counted: a request that ran a look at the loser (span
``path.probe``: up to a launch a slice under it) and one with more than
one launch-site span. The device planes' clock is brought onto the host
plane's by causality over the by-name pairs only: the smallest shift
under which no paired launch starts before its launch-site span; the
slack is what the upper limit (no wait ends before its launch does)
leaves above it. The fastest launch of a capture reads a delay of 0,
so ``launch_delay_ms`` is a LOWER limit and ``completion_ms`` /
``readback_ms`` UPPER limits, each by the same unknown share of the
slack, which the note gives beside them: where every launch is late by
a constant (host operands copied before the runtime enqueues the
program) that constant reads as completion. ``PERF.md`` section 7 says
which host events of the runtime share a ``run_id`` with a launch and
would bound each launch by its own enqueue.

Also here, the path model's own account of its probes
(``/debug/vars`` ``pathModel``: ``probes``, ``probeAborts``,
``probeMs`` a call shape), which needs no trace.

Every reader returns None where what it reads is absent: a program
whose spans carry no ``program`` or whose path model has no
``probeMs``, a run without a trace, a trace without an anchor, no pair.
"""
import bisect
import json
import sys
from types import SimpleNamespace as Context

from . import layer, spans, xplane

SITES = {"kernel.dispatch": ("kernel.wait", "kernel.fetch"),
         "top.kernel": ("top.wait", "top.fetch")}
PROBE = "path.probe"
PS_PER_MS = spans.PS_PER_MS
REACH_PS = spans.PAIR_TOLERANCE_PS   # how far before its site a launch may read
COARSE_WINDOWS = 256                 # windows that score a coarse shift


def named_launches(ctx):
    """{program: [(start_ps, duration_ps)], sorted} from the ``XLA
    Modules`` lines of the capture's device planes, keyed by the
    launch's name up to its ``(``; None without a capture's file.
    Decoded once a run (``ctx`` keeps it)."""
    if hasattr(ctx, "_named_launches"):
        return ctx._named_launches
    ctx._named_launches = None
    capture = next((p["capture"] for _, p in layer.profiles(ctx)
                    if p.get("capture")), None)
    path = capture and xplane.find_xplane(capture["dir"])
    if not path:
        return None
    out = {}
    for plane in xplane.read_planes(path):
        for line in plane["lines"]:
            if line["name"] == "XLA Modules":
                for name, start, dur in line["events"]:
                    out.setdefault(name.split("(")[0], []).append(
                        (start, dur))
    for rows in out.values():
        rows.sort()
    ctx._named_launches = out or None
    return ctx._named_launches


def _after(spans_, name, site):
    """The first span of this name under the site's parent that starts
    after the site does (a span's end is its start plus a duration
    rounded to the microsecond: not to be compared with a start)."""
    got = [s for s in spans_ if s["name"] == name
           and s["parentId"] == site["parentId"] and s["t0"] > site["t0"]]
    return min(got, key=lambda s: s["t0"]) if got else None


def windows(reqs):
    """(wins, probes_left_out, multi_launch_left_out) over the placed
    requests. A window is (d0, d1, w1, f1, program) of a request with
    exactly one launch-site span that names its program, its wait and
    its fetch; a request with none (an older program, an answer from a
    memo) is no candidate and is not counted."""
    wins, probes, multi = [], 0, 0
    for spans_ in reqs:
        if any(s["name"] == PROBE for s in spans_):
            probes += 1
            continue
        sites = [s for s in spans_ if s["name"] in SITES
                 and "program" in s.get("tags", {})]
        if len(sites) > 1:
            multi += 1
        if len(sites) != 1:
            continue
        site = sites[0]
        wait, fetch = (_after(spans_, n, site) for n in SITES[site["name"]])
        if wait and fetch:
            wins.append((site["t0"], site["t1"], wait["t1"], fetch["t1"],
                         "jit_" + site["tags"]["program"]))
    return sorted(wins), probes, multi


def _pairs(wins, launches, shift):
    """[(window, launch)] under ``shift``: a window's launch is the
    first of its program whose shifted start lies between 1 ms before
    its launch-site span and the end of its wait."""
    out = []
    for win in wins:
        d0, _, w1, _, program = win
        mine = launches.get(program, ())
        i = bisect.bisect_left(mine, (d0 - shift - REACH_PS,))
        if i < len(mine) and mine[i][0] <= w1 - shift:
            out.append((win, mine[i]))
    return out


def _coarse_shift(wins, launches):
    """Of the shifts that put a launch of the middle window's program
    at that window's start, the one under which most windows (a spread
    sample of them) have a launch of their own program in reach."""
    d0, program = wins[len(wins) // 2][0], wins[len(wins) // 2][4]
    sample = wins[::max(1, len(wins) // COARSE_WINDOWS)]
    return max((d0 - s for s, _ in launches.get(program, ())),
               key=lambda shift: len(_pairs(sample, launches, shift)),
               default=None)


def by_name(ctx):
    """{"chains", "shift_ps", "slack_ps", "paired", "unpaired",
    "probes_left_out", "multi_launch_left_out"}, or None. Notes the
    counts and the clock on stderr, once a run."""
    if hasattr(ctx, "_chains_by_name"):
        return ctx._chains_by_name
    ctx._chains_by_name = None
    reqs = spans.placed(ctx)
    launches = named_launches(ctx) if reqs else None
    if not launches:
        return None
    wins, probes, multi = windows(reqs)
    if not wins:
        return None
    shift = _coarse_shift(wins, launches)
    if shift is None:
        return None
    # The smallest shift that lets no paired launch start before its
    # site; the pairs are then taken again under it, until they stand.
    pairs = []
    for _ in range(4):
        pairs = _pairs(wins, launches, shift)
        if not pairs:
            return None
        fine = max(win[0] - launch[0] for win, launch in pairs)
        if fine == shift:
            break
        shift = fine
    upper = min(win[2] - sum(launch) for win, launch in pairs)
    out = {"chains": [(d0, s + shift, s + d + shift, w1, f1)
                      for (d0, _, w1, f1, _), (s, d) in pairs],
           "site_ends": [d1 for (_, d1, _, _, _), _ in pairs],
           "shift_ps": shift, "slack_ps": max(0, upper - shift),
           "paired": len(pairs), "unpaired": len(wins) - len(pairs),
           "probes_left_out": probes, "multi_launch_left_out": multi}
    ctx._chains_by_name = out
    med = layer.median_or_none
    print(json.dumps({
        "phase": "chains_by_name",
        **{k: out[k] for k in ("paired", "unpaired", "probes_left_out",
                               "multi_launch_left_out")},
        "device_clock_shift_ms": shift / PS_PER_MS,
        "device_clock_slack_ms": out["slack_ps"] / PS_PER_MS,
        # What the three parts of a chain must add up to, and whether
        # the program starts before its launch-site span has ended.
        "site_to_wait_end_ms": med((w1 - d0) / PS_PER_MS
                                   for d0, _, _, w1, _ in out["chains"]),
        "scan_ms": med((e - s) / PS_PER_MS
                       for _, s, e, _, _ in out["chains"]),
        "launch_after_site_end_ms": med(
            (c[1] - d1) / PS_PER_MS
            for c, d1 in zip(out["chains"], out["site_ends"])),
    }), file=sys.stderr, flush=True)
    return out


def _median_ms(ctx, of):
    got = by_name(ctx)
    return got and layer.median_or_none(of(*c) / PS_PER_MS
                                        for c in got["chains"])


def launch_delay_ms(ctx):
    return _median_ms(ctx, lambda d0, s, e, w1, f1: s - d0)


def completion_ms(ctx):
    return _median_ms(ctx, lambda d0, s, e, w1, f1: w1 - e)


def readback_ms(ctx):
    return _median_ms(ctx, lambda d0, s, e, w1, f1: f1 - e)


def idle_outside_spans_pct(ctx):
    """``spans.idle_outside_spans_pct`` itself, its ``idle_by_span``
    note included, on a copy of the run whose device clock stands under
    the by-name shift: the copy holds this module's chains where that
    reader looks for ``spans.aligned``'s."""
    got = by_name(ctx)
    if not got:
        return None
    shift = got["shift_ps"]
    under = Context(**vars(ctx))
    under._aligned = ([(d0, s, e, f1) for d0, s, e, _, f1 in got["chains"]],
                      [(s + shift, d) for s, d in ctx.trace["launches"]],
                      got["slack_ps"], shift)
    return spans.idle_outside_spans_pct(under)


# ------------------------------------------- the path model's probes

PROBE_KEYS = ("probes", "probeAborts", "probeMs")


def probe_counts(ctx):
    """{"probes", "probeAborts", "probeMs"} over the window: the sum
    over call shapes of ``pathModel``'s counters after it minus before
    it. None where the program's path model has no such keys."""
    after = ctx.after.get("pathModel") or {}
    before = ctx.before.get("pathModel") or {}
    rows = [(row, before.get(shape, {})) for shape, row in after.items()
            if all(k in row for k in PROBE_KEYS)]
    if not rows:
        return None
    return {k: sum(row[k] - was.get(k, 0) for row, was in rows)
            for k in PROBE_KEYS}


def probe_share_pct(ctx):
    """The wall time of the window's probe attempts, by the path
    model's own count, as a share of the window: 0.0 where the keys are
    there and no probe ran. Notes on stderr the counts and, beside
    them, the seconds under ``path.probe`` spans of the profiled
    requests (the same attempts, seen from the trace)."""
    got = probe_counts(ctx)
    if got is None:
        return None
    seen = [s["durationMs"] for _, p in layer.profiles(ctx)
            for s in p.get("spans", ()) if s["name"] == PROBE]
    print(json.dumps({"phase": "path_probes", **got,
                      "probe_spans": len(seen),
                      "probe_span_ms": sum(seen)}),
          file=sys.stderr, flush=True)
    return 100.0 * got["probeMs"] / (ctx.seconds * 1000.0)
