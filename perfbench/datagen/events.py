"""Data of the time-quantum event deployment: who did what on which day.

Frame ``activity`` has quantum ``YMD`` and one row an event type; a bit
(event, user) set with a day's timestamp lands, as a timestamped SetBit
leaves it, in four views: ``standard``, ``standard_YYYY``,
``standard_YYYYMM`` and ``standard_YYYYMMDD``. The generator makes the
DAY bitmaps of a slice from ``[seed, slice]`` (``day_rows``: the
configuration's activity model, each probability a power of two, so a
day is a few ANDs of random words), ORs them into the month, year and
standard views, and restores every (view, slice) fragment, both rows a
body, through ``POST /fragment/data``, which creates the view. Frame
``segment`` holds dense attribute rows as ``segmentation.py`` makes them.
Generation (three slices at a time) overlaps the posts (4 posting
threads). The reference gets the seed and makes the day bitmaps again
(``day_rows``), never a view."""
import datetime
import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .segmentation import CONTAINERS_PER_ROW, W64, backup_tar, tar_of

ARRAY_MAX = 4096            # a roaring container past this is a bitmap
DAY_CHUNK = 16              # days of random words held at a time
MAKERS = 3                  # slices whose bodies are made at a time
COHORTS = [(1, 1), (3, 3), (4, 6)]   # (eighths of the users, -log2 of a
BUY_LOG2 = 3                         # session a day); purchase a session


# ---------------------------------------------------------- the calendar

def first_day(config):
    return datetime.date.fromisoformat(config["shape"]["first_day"])


def n_days(config):
    last = datetime.date.fromisoformat(config["shape"]["last_day"])
    return (last - first_day(config)).days + 1


def day(config, d):
    """The date of day ``d`` since the first (``n_days`` is the day
    after the last)."""
    return first_day(config) + datetime.timedelta(days=d)


def stamp(date):
    """A date's midnight as PQL writes a time."""
    return date.strftime("%Y-%m-%dT00:00")


def window(config, a, b):
    """The operand ``start="...", end="..."`` of the days [a, b)."""
    return (f'start="{stamp(day(config, a))}", '
            f'end="{stamp(day(config, b))}"')


def months(config):
    """[(YYYYMM, first day, day after the last)] of each month that
    holds a day of data, in days since the first, cut to the data."""
    out = {}
    for d in range(n_days(config)):
        month = day(config, d).strftime("%Y%m")
        out[month] = (out.get(month, (d,))[0], d + 1)
    return [(m, lo, hi) for m, (lo, hi) in out.items()]


def view_names(config):
    """Every view of frame ``activity``: standard, the year, the
    months, the days."""
    days = [day(config, d).strftime("%Y%m%d") for d in range(n_days(config))]
    if days[0][:4] != days[-1][:4]:
        raise ValueError("the events span more than one calendar year")
    return (["standard", "standard_" + days[0][:4]]
            + ["standard_" + m for m, _, _ in months(config)]
            + ["standard_" + d for d in days])


# -------------------------------------------------- pools and staging

def pools(config):
    shape = config["shape"]
    n = n_days(config)
    picker = shape["picker_days"]
    week = [window(config, d - 7, d) for d in range(7, n + 1)]
    third = -(-len(week) // 3)
    return {
        "event": [str(r) for r in shape["events"].values()],
        "segment": [str(r) for r in range(shape["segment_rows"])],
        "week": week,
        "week_1of3": week[:third],
        "week_2of3": week[third:2 * third],
        "week_3of3": week[2 * third:],
        "month": [window(config, d - k, d) for k in (28, 30)
                  for d in range(k, n + 1)],
        "picked": [window(config, a, a + k)
                   for k in range(picker[0], min(picker[1], n) + 1)
                   for a in range(n - k + 1)],
    }


def _range(config, event, window_text):
    return (f'Range(frame="{config["shape"]["activity_frame"]}", '
            f'rowID={event}, {window_text})')


def _segment(config, s):
    return f'Bitmap(frame="{config["shape"]["segment_frame"]}", rowID={s})'


def day_only_window(config, views):
    """Days [a, b) whose cover is ``views`` day views and no month: from
    the second day of the first month that has 30 days of data on, which
    holds for up to 57 views (one more and a 28-day month is whole)."""
    lo = next(lo for _, lo, hi in months(config) if hi - lo >= 30)
    if views > 57 or lo + 1 + views > n_days(config):
        raise ValueError(f"no {views} day views from day {lo + 1} on")
    return lo + 1, lo + 1 + views


def stage_queries(config):
    """Builds every stack, then compiles every program the window can
    need. (1) One Count an event over a Union of Ranges that touch every
    view of the frame (each month's days as [first, last) and [last,
    next), the months, the year, and the row's ``standard`` view as a
    Bitmap), and one over all segment rows: three requests of two call
    shapes seen for the first time, which the engine's path model serves
    batched, so all (2 x views + segment rows) stacks are built. (2)
    ``settle`` weeks of the window's own call shape, so that the path
    model has done its exploring before (3) one window of day views
    alone at each cover bucket, once with the sparsest event and the
    densest segment row and once the other way round: the planner puts
    the smaller operand of an Intersect first, so a bucket has a program
    an order. Then the retention form."""
    shape, staging = config["shape"], config["staging"]
    events = list(shape["events"].values())
    every_view = []
    for _, lo, hi in months(config):
        whole = day(config, lo).day == day(config, hi).day == 1
        every_view += [window(config, a, b) for a, b in (
            [(lo, hi - 1), (hi - 1, hi)] if whole else [(lo, hi)])]
    first = day(config, 0).replace(day=1)
    after = (day(config, n_days(config) - 1).replace(day=28)
             + datetime.timedelta(days=4)).replace(day=1)
    every_view += [
        f'start="{stamp(first)}", end="{stamp(after)}"',          # months
        f'start="{first.year}-01-01T00:00", '
        f'end="{first.year + 1}-01-01T00:00"']                    # the year
    out = []
    for event in events:
        kids = [_range(config, event, w) for w in every_view]
        kids.append(f'Bitmap(frame="{shape["activity_frame"]}", '
                    f'rowID={event})')
        out.append(f'Count(Union({", ".join(kids)}))')
    segments = ", ".join(_segment(config, s)
                         for s in range(shape["segment_rows"]))
    out.append(f"Count(Union({segments}))")

    dense, sparse = staging["densest_segment"], staging["sparsest_segment"]
    busy, rare = staging["densest_event"], staging["sparsest_event"]

    def form(event, a, b, s):
        return (f"Count(Intersect({_range(config, event, window(config, a, b))}"
                f", {_segment(config, s)}))")

    out += [form(busy, d, d + 7, dense) for d in range(staging["settle"])]
    for views in staging["cover_views"]:
        a, b = day_only_window(config, views)
        out += [form(rare, a, b, dense), form(busy, a, b, sparse)]
    out.append(
        f"Count(Intersect({_range(config, events[0], window(config, 0, 7))}, "
        f"{_range(config, events[1], window(config, 1, 8))}, "
        f"{_segment(config, dense)}))")
    return out


# ---------------------------------------------------------------- the bits

def _and(words):
    """AND of the random words along axis 1: a bit is set with
    probability 2^-(number of words)."""
    return np.bitwise_and.reduce(words, axis=1)


def day_rows(config, seed, s):
    """uint64[events, days, W64]: slice s's day bitmaps, from the seed.
    A user's cohort comes from three random bits (7: daily, 4-6: weekly,
    0-3: rare); on a day a user has a session with the cohort's
    probability (2^-1, 2^-3, 2^-6), and a session day is a purchase day
    with probability 2^-3, days independent."""
    model = config["shape"]["activity_model"]
    if ([(c["eighths"], c["session_log2"]) for c in model["cohorts"]],
            model["purchase_given_session_log2"]) != (COHORTS, BUY_LOG2):
        raise ValueError(f"day_rows builds the cohorts {COHORTS} (eighths, "
                         f"-log2 of a session a day) and 2^-{BUY_LOG2} only")
    rng = np.random.default_rng([seed, s])
    c = rng.integers(0, 1 << 64, size=(3, W64), dtype=np.uint64)
    daily = c[0] & c[1] & c[2]
    weekly_or_daily = c[2]
    n = n_days(config)
    out = np.empty((2, n, W64), dtype=np.uint64)
    for d0 in range(0, n, DAY_CHUNK):
        d1 = min(d0 + DAY_CHUNK, n)
        w = rng.integers(0, 1 << 64, size=(d1 - d0, 6 + BUY_LOG2, W64),
                         dtype=np.uint64)
        # One word for the daily, three for the weekly, six for the rare.
        session = w[:, 0] & (daily | (_and(w[:, 1:3]) & (
            weekly_or_daily | _and(w[:, 3:6]))))
        out[0, d0:d1] = session
        out[1, d0:d1] = session & _and(w[:, 6:])
    return out


def segment_rows(config, seed, s):
    """uint64[segment_rows, W64]: slice s of the dense attribute rows;
    row r is the AND of ``and_depths[r % 3]`` random words."""
    shape = config["shape"]
    depths = [shape["and_depths"][r % len(shape["and_depths"])]
              for r in range(shape["segment_rows"])]
    rng = np.random.default_rng([seed, s, 1])
    raw = rng.integers(0, 1 << 64, size=(sum(depths), W64), dtype=np.uint64)
    ends = np.cumsum(depths)
    return np.stack([np.bitwise_and.reduce(raw[e - d:e], axis=0)
                     for d, e in zip(depths, ends)])


def roaring(words):
    """A fragment's roaring file from uint64[rows, W64], row id = index:
    a row whose fullest container holds up to 4,096 columns goes as ARRAY
    containers (sorted uint16), any other as bitmap containers; a
    container without a bit is left out. (Uniform data: the containers of
    one row are alike, so the row decides.)"""
    keys, types, counts, sizes, payload = [], [], [], [], []
    for r, row in enumerate(words):
        cards = np.bitwise_count(row.reshape(CONTAINERS_PER_ROW, 1024)) \
            .sum(axis=1)
        at = np.flatnonzero(cards)
        if not len(at):
            continue
        keys.append(r * CONTAINERS_PER_ROW + at)
        counts.append(cards[at])
        if int(cards.max()) <= ARRAY_MAX:
            cols = np.flatnonzero(np.unpackbits(row.view(np.uint8),
                                                bitorder="little"))
            types.append(np.full(len(at), 1))
            sizes.append(2 * cards[at])
            payload.append((cols & 0xFFFF).astype("<u2").tobytes())
        else:
            types.append(np.full(len(at), 2))
            sizes.append(np.full(len(at), 8192))
            payload.append(row.reshape(CONTAINERS_PER_ROW, 1024)[at]
                           .tobytes())
    if not keys:
        return np.array([12348, 0], dtype="<u4").tobytes()
    keys, types, counts, sizes = map(np.concatenate,
                                     (keys, types, counts, sizes))
    n = len(keys)
    hdr = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"), ("n", "<u2")])
    hdr["key"], hdr["typ"], hdr["n"] = keys, types, counts - 1
    offs = (8 + 16 * n + np.cumsum(sizes) - sizes).astype("<u4")
    return (np.array([12348, n], dtype="<u4").tobytes() + hdr.tobytes()
            + offs.tobytes() + b"".join(payload))


def slice_views(config, days):
    """[(view, uint64[events, W64])] of one slice from its day bitmaps,
    in ``view_names``' order: a view is the OR of the days it spans."""
    by_month = [np.bitwise_or.reduce(days[:, lo:hi], axis=1)
                for _, lo, hi in months(config)]
    year = np.bitwise_or.reduce(by_month, axis=0)
    rows = [year, year] + by_month + [days[:, d]
                                      for d in range(days.shape[1])]
    return list(zip(view_names(config), rows))


def load(client, config, seed, note):
    """Create the index and restore every slice. The reference makes the
    day bitmaps again from the seed: nothing of the data is handed on."""
    shape = config["shape"]
    index = shape["index"]
    activity, segment = shape["activity_frame"], shape["segment_frame"]
    client.json("POST", f"/index/{index}", "{}")
    client.json("POST", f"/index/{index}/frame/{activity}", json.dumps(
        {"options": {"timeQuantum": shape["time_quantum"]}}))
    client.json("POST", f"/index/{index}/frame/{segment}", "{}")
    events = list(shape["events"].values())
    if events != list(range(len(events))):
        raise ValueError("event rows are 0..n-1 in this generator")
    sent = posts = 0

    def bodies_of(s):
        out = [(activity, view, tar_of(roaring(rows), events))
               for view, rows in slice_views(config,
                                             day_rows(config, seed, s))]
        out.append((segment, "standard", backup_tar(
            range(shape["segment_rows"]), segment_rows(config, seed, s))))
        return out

    def post(frame, view, s, tar):
        client.request("POST", f"/fragment/data?index={index}&frame={frame}"
                               f"&view={view}&slice={s}", tar)

    t0 = time.perf_counter()
    n = shape["slices"]
    with ThreadPoolExecutor(MAKERS) as makers, \
            ThreadPoolExecutor(4) as posters:
        made = [makers.submit(bodies_of, s) for s in range(min(MAKERS, n))]
        futs = []
        for s in range(n):
            bodies = made.pop(0).result()
            if s + MAKERS < n:
                made.append(makers.submit(bodies_of, s + MAKERS))
            for frame, view, tar in bodies:
                sent += len(tar)
                posts += 1
                futs.append(posters.submit(post, frame, view, s, tar))
            while len(futs) > 256:
                futs.pop(0).result()
        for f in futs:
            f.result()
    dt = time.perf_counter() - t0
    note("restore", bytesSent=sent, fragments=posts, seconds=round(dt, 2),
         MBps=round(sent / dt / 1e6, 1))
    return {"seed": seed}
