"""Data of the taxi deployment: one ride a column, in pickup order.

The configuration's months hold ``monthly_rides`` rides each, one after
the other, so the rides of a month are a contiguous run of columns and a
slice holds one or two months. Frame ``pickup`` (quantum ``YM``) has row
0 set for every ride, as a SetBit with the ride's pickup time leaves it:
in ``standard``, in the ride's year's view and in its month's view
(bitmap containers: the runs are dense). Frames ``passenger_count`` and
``dist_miles`` hold one row a value; each ride's passenger count and
rounded miles are drawn from ``[seed, slice]`` by the configuration's
shares and distance law, independently. A fragment's rows go as ARRAY
containers where no container of the row passes 4,096 columns, else as
bitmap containers; the fragment's ranked cache lists the rows it holds.
Every (frame, view, slice) fragment is restored through
``POST /fragment/data``, generation (three slices at a time) overlapping
the posts (4 posting threads). What goes to the reference is the cube
of ride counts slice x month x passenger count x distance
(``taxi_topn.Cube``), never a bitmap."""
import datetime
import itertools
import json
import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib import cover_bytes_model
from ..reference.taxi_topn import Cube
from .segmentation import CONTAINERS_PER_ROW, SLICE_WIDTH, tar_of
from .ssb import ARRAY_MAX, _roaring, roaring_bitmaps

MAKERS = 3


# ---------------------------------------------------------- the calendar

def month_start(config, k):
    """The first day of month k since the first (k = months is the first
    day after the data)."""
    first = datetime.date.fromisoformat(config["shape"]["first_month"] + "-01")
    k += first.month - 1
    return datetime.date(first.year + k // 12, k % 12 + 1, 1)


def stamp(date):
    return date.strftime("%Y-%m-%dT00:00")


def window(config, a, b):
    """The Range of the months [a, b)."""
    return (f'Range(frame="{config["shape"]["pickup_frame"]}", rowID=0, '
            f'start="{stamp(month_start(config, a))}", '
            f'end="{stamp(month_start(config, b))}")')


def month_bounds(config):
    """int64[months + 1]: the first column of each month, then the
    number of rides."""
    return np.concatenate([[0], np.cumsum(config["shape"]["monthly_rides"],
                                          dtype=np.int64)])


def cover_views(config, a, b):
    """How many views the minimal cover of the months [a, b) reads."""
    return len(cover_bytes_model.cover(
        *(datetime.datetime.combine(month_start(config, k), datetime.time())
          for k in (a, b)), config["shape"]["time_quantum"]))


# -------------------------------------------------- pools and staging

def pools(config):
    shape = config["shape"]
    months = shape["months"]
    passengers = range(len(shape["passenger_pct"]))
    return {
        "window": [window(config, a, b) for a in range(months)
                   for b in range(a + 1, months + 1)],
        "passengers": [str(p) for p in passengers],
        "passenger_pair": [
            f'Union(Bitmap(frame="{shape["passenger_frame"]}", rowID={a}), '
            f'Bitmap(frame="{shape["passenger_frame"]}", rowID={b}))'
            for a, b in itertools.combinations(passengers, 2)],
        "distance": [str(d) for d in range(shape["distance_rows"])],
    }


def _bitmaps(frame, rows):
    return ", ".join(f'Bitmap(frame="{frame}", rowID={r})' for r in rows)


def stage_queries(config):
    """Builds every stack, then compiles every program the window can
    need. (1) Three Counts of a Union, three call shapes seen for the
    first time, which the engine's path model serves batched: the month
    views and each whole year's view, the passenger rows, the distance
    rows. (2) Each form of the mix once a cover size its windows reach
    (the first window of the pool with that many views): the engine
    brings a cover to a bucket of operands, one src program a bucket
    and form, and this meets every bucket without knowing the rule. q3
    and q4 are ONE call shape (frame names are not part of it), whose
    path model looks at the serial path on every other of its queries
    3 to 11: sent two and two (q3, q3, q4, q4, ...) those looks scan
    fragments of both grouped frames, so that the per-fragment scan's
    program of each frame's shape compiles here and not in the window.
    (3) One TopN with explicit ids at each power of two of candidates
    up to each grouped frame's rows: the counts program of a phase is
    one a candidate bucket, phase 2 is given any number of ids, and one
    or two candidates are gathered from each fragment of the frame."""
    shape = config["shape"]
    months = shape["months"]
    passenger, distance = shape["passenger_frame"], shape["distance_frame"]
    views = [window(config, k, k + 1) for k in range(months)]
    views += [window(config, k, k + 12) for k in range(months - 11)
              if month_start(config, k).month == 1]
    out = [f"Count(Union({', '.join(views)}))",
           f"Count(Union({_bitmaps(passenger, range(len(shape['passenger_pct'])))}))",
           f"Count(Union({_bitmaps(distance, range(shape['distance_rows']))}))"]
    by_size = {}
    for a in range(months):
        for b in range(a + 1, months + 1):
            by_size.setdefault(cover_views(config, a, b), window(config, a, b))
    pair = (f'Union(Bitmap(frame="{passenger}", rowID=1), '
            f'Bitmap(frame="{passenger}", rowID=2))')
    q3 = [f'TopN(Intersect({w}, Bitmap(frame="{distance}", rowID=2)), '
          f'frame="{passenger}")' for w in by_size.values()]
    q4 = [f'TopN(Intersect({w}, Bitmap(frame="{passenger}", rowID=1)), '
          f'frame="{distance}")' for w in by_size.values()]
    top10 = [f'TopN(Intersect({w}, {pair}), frame="{distance}", n=10)'
             for w in by_size.values()]
    for i in range(0, len(by_size), 2):
        out += q3[i:i + 2] + q4[i:i + 2] + top10[i:i + 2]
    whole = window(config, 0, months)
    for frame, rows, other in (
            (distance, shape["distance_rows"], passenger),
            (passenger, len(shape["passenger_pct"]), distance)):
        k = 1
        while k < 2 * rows:
            out.append(f'TopN(Intersect({whole}, Bitmap(frame="{other}", '
                       f'rowID=1)), frame="{frame}", '
                       f'ids={list(range(min(k, rows)))})')
            k *= 2
    return out


# ---------------------------------------------------------- the rides

def _law(config):
    """float64[distance rows]: the probability of each rounded mile,
    from the configuration's mixture, cut at the last row and
    renormalised."""
    shape = config["shape"]
    law = shape["distance_law"]
    edges = np.arange(shape["distance_rows"] + 1) - 0.5
    edges[0] = 0.0
    z = (np.log(np.maximum(edges, 1e-300)) - math.log(law["body_median_mi"])) \
        / law["body_sigma"]
    cdf = np.array([0.5 * math.erfc(-x / math.sqrt(2)) for x in z])
    cdf[0] = 0.0
    p = law["body_pct"] * np.diff(cdf)
    lo, hi = law["airport_mi"]
    p[lo:hi + 1] += law["airport_pct"] / (hi + 1 - lo)
    p[0] += law["zero_pct"]
    return p / p.sum()


def _draw(rng, p, n):
    """uint8[n]: n draws from the probabilities ``p`` by inverse CDF."""
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(n), side="right").astype(np.uint8)


def columns_in_slice(config, s):
    return int(min(SLICE_WIDTH,
                   config["shape"]["rides"] - s * SLICE_WIDTH))


def rides(config, seed, s):
    """(month index, passenger count, distance row) of every ride of
    slice s: uint8 arrays, the month from the column alone, the other
    two drawn from ``[seed, s]``."""
    shape = config["shape"]
    n = columns_in_slice(config, s)
    col = s * SLICE_WIDTH + np.arange(n, dtype=np.int64)
    month = (np.searchsorted(month_bounds(config), col, side="right") - 1) \
        .astype(np.uint8)
    rng = np.random.default_rng([seed, s])
    share = np.asarray(shape["passenger_pct"], dtype=np.float64)
    passenger = _draw(rng, share / share.sum(), n)
    distance = _draw(rng, _law(config), n)
    return month, passenger, distance


# -------------------------------------------------------- the fragments

def roaring_codes(code):
    """(rows present, a fragment's roaring file) where column c has its
    one bit in row ``code[c]``: a row with more than 4,096 columns in a
    container goes as bitmap containers, any other as ARRAY containers
    (sorted uint16); a container without a bit is left out."""
    order = np.argsort(code, kind="stable").astype(np.int64)  # by row, column
    cell = code[order].astype(np.int64) * CONTAINERS_PER_ROW + (order >> 16)
    counts = np.bincount(cell)
    at = np.flatnonzero(counts)                  # key order: row, container
    cards = counts[at]
    starts = np.concatenate([[0], np.cumsum(cards)])
    row_of = at // CONTAINERS_PER_ROW
    widest = np.zeros(int(row_of.max()) + 1, dtype=np.int64)
    np.maximum.at(widest, row_of, cards)
    dense = widest[row_of] > ARRAY_MAX
    packed = {int(r): np.packbits(
        np.pad(code == r, (0, SLICE_WIDTH - len(code))), bitorder="little")
        for r in np.unique(row_of[dense])}
    payload, sizes = [], []
    for k, key in enumerate(at.tolist()):
        if dense[k]:
            c = key % CONTAINERS_PER_ROW
            payload.append(packed[key // CONTAINERS_PER_ROW]
                           [c * 8192:(c + 1) * 8192].tobytes())
        else:
            payload.append((order[starts[k]:starts[k + 1]] & 0xFFFF)
                           .astype("<u2").tobytes())
        sizes.append(len(payload[-1]))
    sizes = np.asarray(sizes, dtype=np.int64)
    data = _roaring(at.astype(np.uint64), np.where(dense, 2, 1), cards,
                    np.cumsum(sizes) - sizes, b"".join(payload))
    return np.unique(row_of).tolist(), data


def _run(lo, hi):
    """uint64[1, W64]: row 0 of a fragment whose columns [lo, hi) are
    set."""
    bits = np.zeros(SLICE_WIDTH, dtype=bool)
    bits[lo:hi] = True
    return np.packbits(bits, bitorder="little").view(np.uint64)[None]


def slice_posts(config, s, month):
    """[(frame, view, backup tar)] of slice s's pickup views."""
    shape = config["shape"]
    frame = shape["pickup_frame"]
    n = len(month)
    runs = {"standard": (0, n)}
    for m in np.unique(month).tolist():
        at = np.flatnonzero(month == m)
        start = month_start(config, m)
        for view in (f"standard_{start:%Y}", f"standard_{start:%Y%m}"):
            lo, hi = runs.get(view, (int(at[0]), int(at[-1]) + 1))
            runs[view] = (min(lo, int(at[0])), max(hi, int(at[-1]) + 1))
    return [(frame, view, tar_of(roaring_bitmaps([0], _run(lo, hi)), [0]))
            for view, (lo, hi) in runs.items()]


def load(client, config, seed, note):
    """Create the index and restore every slice. Returns the cube of
    ride counts for the reference."""
    shape = config["shape"]
    index = shape["index"]
    if len(month_bounds(config)) != shape["months"] + 1 \
            or month_bounds(config)[-1] != shape["rides"] \
            or -(-shape["rides"] // SLICE_WIDTH) != shape["slices"]:
        raise ValueError("months, rides and slices disagree")
    client.json("POST", f"/index/{index}", "{}")
    client.json("POST", f"/index/{index}/frame/{shape['pickup_frame']}",
                json.dumps({"options": {"timeQuantum": shape["time_quantum"]}}))
    for frame in (shape["passenger_frame"], shape["distance_frame"]):
        client.json("POST", f"/index/{index}/frame/{frame}", json.dumps(
            {"options": {"cacheType": shape["cache_type"],
                         "cacheSize": shape["cache_size"]}}))
    cube = Cube(shape["slices"], shape["months"], len(shape["passenger_pct"]),
                shape["distance_rows"])
    sent = posts = 0

    def bodies_of(s):
        month, passenger, distance = rides(config, seed, s)
        cube.add(s, month, passenger, distance)
        out = slice_posts(config, s, month)
        for frame, code in ((shape["passenger_frame"], passenger),
                            (shape["distance_frame"], distance)):
            present, data = roaring_codes(code)
            out.append((frame, "standard", tar_of(data, present)))
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
            while len(futs) > 64:
                futs.pop(0).result()
        for f in futs:
            f.result()
    dt = time.perf_counter() - t0
    note("restore", bytesSent=sent, fragments=posts, seconds=round(dt, 2),
         MBps=round(sent / dt / 1e6, 1), rides=int(cube.counts.sum()))
    return {"cube": cube}
