"""Data of the Star Schema Benchmark's flight 1: the lineorder fact table,
one row a column, made from the seed by the specification's distributions
(each recalled value is under ``assumed`` in the configuration's file),
bit-sliced on the host and restored slice by slice through
``POST /fragment/data``: the three measures as plane rows of the field
views of frame ``lo`` (plane i of value minus ``min``, the exists row at
``depth``; bitmap containers), the order date's year as 7 rows of bitmap
containers, its year-month number and week number as 84 and 53 rows of
ARRAY containers. Generation overlaps the posts (4 posting threads, as
``segmentation.py``). What goes to the reference is the lineorder
VALUES, reduced as they are made (``ssb_flight1.Cube``)."""
import json
import struct
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib.sum_bytes_model import field_rows
from ..reference.ssb_flight1 import Cube
from .segmentation import CONTAINERS_PER_ROW, SLICE_WIDTH, tar_of

ARRAY_MAX = 4096            # a roaring container past this is a bitmap


def bit_depth(field):
    """Planes of a field: bits of ``max - min`` (Field.bit_depth); the
    exists row comes on top."""
    return field_rows(field) - 1


def n_days(config):
    shape = config["shape"]
    return int((np.datetime64(shape["last_order_date"])
                - np.datetime64(shape["first_order_date"])).astype(int)) + 1


def date_attributes(config):
    """{date frame: row id of each order day}, by numpy's calendar."""
    days = np.datetime64(config["shape"]["first_order_date"]) \
        + np.arange(n_days(config))
    years = days.astype("datetime64[Y]")
    months = days.astype("datetime64[M]")
    year = years.astype(int) + 1970
    return {"d_year": year,
            "d_yearmonthnum": year * 100
            + (months - years.astype("datetime64[M]")).astype(int) + 1,
            "d_weeknuminyear": (days - years.astype("datetime64[D]"))
            .astype(int) // 7 + 1}


def date_codes(of_day):
    """(row ids in order, uint8 index into them of each order day)."""
    ids, codes = np.unique(of_day, return_inverse=True)
    return ids, codes.astype(np.uint8)


def date_rows(config):
    """{date frame: every row id of the date dimension}: all of
    1992-1998, whether or not an order falls on it."""
    years = range(1992, 1992 + config["shape"]["date_frames"]["d_year"])
    return {"d_year": list(years),
            "d_yearmonthnum": [y * 100 + m for y in years
                               for m in range(1, 13)],
            "d_weeknuminyear": list(range(
                1, config["shape"]["date_frames"]["d_weeknuminyear"] + 1))}


def pools(config):
    rows = date_rows(config)
    return {"year": [str(r) for r in rows["d_year"]],
            "yearmonth": [str(r) for r in rows["d_yearmonthnum"]],
            "week": [str(r) for r in rows["d_weeknuminyear"]],
            "dwindow": [f"[{d - 1}, {d + 1}]" for d in range(1, 10)],
            "qbound": [str(q) for q in range(2, 51)],
            "qwindow": [f"[{q}, {q + 9}]" for q in range(1, 42)]}


def stage_queries(config):
    """One Sum a date frame under a Union of all its rows (a call shape
    of its own each, so the path model serves it batched: its first
    query of a shape always is), over one field each: every date row's
    stack and the three plane stacks are built. Then Q1.1-Q1.3 as the
    specification writes them, which compiles the three forms' programs
    where the harness's ladder would too."""
    shape = config["shape"]
    lo = shape["bsi_frame"]
    out = []
    for (frame, rows), field in zip(date_rows(config).items(),
                                    shape["fields"]):
        union = ", ".join(f'Bitmap(frame="{frame}", rowID={r})'
                          for r in rows)
        out.append(f'Sum(Union({union}), frame="{lo}", field="{field}")')
    tail = f'frame="{lo}", field="{shape["sum_field"]}")'
    rng = f'Range(frame="{lo}", '
    out += [
        f'Sum(Intersect(Bitmap(frame="d_year", rowID=1993), '
        f'{rng}lo_discount >< [1, 3]), {rng}lo_quantity < 25)), {tail}',
        f'Sum(Intersect(Bitmap(frame="d_yearmonthnum", rowID=199401), '
        f'{rng}lo_discount >< [4, 6]), {rng}lo_quantity >< [26, 35])), '
        f'{tail}',
        f'Sum(Intersect(Bitmap(frame="d_weeknuminyear", rowID=6), '
        f'Bitmap(frame="d_year", rowID=1994), '
        f'{rng}lo_discount >< [5, 7]), {rng}lo_quantity >< [26, 35])), '
        f'{tail}']
    return out


def rows_in_slice(config, s):
    return min(SLICE_WIDTH,
               config["shape"]["lineorder_rows"] - s * SLICE_WIDTH)


def lineorder(config, seed, s):
    """The lineorder rows of slice s, from the seed: (order day since
    the first, lo_discount, lo_quantity, lo_extendedprice in cents)."""
    n = rows_in_slice(config, s)
    rng = np.random.default_rng([seed, s])
    day = rng.integers(0, n_days(config), size=n, dtype=np.int32)
    discount = rng.integers(0, 11, size=n, dtype=np.int32)
    quantity = rng.integers(1, 51, size=n, dtype=np.int32)
    partkey = rng.integers(1, config["shape"]["parts"] + 1, size=n,
                           dtype=np.int32)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    return day, discount, quantity, quantity * retail


def planes(values, field):
    """uint64[depth + 1, SLICE_WIDTH / 64]: plane i holds bit i of
    ``value - min`` of each column, the last row the columns that have a
    value (all of ``values``; a part-full slice leaves the rest 0)."""
    depth = bit_depth(field)
    base = values - field["min"]
    if int(base.min()) < 0 or int(base.max()) >> depth:
        raise ValueError(f"a value beyond the field's range: {field}")
    out = np.zeros((depth + 1, SLICE_WIDTH // 8), dtype=np.uint8)
    n = -(-len(base) // 8)
    for i in range(depth):
        out[i, :n] = np.packbits(base & (1 << i) != 0, bitorder="little")
    out[depth, :n] = np.packbits(np.ones(len(base), dtype=bool),
                                 bitorder="little")
    return out.view(np.uint64)


def roaring_bitmaps(row_ids, words):
    """A fragment's roaring file of bitmap containers: row r's words are
    ``words[r]``; a container without a bit is left out."""
    n = len(row_ids) * CONTAINERS_PER_ROW
    blocks = words.reshape(n, 1024)
    cards = np.bitwise_count(blocks).sum(axis=1)
    keys = (np.repeat(np.asarray(row_ids, dtype=np.uint64),
                      CONTAINERS_PER_ROW) * CONTAINERS_PER_ROW
            + np.tile(np.arange(CONTAINERS_PER_ROW, dtype=np.uint64),
                      len(row_ids)))
    keep = cards > 0
    return _roaring(keys[keep], 2, cards[keep],
                    8192 * np.arange(int(keep.sum())),
                    blocks[keep].tobytes())


def roaring_arrays(row_ids, row_of_column):
    """A fragment's roaring file of ARRAY containers: column c has its
    one bit in row ``row_ids[row_of_column[c]]`` (``row_of_column`` is
    uint8, which sorts by radix). No container may pass 4,096 columns
    (a row of a fifty-third or less of the columns stays far below)."""
    cols = np.argsort(row_of_column, kind="stable").astype(np.uint32)
    # Columns a (row, container), in the order of ``cols``: by row, then
    # by column.
    counts = np.bincount(row_of_column.astype(np.int64) * CONTAINERS_PER_ROW
                         + (np.arange(len(cols)) >> 16))
    at = np.flatnonzero(counts)
    keys = (row_ids[at // CONTAINERS_PER_ROW].astype(np.uint64)
            * CONTAINERS_PER_ROW + at % CONTAINERS_PER_ROW)
    counts = counts[at]
    if int(counts.max()) > ARRAY_MAX:
        raise ValueError("an ARRAY container of more than 4,096 columns")
    return _roaring(keys, 1, counts, 2 * (np.cumsum(counts) - counts),
                    cols.astype("<u2").tobytes())


def _roaring(keys, typ, counts, offsets, payload):
    n = len(keys)
    hdr = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"), ("n", "<u2")])
    hdr["key"], hdr["typ"], hdr["n"] = keys, typ, counts - 1
    offs = (8 + 16 * n + offsets).astype("<u4")
    return (struct.pack("<II", 12348, n) + hdr.tobytes() + offs.tobytes()
            + payload)


def slice_posts(config, values, attrs):
    """[(frame, view, backup tar)] of one slice from its lineorder
    values: the three field views and the three date frames."""
    shape = config["shape"]
    day, discount, quantity, price = values
    measures = {"lo_quantity": quantity, "lo_discount": discount,
                "lo_revrate": price * discount}
    out = []
    for name, field in shape["fields"].items():
        mat = planes(measures[name], field)
        out.append((shape["bsi_frame"], "field_" + name,
                    tar_of(roaring_bitmaps(range(len(mat)), mat), [])))
    for frame, (ids, code_of_day) in attrs.items():
        code = code_of_day[day]
        present = np.flatnonzero(np.bincount(code))
        if shape["date_frames"][frame] > 65536 // ARRAY_MAX:
            data = roaring_arrays(ids, code)
        else:       # 16 rows or fewer: a row passes 4,096 a container
            words = np.zeros((len(present), SLICE_WIDTH // 8), dtype=np.uint8)
            packed = np.packbits(code[None, :] == present[:, None], axis=1,
                                 bitorder="little")
            words[:, :packed.shape[1]] = packed
            data = roaring_bitmaps(ids[present], words.view(np.uint64))
        out.append((frame, "standard",
                    tar_of(data, ids[present].tolist())))
    return out


def load(client, config, seed, note):
    """Create the index and restore every slice. Returns the exact cube
    of the lineorder values for the reference."""
    shape = config["shape"]
    index, lo = shape["index"], shape["bsi_frame"]
    client.json("POST", f"/index/{index}", "{}")
    client.json("POST", f"/index/{index}/frame/{lo}",
                json.dumps({"options": {"rangeEnabled": True}}))
    for name, field in shape["fields"].items():
        client.json("POST", f"/index/{index}/frame/{lo}/field/{name}",
                    json.dumps({"type": "int", **field}))
    for frame in shape["date_frames"]:
        client.json("POST", f"/index/{index}/frame/{frame}", "{}")
    attrs = {frame: date_codes(of_day)
             for frame, of_day in date_attributes(config).items()}
    cube = Cube(n_days(config))
    sent = 0

    def post(frame, view, s, tar):
        client.request("POST", f"/fragment/data?index={index}&frame={frame}"
                               f"&view={view}&slice={s}", tar)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(4) as pool:
        futs = []
        for s in range(shape["slices"]):
            values = lineorder(config, seed, s)
            cube.add(*values)
            for frame, view, tar in slice_posts(config, values, attrs):
                sent += len(tar)
                futs.append(pool.submit(post, frame, view, s, tar))
            while len(futs) > 64:
                futs.pop(0).result()
        for f in futs:
            f.result()
    dt = time.perf_counter() - t0
    note("restore", bytesSent=sent, seconds=round(dt, 2),
         MBps=round(sent / dt / 1e6, 1), rows=int(cube.counts.sum()))
    return {"cube": cube}
