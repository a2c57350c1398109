#!/usr/bin/env python3
"""chip_smoke.py: the served path, end to end, on the accelerator.

The quickest proof that the system still starts on the chip and answers
correctly there. It runs the segmentation deployment of
docs/introduction.md ("When to use it") and docs/examples.md
("Segmentation / audience selection") at benchmarks/e2e_northstar.py's
column count: index ``users``, 954 slices (1.0 B columns), 32 dense
attribute rows (12.5-50 % density) over the frames behavior/device/geo,
4.0 GB packed. Everything goes through the entry points a user calls:

- this parent never initializes a JAX backend. It builds the native
  runtime from source, starts ONE ``python -m pilosa_tpu.cli server``
  child, which owns every visible chip, and drives it over HTTP with a
  NumPy-only client. Data and the oracle come from ``--seed``;
- the dense rows load as a seeded backup through POST /fragment/data,
  the route ``cli restore`` uses; small riders (ARRAY/RUN tag rows, a
  narrow-window dense frame, a BSI field, a YMD time frame) are
  ingested live through POST /index/users/ingest;
- every read is sent with ?profile=true and compared bit-exactly with
  the NumPy oracle; every read shape runs a second time on other rows,
  during which nothing may compile;
- SIGTERM must drain to exit 0, and a restart on the same directory
  must answer the same Count. The sparse frames are evicted then, so
  the compressed container tier serves them, singly and through the
  coalescer's lanes;
- a second child, after the server is gone, compiles the Pallas kernels
  and the ingest pack kernel on the chip and checks them against XLA.

It fails rather than degrades: a platform other than ``tpu`` (without
``--rehearse``), an ``error`` hop in any fallbackChain, a dense Count
not served by the fused tier, a failed width warm, a compile in the
repeat pass, ``nativeLoaded`` false, any mismatch with the oracle or any
non-200 is an exception, and no phase is wrapped in a catch.

``--rehearse`` runs the same script at a tiny size on whatever platform
JAX reports (``JAX_PLATFORMS=cpu python chip_smoke.py --rehearse`` in the
sandbox), with the coalescer and the width warmer pinned on so the
chip-only branches run; the last line then carries ``"rehearsal": true``.

Speeds printed here are smoke observations, not benchmark metrics. The
last line of stdout is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it.
"""
import argparse
import http.client
import io
import itertools
import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tarfile
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

SLICE_WIDTH = 1 << 20
W64 = SLICE_WIDTH // 64          # uint64 words per slice row
CONTAINERS_PER_ROW = 16          # 2^16-bit roaring containers per slice row
FULL_SLICES, FULL_ROWS, FLOOR_ROWS = 954, 32, 16
FRAMES = ("behavior", "device", "geo")
INDEX = "users"
# What the operator of a 16 GB chip would set so that all 32 row stacks
# (125 MB each, 3.7 GiB) stay resident; the default is 2 GiB. Not more:
# the budget bounds the stack cache and, separately, each plan's
# staging, so the coalescer's [K, S, W] copies and TopN's fragment
# mirrors come on top of it. At 8 GiB the 8-client phase exhausted the
# chip's 15.7 GiB (chip run, PR 21).
STACK_BYTES = 5 << 30
DAY0 = 1496318400                # 2017-06-01T12:00Z, the first event day


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


REPORT = {}


def say(phase, **info):
    """One JSON line per phase on stdout; REPORT keeps them for the file."""
    REPORT[phase] = info
    print(json.dumps({"phase": phase, **info}), flush=True)


# ------------------------------------------------------------------ client

class _NoDelay(http.client.HTTPConnection):
    def connect(self):
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


class Client:
    """Keep-alive HTTP client, one connection per thread. Any non-200
    raises: the smoke has no request that may fail."""

    def __init__(self, port, timeout=600):
        self.port = port
        self.timeout = timeout
        self._tls = threading.local()

    def request(self, method, path, body=None):
        conn = getattr(self._tls, "conn", None)
        if conn is None:
            conn = self._tls.conn = _NoDelay("127.0.0.1", self.port,
                                             timeout=self.timeout)
        if isinstance(body, str):
            body = body.encode()
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        data = resp.read()
        check(resp.status == 200,
              f"{method} {path}: HTTP {resp.status}: {data[:400]!r}")
        return data

    def json(self, method, path, body=None):
        return json.loads(self.request(method, path, body) or b"{}")

    def close(self):
        conn = getattr(self._tls, "conn", None)
        if conn is not None:
            conn.close()
            self._tls.conn = None


class Served:
    """Runs profiled queries and keeps the servedBy table, the latency
    of the first and the repeat run of every shape, and the hops."""

    def __init__(self, client):
        self.client = client
        self.table = {}      # shape -> {"servedBy": {...}, "firstMs", ...}
        self._mu = threading.Lock()

    def query(self, pql, shape=None, repeat=False):
        t0 = time.perf_counter()
        out = self.client.json(
            "POST", f"/index/{INDEX}/query?profile=true", pql)
        ms = (time.perf_counter() - t0) * 1000
        res = out["profile"]["resources"]
        chain = res["fallbackChain"]
        check(not any(hop.endswith(":error") for hop in chain),
              f"error hop in fallbackChain {chain} for {pql}")
        compiled = [sp["name"] for sp in out["profile"]["spans"]
                    if sp["tags"].get("first_compile")]
        check(not (repeat and compiled),
              f"{compiled} compiled in the repeat pass for {pql}")
        if shape is not None:
            with self._mu:
                row = self.table.setdefault(
                    shape, {"servedBy": {}, "fallbackChain": []})
                for tier, n in res["servedBy"].items():
                    row["servedBy"][tier] = row["servedBy"].get(tier, 0) + n
                for hop in chain:
                    if hop not in row["fallbackChain"]:
                        row["fallbackChain"].append(hop)
                for fmt in ("Dense", "Array", "Run"):
                    n = res["containerBlocks" + fmt]
                    if n:
                        blocks = row.setdefault("containerBlocks", {})
                        blocks[fmt] = blocks.get(fmt, 0) + n
                row.setdefault("repeatMs" if repeat else "firstMs",
                               round(ms, 2))
            if "concurrent" not in shape:
                print(f"{ms:10.1f} ms  {shape}{' (repeat)' * repeat}  "
                      f"{res['servedBy']} {chain}", file=sys.stderr)
        return out["results"][0], res


def fused(res):
    """True when a Count was served by the fused device tier."""
    return any(t == "batched" or t.startswith("coalesced_")
               for t in res["servedBy"])


# -------------------------------------------------------------------- data

def row_home(r):
    """Dense attribute row r -> (frame, rowID, AND-depth). Depth d gives
    density 2^-d: the frames each hold rows of 50, 25 and 12.5 %."""
    return FRAMES[r % 3], r // 3, 1 + (r // 3) % 3


def gen_slice(seed, n_rows, s):
    """uint64[n_rows, W64]: slice s of every dense row, from the seed."""
    rng = np.random.default_rng([seed, s])
    depths = [row_home(r)[2] for r in range(n_rows)]
    raw = rng.integers(0, 1 << 64, size=(sum(depths), W64),
                       dtype=np.uint64)
    out = np.empty((n_rows, W64), dtype=np.uint64)
    o = 0
    for r, d in enumerate(depths):
        out[r] = np.bitwise_and.reduce(raw[o:o + d], axis=0)
        o += d
    return out


def backup_tar(row_ids, words):
    """Fragment backup archive (the format ``cli backup`` writes and
    POST /fragment/data reads): a ``data`` member holding the roaring
    file of the fragment, here bitmap containers only, and a ``cache``
    member listing the ranked rows. words: uint64[len(row_ids), W64]."""
    n = len(row_ids) * CONTAINERS_PER_ROW
    blocks = words.reshape(n, 1024)
    cards = np.bitwise_count(blocks).sum(axis=1)
    check(int(cards.min()) >= 1, "empty container in generated data")
    hdr = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"), ("n", "<u2")])
    hdr["key"] = (np.repeat(np.asarray(row_ids, dtype=np.uint64),
                            CONTAINERS_PER_ROW) * CONTAINERS_PER_ROW
                  + np.tile(np.arange(CONTAINERS_PER_ROW, dtype=np.uint64),
                            len(row_ids)))
    hdr["typ"] = 2                                   # bitmap container
    hdr["n"] = cards - 1
    offs = (8 + 16 * n + 8192 * np.arange(n)).astype("<u4")
    data = (struct.pack("<II", 12348, n) + hdr.tobytes() + offs.tobytes()
            + blocks.tobytes())
    cache = json.dumps([int(r) for r in row_ids]).encode()
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for name, payload in (("data", data), ("cache", cache)):
            info = tarfile.TarInfo(name)
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
    return buf.getvalue()


def popcount(x):
    return int(np.bitwise_count(x).sum())


class Oracle:
    """The plain reference: the same operations on the same data in
    NumPy, independent of the code under test."""

    def __init__(self, n_rows, n_slices):
        self.dense = np.zeros((n_rows, n_slices, W64), dtype=np.uint64)
        self.n_slices = n_slices
        self.sparse = {}     # (frame, rowID) -> sorted unique columns

    def words(self, key):
        """Row words for a dense row index or a sparse (frame, rowID)."""
        if isinstance(key, int):
            return self.dense[key]
        out = np.zeros(self.n_slices * SLICE_WIDTH // 8, dtype=np.uint8)
        cols = self.sparse[key]
        np.bitwise_or.at(out, cols >> 3,
                         (1 << (cols & 7)).astype(np.uint8))
        return out.view(np.uint64).reshape(self.n_slices, W64)

    def set_bit(self, r, col, on):
        s, w, b = col // SLICE_WIDTH, (col % SLICE_WIDTH) // 64, col % 64
        if on:
            self.dense[r, s, w] |= np.uint64(1 << b)
        else:
            self.dense[r, s, w] &= ~np.uint64(1 << b)

    def bits_at(self, r, cols):
        """bool[len(cols)]: which of the columns are set in dense row r."""
        cols = np.asarray(cols, dtype=np.int64)
        w = self.dense[r, cols // SLICE_WIDTH, (cols % SLICE_WIDTH) // 64]
        return ((w >> (cols % 64).astype(np.uint64)) & np.uint64(1)) == 1


def bitmap(key):
    if isinstance(key, int):
        frame, rid, _ = row_home(key)
    else:
        frame, rid = key
    return f'Bitmap(frame="{frame}", rowID={rid})'


OPS = {
    "Intersect": lambda a, b: a & b,
    "Union": lambda a, b: a | b,
    "Difference": lambda a, b: a & ~b,
    "Xor": lambda a, b: a ^ b,
}


# ------------------------------------------------------------------ server

def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerProc:
    """The one child that owns the chip."""

    def __init__(self, data_dir, rehearse):
        self.data_dir = data_dir
        self.port = free_port()
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        self.env["PILOSA_TPU_STACK_BYTES"] = str(STACK_BYTES)
        self.env["TZ"] = "UTC"   # ingest maps epoch seconds to local days
        if rehearse:
            # Pin on the two tiers a CPU backend leaves off by default,
            # so the rehearsal runs the chip's branches; and pin the
            # fused path, which the adaptive model still probes against
            # per-slice execution below 512 slices and never above.
            self.env["PILOSA_TPU_COALESCE"] = "1"
            self.env["PILOSA_TPU_WARM_WIDTHS"] = "1"
            self.env["PILOSA_TPU_FORCE_PATH"] = "batched"
        self.proc = None
        self.boots = 0

    def start(self):
        self.boots += 1
        os.makedirs(OUT_DIR, exist_ok=True)
        self.stdout_path = os.path.join(
            OUT_DIR, f"chip_smoke_server{self.boots}.out")
        self.stderr_path = os.path.join(
            OUT_DIR, f"chip_smoke_server{self.boots}.log")
        t0 = time.perf_counter()
        with open(self.stdout_path, "wb") as out, \
                open(self.stderr_path, "wb") as err:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "-d", self.data_dir, "-b", f"127.0.0.1:{self.port}"],
                cwd=HERE, env=self.env, stdout=out, stderr=err)
        probe = Client(self.port, timeout=5)
        deadline = time.monotonic() + 300
        while True:
            check(self.proc.poll() is None,
                  f"server exited at boot, rc={self.proc.returncode}")
            check(time.monotonic() < deadline, "server boot timed out")
            try:
                probe.request("GET", "/version")
                break
            except OSError:
                time.sleep(0.25)
            finally:
                probe.close()
        return Client(self.port), time.perf_counter() - t0

    def drain(self):
        """SIGTERM -> exit 0 and 'drained and closed' on stdout."""
        t0 = time.perf_counter()
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=300)
        with open(self.stdout_path) as f:
            out = f.read()
        check(rc == 0, f"server exit code {rc} after SIGTERM")
        check("drained and closed" in out,
              f"no 'drained and closed' on server stdout: {out!r}")
        self.proc = None
        return time.perf_counter() - t0

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def device_block(client):
    return client.json("GET", "/debug/vars")["device"]


def compile_state(client):
    """({cell: compileCalls}, compile seconds) from /debug/kernels."""
    cells = client.json("GET", "/debug/kernels")["cells"]
    return ({f"{c['op']} {c['cell']} {c['bucket']}": c["compileCalls"]
             for c in cells if c["compileCalls"]},
            sum(c["compileMs"] for c in cells) / 1000)


class HostMemory(threading.Thread):
    """Samples the machine's MemAvailable five times a second. On the
    chip's 40 GiB host, memory has gone missing that no process's RSS
    showed (see dense_work), and running out ends the run."""

    def __init__(self):
        super().__init__(daemon=True)
        self._low = self.read()
        self.start()

    @staticmethod
    def read():
        with open("/proc/meminfo") as f:
            return next(int(ln.split()[1]) for ln in f
                        if ln.startswith("MemAvailable")) // 1024

    def run(self):
        while True:
            self._low = min(self._low, self.read())
            time.sleep(0.2)

    def low(self):
        """Lowest MB available since the last call."""
        low, self._low = self._low, self.read()
        return low


def memory_mark(client, host):
    """Device [bytes_in_use, peak_bytes_in_use] per device as the
    server's JAX reports them (the peak only grows, so its steps say
    which phase set it), and the host's low-water mark since the last
    mark."""
    return {"hbm": [m and [m.get("bytes_in_use"),
                           m.get("peak_bytes_in_use")]
                    for m in device_block(client)["memoryStats"]],
            "hostAvailableLowMB": host.low()}


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


def wait_warm_quiet(client):
    """Block until the background width warmer has nothing in flight;
    a failed warm compile is fatal."""
    deadline = time.monotonic() + 600
    while True:
        warm = client.json("GET", "/debug/vars")["widthWarmer"]
        check(warm["failed"] == 0, f"width warm failed: {warm}")
        if warm["inflight"] == 0:
            return warm
        check(time.monotonic() < deadline, f"warmer never quiet: {warm}")
        time.sleep(0.5)


# ------------------------------------------------------------------ phases

def load_dense(client, oracle, seed, n_rows, n_slices):
    """Generate slice by slice and restore through POST /fragment/data
    (generation overlaps the posts; a 4-thread pool posts)."""
    for frame in FRAMES:
        client.json("POST", f"/index/{INDEX}/frame/{frame}", "{}")
    by_frame = {f: [r for r in range(n_rows) if row_home(r)[0] == f]
                for f in FRAMES}
    sent = 0

    def post(frame, s, tar):
        client.request(
            "POST", f"/fragment/data?index={INDEX}&frame={frame}"
                    f"&view=standard&slice={s}", tar)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(4) as pool:
        futs = []
        for s in range(n_slices):
            words = gen_slice(seed, n_rows, s)
            oracle.dense[:, s, :] = words
            for frame, rows in by_frame.items():
                tar = backup_tar([row_home(r)[1] for r in rows],
                                 words[rows])
                sent += len(tar)
                futs.append(pool.submit(post, frame, s, tar))
            while len(futs) > 64:        # bound the tars held in memory
                futs.pop(0).result()
        for f in futs:
            f.result()
    dt = time.perf_counter() - t0
    return {"bytesPacked": int(oracle.dense.nbytes), "bytesSent": sent,
            "seconds": round(dt, 2),
            "restoreMBps": round(sent / dt / 1e6, 1)}


def ingest(client, frame, rows, cols, ts=None):
    body = {"frame": frame, "rows": rows.tolist(), "columns": cols.tolist()}
    if ts is not None:
        body["timestamps"] = ts.tolist()
    out = client.json("POST", f"/index/{INDEX}/ingest", json.dumps(body))
    check(out["accepted"] == len(rows), f"ingest accepted {out}")


def load_riders(client, oracle, seed, n_slices):
    """Small live-ingested frames whose job is to make their kernels
    compile on the chip, not to be large."""
    rng = np.random.default_rng([seed, 1 << 20])
    k = min(n_slices, 16)                    # slices the riders touch
    bases = np.arange(k, dtype=np.int64) * SLICE_WIDTH
    n_bits = 0
    t0 = time.perf_counter()

    def put(frame, rid, cols):
        nonlocal n_bits
        cols = np.unique(cols)
        oracle.sparse[(frame, rid)] = cols
        n_bits += len(cols)
        return np.full(len(cols), rid, dtype=np.int64), cols

    # tags: rows 0-3 ARRAY (1,500 scattered bits a slice), rows 4-7 RUN
    # (two runs of 1,024 columns a slice; the container tier only takes
    # rows of at most 4,096 bits a slice).
    client.json("POST", f"/index/{INDEX}/frame/tags", "{}")
    parts = []
    for rid in range(4):
        parts.append(put("tags", rid, np.concatenate(
            [b + rng.choice(SLICE_WIDTH, 1500, replace=False)
             for b in bases])))
    for rid in range(4, 8):
        starts = rng.integers(0, SLICE_WIDTH - 1024, size=(k, 2))
        parts.append(put("tags", rid, np.concatenate(
            [b + st + np.arange(1024) for b, pair in zip(bases, starts)
             for st in pair])))
    ingest(client, "tags", np.concatenate([p[0] for p in parts]),
           np.concatenate([p[1] for p in parts]))

    # recent: rows too dense for ARRAY (6,000 bits a slice) inside the
    # first 16,384 columns of each slice: a narrow column window.
    client.json("POST", f"/index/{INDEX}/frame/recent", "{}")
    parts = [put("recent", rid, np.concatenate(
        [b + rng.choice(16384, 6000, replace=False) for b in bases]))
        for rid in (0, 1)]
    ingest(client, "recent", np.concatenate([p[0] for p in parts]),
           np.concatenate([p[1] for p in parts]))

    # events: YMD time quantum, 30 days of June 2017, one row, in the
    # same narrow window: a Range stages one stack per day view over
    # every slice of the index, 119 MB each at full slice width.
    client.json("POST", f"/index/{INDEX}/frame/events",
                json.dumps({"options": {"timeQuantum": "YMD"}}))
    ev_cols = np.unique(np.concatenate(
        [b + rng.choice(16384, 2000, replace=False) for b in bases]))
    ev_days = rng.integers(0, 30, size=len(ev_cols))
    ingest(client, "events", np.full(len(ev_cols), 3, dtype=np.int64),
           ev_cols, DAY0 + ev_days * 86400)
    n_bits += len(ev_cols)

    # stats.age: a BSI field on the first slices.
    client.json("POST", f"/index/{INDEX}/frame/stats",
                json.dumps({"options": {"rangeEnabled": True}}))
    client.json("POST", f"/index/{INDEX}/frame/stats/field/age",
                json.dumps({"type": "int", "min": 0, "max": 1000}))
    age_cols = np.unique(np.concatenate(
        [b + rng.choice(SLICE_WIDTH, 5000, replace=False)
         for b in bases[:4]]))
    ages = rng.integers(0, 1001, size=len(age_cols))
    out = client.json("POST", f"/index/{INDEX}/ingest", json.dumps(
        {"frame": "stats", "field": "age", "columns": age_cols.tolist(),
         "values": ages.tolist()}))
    check(out["accepted"] == len(age_cols), f"ingest accepted {out}")
    dt = time.perf_counter() - t0
    return ({"bits": n_bits, "values": len(age_cols), "slices": k,
             "seconds": round(dt, 2),
             "ingestBitsPerSec": round(n_bits / dt)},
            (ev_cols, ev_days), (age_cols, ages))


def count_shapes(salt):
    """Dense Count shapes over distinct rows. ``salt`` picks other rows
    of the same densities for the repeat pass: no memo can serve it, and
    the planner, which orders operands by cardinality, builds the same
    trees. (Count over one Bitmap is the stage loop: every row is asked
    exactly once.) The nested shape is the docs' segmentation example,
    one row each of behavior, device and geo."""
    a, b, c = ((0, 4, 8), (1, 5, 6))[salt]     # densities 50, 25, 12.5 %
    for name, fn in OPS.items():
        yield (f"count_{name.lower()}",
               f"Count({name}({bitmap(a)}, {bitmap(b)}))",
               lambda o, fn=fn: popcount(fn(o.dense[a], o.dense[b])))
    yield ("count_nested",
           f"Count(Intersect({bitmap(a)}, "
           f"Difference({bitmap(b)}, {bitmap(c)})))",
           lambda o: popcount(o.dense[a] & (o.dense[b] & ~o.dense[c])))


def topn_oracle(oracle, rows, src=None):
    pairs = []
    for r in rows:
        w = oracle.dense[r] if src is None else oracle.dense[r] & src
        pairs.append((row_home(r)[1], popcount(w)))
    pairs = [p for p in pairs if p[1] > 0]
    return sorted(pairs, key=lambda p: (-p[1], p[0]))[:10]


def read_pass(served, oracle, n_rows, events, ages, repeat):
    """Every read shape once. ``repeat`` runs the same shapes on other
    rows, so that neither a memo nor a compile can hide in it; shapes
    without an argument to vary (plain TopN, unfiltered Sum/Min/Max)
    run in the first pass only."""
    salt = 1 if repeat else 0
    q = served.query

    for shape, pql, want in count_shapes(salt):
        got, res = q(pql, shape, repeat)
        check(got == want(oracle), f"{pql}: got {got}")
        check(fused(res),
              f"{pql}: dense Count served by {res['servedBy']}")

    # Materializing Intersect on sparse (ARRAY) rows.
    a, b = ("tags", 2 * salt), ("tags", 2 * salt + 1)
    got, _ = q(f"Intersect({bitmap(a)}, {bitmap(b)})",
               "intersect_materialize", repeat)
    want = np.intersect1d(oracle.sparse[a], oracle.sparse[b])
    check(got["bits"] == want.tolist(),
          f"materialized Intersect {a} {b}: {len(got['bits'])} bits, "
          f"want {len(want)}")

    # TopN over the frame with at most ten rows, so both phases are
    # exact; then restricted to a src bitmap of another frame.
    geo = [r for r in range(n_rows) if row_home(r)[0] == "geo"]
    if not repeat:
        got, _ = q('TopN(frame="geo", n=10)', "topn")
        check([(p["id"], p["count"]) for p in got]
              == topn_oracle(oracle, geo), f"TopN: {got}")
    src = 1 + 3 * salt                       # a device row
    got, _ = q(f'TopN({bitmap(src)}, frame="geo", n=10)', "topn_src",
               repeat)
    check([(p["id"], p["count"]) for p in got]
          == topn_oracle(oracle, geo, oracle.dense[src]),
          f"TopN src: {got}")

    # BSI: Sum, filtered Sum, Min, Max, Range.
    age_cols, age = ages
    filt = 3 * salt                          # a behavior row
    in_filt = oracle.bits_at(filt, age_cols)
    lo, hi, gt = (100, 700, 300) if repeat else (200, 600, 500)
    bsi = [
        ("bsi_sum_filtered",
         f'Sum({bitmap(filt)}, frame="stats", field="age")',
         {"sum": int(age[in_filt].sum()), "count": int(in_filt.sum())}),
        ("bsi_range_gt", f'Count(Range(frame="stats", age > {gt}))',
         int((age > gt).sum())),
        ("bsi_range_between",
         f'Count(Range(frame="stats", age >< [{lo}, {hi}]))',
         int(((age >= lo) & (age <= hi)).sum())),
    ]
    if not repeat:
        bsi += [
            ("bsi_sum", 'Sum(frame="stats", field="age")',
             {"sum": int(age.sum()), "count": len(age)}),
            ("bsi_min", 'Min(frame="stats", field="age")',
             {"sum": int(age.min()),
              "count": int((age == age.min()).sum())}),
            ("bsi_max", 'Max(frame="stats", field="age")',
             {"sum": int(age.max()),
              "count": int((age == age.max()).sum())}),
        ]
    for shape, pql, want in bsi:
        got, _ = q(pql, shape, repeat)
        check(got == want, f"{pql}: got {got}, want {want}")

    # Time Range over the YMD views: fourteen days either time.
    ev_cols, ev_days = events
    d0 = 10 * salt
    got, _ = q(
        f'Count(Range(frame="events", rowID=3, '
        f'start="2017-06-{d0 + 1:02d}T00:00", '
        f'end="2017-06-{d0 + 15:02d}T00:00"))', "time_range", repeat)
    check(got == int(((ev_days >= d0) & (ev_days < d0 + 14)).sum()),
          f"time Range: got {got}")

    sparse_cells(served, oracle, salt, repeat)


def sparse_cells(served, oracle, salt, repeat=False, evicted=False):
    """Count over array x array, array x dense, run x dense and
    run x run (tags rows 0-3 are ARRAY-shaped, 4-7 RUN-shaped). While
    their fragments are resident the fused dense tier serves them. Once
    evicted (after a restart, before anything faults them in) a plan of
    sparse rows only must come from the compressed container tier,
    which ``evicted`` checks."""
    dense = 7 + salt                          # a 12.5 % row
    for shape, a, b in (
            ("array_array", ("tags", 2 * salt), ("tags", 2 * salt + 1)),
            ("array_dense", ("tags", salt), dense),
            ("run_dense", ("tags", 4 + salt), dense),
            ("run_run", ("tags", 4 + 2 * salt), ("tags", 5 + 2 * salt))):
        got, res = served.query(
            f"Count(Intersect({bitmap(a)}, {bitmap(b)}))",
            ("evicted_" if evicted else "count_") + shape, repeat)
        check(got == popcount(oracle.words(a) & oracle.words(b)),
              f"{shape}: got {got}")
        check(not evicted or isinstance(b, int)
              or res["containerBlocksArray"] + res["containerBlocksRun"],
              f"evicted {shape} did not touch the container tier: {res}")


def set_bit(client, key, col, call="SetBit"):
    frame, rid = row_home(key)[:2] if isinstance(key, int) else key
    out = client.json(
        "POST", f"/index/{INDEX}/query",
        f'{call}(frame="{frame}", rowID={rid}, columnID={col})')
    check(out["results"] == [True], f"{call} {key} {col}: {out}")


def write_phase(served, client, oracle):
    """SetBit/ClearBit on a dense row read back at once; then a SetBit
    beyond the narrow frame's column window (the width change that made
    the recorded compile convoy, ROADMAP S2), then a query."""
    a, b = 0, 3
    base = SLICE_WIDTH * (oracle.n_slices > 1)       # in slice 1
    cand = np.arange(base + 17, base + 17 + 4096)
    col = int(cand[~oracle.bits_at(a, cand) & ~oracle.bits_at(b, cand)][0])
    pql = f"Count(Union({bitmap(a)}, {bitmap(b)}))"
    for on in (True, False):
        call = "SetBit" if on else "ClearBit"
        set_bit(client, a, col, call)
        oracle.set_bit(a, col, on)
        got, res = served.query(pql, f"count_after_{call.lower()}")
        check(got == popcount(oracle.dense[a] | oracle.dense[b]),
              f"read-your-write after {call}: got {got}")
        check(fused(res),
              f"Count after {call} served by {res['servedBy']}")
    set_bit(client, b, col)          # left set: the restart must keep it
    oracle.set_bit(b, col, True)

    r0, r1 = ("recent", 0), ("recent", 1)
    pql = f"Count(Intersect({bitmap(r0)}, {bitmap(r1)}))"
    want = len(np.intersect1d(oracle.sparse[r0], oracle.sparse[r1]))
    got, res = served.query(pql, "count_narrow_window")
    check(got == want, f"narrow-window Count: got {got}, want {want}")
    check(fused(res),
          f"narrow-window Count served by {res['servedBy']}")
    wait_warm_quiet(client)
    far = SLICE_WIDTH - 1                    # last column of slice 0
    for key in (r0, r1):
        set_bit(client, key, far)
        oracle.sparse[key] = np.union1d(oracle.sparse[key], [far])
    got, res = served.query(pql, "count_widened_window")
    check(got == want + 1, f"widened-window Count: got {got}")
    check(fused(res),
          f"widened-window Count served by {res['servedBy']}")


def count_work(keys, single, inter):
    """Distinct (pql, want) Counts over every pair of rows, from |a|
    and |a & b| by inclusion and exclusion."""
    work = []
    for (i, j), n in inter.items():
        a, b, na, nb = bitmap(keys[i]), bitmap(keys[j]), single[i], single[j]
        work += [(f"Count(Intersect({a}, {b}))", n),
                 (f"Count(Union({a}, {b}))", na + nb - n),
                 (f"Count(Difference({a}, {b}))", na - n),
                 (f"Count(Difference({b}, {a}))", nb - n),
                 (f"Count(Xor({a}, {b}))", na + nb - 2 * n)]
    return work


def dense_work(oracle, n_rows):
    """|a| and |a & b| over every pair of dense rows, sixteen slices at
    a time. Whole-row temporaries (125 MB each, eight threads of them)
    cost the chip's host 22 GB and more of available memory that no
    process's RSS showed, and once all 40 GiB (chip runs, PR 21)."""
    pairs = list(itertools.combinations(range(n_rows), 2))

    def chunk(s0):
        d = oracle.dense[:n_rows, s0:s0 + 16]
        return (np.bitwise_count(d).sum(axis=(1, 2), dtype=np.int64),
                np.array([popcount(d[a] & d[b]) for a, b in pairs]))

    with ThreadPoolExecutor(8) as pool:
        parts = list(pool.map(chunk, range(0, oracle.n_slices, 16)))
    single = sum(p[0] for p in parts).tolist()
    inter = sum(p[1] for p in parts).tolist()
    return count_work(list(range(n_rows)), single, dict(zip(pairs, inter)))


def sparse_work(oracle):
    keys = [("tags", r) for r in range(8)]
    cols = [oracle.sparse[k] for k in keys]
    inter = {(i, j): len(np.intersect1d(cols[i], cols[j],
                                        assume_unique=True))
             for i, j in itertools.combinations(range(8), 2)}
    return count_work(keys, [len(c) for c in cols], inter)


def run_clients(served, work, seed, seconds, shape, need_fused):
    """8 client threads of distinct Counts for ``seconds``: the only
    traffic under which the cross-query coalescer runs at all."""
    np.random.default_rng([seed, len(work)]).shuffle(work)
    n_threads = 8
    stop = time.monotonic() + seconds
    done = [0] * n_threads

    def run(k):
        for pql, want in work[k::n_threads]:
            if time.monotonic() >= stop:
                return
            got, res = served.query(pql, shape)
            check(got == want, f"concurrent {pql}: got {got}, want {want}")
            check(not need_fused or fused(res),
                  f"concurrent {pql} served by {res['servedBy']}")
            done[k] += 1

    t0 = time.perf_counter()
    with ThreadPoolExecutor(n_threads) as pool:
        for f in [pool.submit(run, k) for k in range(n_threads)]:
            f.result()
    return {"threads": n_threads, "queries": sum(done),
            "distinctAvailable": len(work),
            "seconds": round(time.perf_counter() - t0, 2)}


def coalescer_counters(client):
    co = client.json("GET", "/debug/vars")["countCoalescer"]
    return {k: co[k] for k in (
        "enabled", "rounds", "fused_queries", "compressedFusedQueries",
        "max_group", "laneLaunches", "declined")}


def kernels_child(rehearse):
    """Runs in its own process after the server is gone: compile the
    three Pallas kernels and the ingest pack kernel, check each against
    XLA / NumPy. Compiled under Mosaic unless rehearsing off-chip."""
    from pilosa_tpu.utils import compilecache

    compilecache.enable()
    import jax

    from pilosa_tpu.ops import bitops, ingest as ingest_ops
    from pilosa_tpu.ops import pallas_kernels as pk

    interpret = jax.devices()[0].platform != "tpu"
    check(rehearse or not interpret, "kernels child found no TPU")
    shapes = [(8, 256)] if rehearse else [(64, 32768), (1024, 32768)]
    out = {"pallasInterpreted": interpret, "shapes": shapes}
    for i, (r, w) in enumerate(shapes):
        ka, kb, kf = jax.random.split(jax.random.PRNGKey(i), 3)
        a = jax.random.bits(ka, (r, w), dtype="uint32")
        b = jax.random.bits(kb, (r, w), dtype="uint32")
        f = jax.random.bits(kf, (w,), dtype="uint32")
        t0 = time.perf_counter()
        got = (int(pk.count_and(a, b, interpret=interpret)),
               np.asarray(pk.count_rows(a, interpret=interpret)),
               np.asarray(pk.count_and_rows(a, f, interpret=interpret)))
        out[f"pallas_{r}x{w}_s"] = round(time.perf_counter() - t0, 2)
        want = (int(bitops.count_and(a, b)),
                np.asarray(bitops.count_rows(a)),
                np.asarray(bitops.count_and_rows(a, f)))
        check(got[0] == want[0], f"pallas count_and {r}x{w}")
        check((got[1] == want[1]).all(), f"pallas count_rows {r}x{w}")
        check((got[2] == want[2]).all(), f"pallas count_and_rows {r}x{w}")
    # ingest pack_classify: no served route reaches it (live ingest
    # takes the ``classify`` cell), so it is compiled here.
    rng = np.random.default_rng(0)
    n_rows, per_row = (4, 300) if rehearse else (64, 16000)
    pos = np.sort(np.stack([rng.choice(SLICE_WIDTH, per_row, replace=False)
                            for _ in range(n_rows)]), axis=1)
    rowidx = np.repeat(np.arange(n_rows, dtype=np.int32), per_row)
    t0 = time.perf_counter()
    words, counts, runs = ingest_ops.pack_classify(
        rowidx, pos.reshape(-1).astype(np.int32), n_rows,
        SLICE_WIDTH // 32)
    out["pack_classify_s"] = round(time.perf_counter() - t0, 2)
    want_counts, want_runs = ingest_ops.classify_stats_host(
        rowidx, pos.reshape(-1), n_rows)
    check((counts == want_counts).all() and (runs == want_runs).all(),
          "pack_classify stats")
    check(int(np.bitwise_count(np.asarray(words)).sum())
          == n_rows * per_row, "pack_classify words")
    print(json.dumps(out))


# -------------------------------------------------------------------- main

def run(args, data_dir):
    from pilosa_tpu import native    # no JAX: ctypes loader only

    t_all = time.perf_counter()
    native.build()                   # from roaring.cpp, every run; raises
    server = ServerProc(data_dir, args.rehearse)
    try:
        client, boot_s = server.start()
        dev = device_block(client)
        check(args.rehearse or dev["platform"] == "tpu",
              f"platform is {dev['platform']!r}, not 'tpu' "
              "(--rehearse allows a tiny run elsewhere)")
        say("device", platform=dev["platform"], deviceKind=dev["deviceKind"],
            deviceCount=dev["deviceCount"], nativeLoaded=dev["nativeLoaded"],
            compileCacheDir=dev["compileCacheDir"],
            cacheEntriesBefore=cache_entries(dev["compileCacheDir"]),
            bootSeconds=round(boot_s, 2))
        check(dev["nativeLoaded"], "nativeLoaded is false")

        why = "rehearsal" if args.rehearse else "--rows on the command line"
        reduced = []
        if args.rows < FULL_ROWS:
            reduced.append(f"rows {args.rows} of {FULL_ROWS}: {why}")
        if args.slices < FULL_SLICES:
            reduced.append(f"slices {args.slices} of {FULL_SLICES}: {why}")
        oracle = Oracle(args.rows, args.slices)
        host = HostMemory()
        client.json("POST", f"/index/{INDEX}", "{}")
        loaded = load_dense(client, oracle, args.seed, args.rows,
                            args.slices)
        say("restore", slices=args.slices, rows=args.rows,
            columns=args.slices * SLICE_WIDTH, reduced=reduced, **loaded)
        riders, events, ages = load_riders(client, oracle, args.seed,
                                           args.slices)
        say("ingest", **riders)
        mem = {"afterLoad": memory_mark(client, host)}

        served = Served(client)
        # Stage every dense row once, which also checks the whole
        # restore against the oracle.
        t0 = time.perf_counter()
        for r in range(args.rows):
            got, _ = served.query(f"Count({bitmap(r)})", "stage_row")
            check(got == popcount(oracle.dense[r]),
                  f"row {r} count after restore: got {got}")
        say("stage", rows=args.rows, seconds=round(
            time.perf_counter() - t0, 2))
        mem["afterStage"] = memory_mark(client, host)

        t0 = time.perf_counter()
        read_pass(served, oracle, args.rows, events, ages, repeat=False)
        first_s = time.perf_counter() - t0
        write_phase(served, client, oracle)
        mem["afterFirstPass"] = memory_mark(client, host)
        work = dense_work(oracle, args.rows)
        mem["afterPairCounts"] = memory_mark(client, host)
        seconds = 1.5 if args.rehearse else 5.0
        conc = run_clients(served, work, args.seed, seconds,
                           "count_concurrent", need_fused=True)
        mem["afterConcurrent"] = memory_mark(client, host)
        warm = wait_warm_quiet(client)
        calls0, compile_s = compile_state(client)
        entries0 = cache_entries(dev["compileCacheDir"])
        t0 = time.perf_counter()
        read_pass(served, oracle, args.rows, events, ages, repeat=True)
        repeat_s = time.perf_counter() - t0
        calls1, _ = compile_state(client)
        check(calls1 == calls0, "compilation in the repeat pass: "
              f"{sorted(set(calls1.items()) - set(calls0.items()))}")
        check(cache_entries(dev["compileCacheDir"]) == entries0,
              "the compile cache grew in the repeat pass")
        say("compile", compileCalls=sum(calls0.values()),
            compileSeconds=round(compile_s, 2), repeatPassCompiles=0,
            firstPassSeconds=round(first_s, 2),
            repeatPassSeconds=round(repeat_s, 2), widthWarmer=warm)
        say("coalescer", **conc, **coalescer_counters(client))
        vars_ = client.json("GET", "/debug/vars")
        say("planner", costModel=vars_["costModel"])
        mem["afterRepeat"] = memory_mark(client, host)
        say("memory", marks=mem, perDevice=[
            {k: m.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit")} if m else None
            for m in vars_["device"]["memoryStats"]],
            stackCacheBytes=vars_["memory"]["executor"]["stackCacheBytes"])

        # Drain, then restart on the same directory: the write-phase
        # bits must have survived, so one Count is exact again.
        a, b = 0, 3
        pql = f"Count(Intersect({bitmap(a)}, {bitmap(b)}))"
        want = popcount(oracle.dense[a] & oracle.dense[b])
        got, _ = served.query(pql)
        check(got == want, f"Count before drain: got {got}")
        client.close()
        drain_s = server.drain()
        client, boot2_s = server.start()
        served.client = client
        got, res = served.query(pql, "count_after_restart")
        check(got == want, f"Count after restart: got {got}, want {want}")
        check(fused(res),
              f"Count after restart served by {res['servedBy']}")
        say("restart", drainSeconds=round(drain_s, 2),
            bootSeconds=round(boot2_s, 2), count=got)
        # Nothing has faulted the sparse frames in yet: evicted, they
        # are the compressed container tier's to serve, singly and then
        # through the coalescer's lanes.
        sparse_cells(served, oracle, salt=0, evicted=True)
        lanes = run_clients(served, sparse_work(oracle), args.seed,
                            seconds / 2, "evicted_concurrent",
                            need_fused=False)
        say("lanes", **lanes, **coalescer_counters(client),
            memory=memory_mark(client, host))
        say("served", **served.table)
        client.close()
        server.drain()
    finally:
        server.kill()

    # The chip is free again: one more child compiles the kernels no
    # served route reaches.
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--kernels-child"]
        + (["--rehearse"] if args.rehearse else []),
        cwd=HERE, env=server.env, stdout=subprocess.PIPE, timeout=900)
    check(child.returncode == 0, f"kernels child rc={child.returncode}")
    say("kernels", **json.loads(child.stdout.decode().splitlines()[-1]))
    say("total", seconds=round(time.perf_counter() - t_all, 2),
        cacheEntriesAfter=cache_entries(dev["compileCacheDir"]))
    return dev


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size, any platform (say JAX_PLATFORMS=cpu)")
    ap.add_argument("--slices", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None,
                    help=f"dense rows, {FLOOR_ROWS}..{FULL_ROWS}")
    ap.add_argument("--kernels-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernels_child:
        kernels_child(args.rehearse)
        return 0
    if args.rehearse:
        args.slices = args.slices or 6
        args.rows = args.rows or 9
        if args.rows < 9:
            ap.error("the read shapes name nine rows; --rows >= 9")
    else:
        args.slices = args.slices or FULL_SLICES
        args.rows = args.rows or FULL_ROWS
        if args.slices < FULL_SLICES or not (
                FLOOR_ROWS <= args.rows <= FULL_ROWS):
            ap.error(f"the floor is {FULL_SLICES} slices x {FLOOR_ROWS} "
                     "rows; smaller sizes need --rehearse")

    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        dev = run(args, data_dir)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke_report.json"), "w") as f:
        json.dump(REPORT, f, indent=1)
    last = {"ok": True, "device": {"platform": dev["platform"],
                                   "kind": dev["deviceKind"],
                                   "count": dev["deviceCount"]}}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
