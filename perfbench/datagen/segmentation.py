"""Data of the segmentation deployments: dense attribute rows over the
frames behavior / device / geo, restored slice by slice through
``POST /fragment/data`` (the route ``cli restore`` uses). A copy of
``chip_smoke.py``'s ``gen_slice`` / ``backup_tar`` / ``load_dense``
(PR 21), without the smoke's riders."""
import io
import json
import struct
import tarfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICE_WIDTH = 1 << 20
W64 = SLICE_WIDTH // 64
CONTAINERS_PER_ROW = 16


def row_home(config, r):
    """Dense row r -> (frame, rowID, AND-depth): depth d gives density
    2^-d, so each frame holds rows of 50, 25 and 12.5 %."""
    frames = config["shape"]["frames"]
    depths = config["shape"]["and_depths"]
    return frames[r % len(frames)], r // len(frames), \
        depths[(r // len(frames)) % len(depths)]


def bitmap(config, r):
    frame, rid, _ = row_home(config, r)
    return f'Bitmap(frame="{frame}", rowID={rid})'


def pools(config):
    rows = [bitmap(config, r) for r in range(config["shape"]["rows"])]
    out = {"row": rows}
    for f in config["shape"]["frames"]:
        out[f] = [b for b in rows if f'frame="{f}"' in b]
    return out


def stage_queries(config):
    """The first Count of each row builds and uploads its stack."""
    return [f"Count({b})" for b in pools(config)["row"]]


def gen_slice(config, seed, s):
    """uint64[rows, W64]: slice s of every dense row, from the seed."""
    n_rows = config["shape"]["rows"]
    rng = np.random.default_rng([seed, s])
    depths = [row_home(config, r)[2] for r in range(n_rows)]
    raw = rng.integers(0, 1 << 64, size=(sum(depths), W64), dtype=np.uint64)
    out = np.empty((n_rows, W64), dtype=np.uint64)
    o = 0
    for r, d in enumerate(depths):
        out[r] = np.bitwise_and.reduce(raw[o:o + d], axis=0)
        o += d
    return out


def backup_tar(row_ids, words):
    """Fragment backup archive as ``cli backup`` writes it: a ``data``
    member (the fragment's roaring file, bitmap containers only) and a
    ``cache`` member listing the ranked rows."""
    n = len(row_ids) * CONTAINERS_PER_ROW
    blocks = words.reshape(n, 1024)
    cards = np.bitwise_count(blocks).sum(axis=1)
    if int(cards.min()) < 1:
        raise ValueError("empty container in generated data")
    hdr = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"), ("n", "<u2")])
    hdr["key"] = (np.repeat(np.asarray(row_ids, dtype=np.uint64),
                            CONTAINERS_PER_ROW) * CONTAINERS_PER_ROW
                  + np.tile(np.arange(CONTAINERS_PER_ROW, dtype=np.uint64),
                            len(row_ids)))
    hdr["typ"] = 2
    hdr["n"] = cards - 1
    offs = (8 + 16 * n + 8192 * np.arange(n)).astype("<u4")
    data = (struct.pack("<II", 12348, n) + hdr.tobytes() + offs.tobytes()
            + blocks.tobytes())
    return tar_of(data, [int(r) for r in row_ids])


def tar_of(data, cache_ids):
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for name, payload in (("data", data),
                              ("cache", json.dumps(cache_ids).encode())):
            info = tarfile.TarInfo(name)
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
    return buf.getvalue()


def load(client, config, seed, note):
    """Create the index and restore every slice; generation overlaps
    the posts (4 posting threads, as the smoke). Returns the dense rows,
    uint64[rows, slices, W64], for the reference."""
    shape = config["shape"]
    index, n_rows, n_slices = shape["index"], shape["rows"], shape["slices"]
    client.json("POST", f"/index/{index}", "{}")
    for frame in shape["frames"]:
        client.json("POST", f"/index/{index}/frame/{frame}", "{}")
    by_frame = {f: [r for r in range(n_rows)
                    if row_home(config, r)[0] == f] for f in shape["frames"]}
    dense = np.zeros((n_rows, n_slices, W64), dtype=np.uint64)
    sent = 0

    def post(frame, s, tar):
        client.request("POST", f"/fragment/data?index={index}&frame={frame}"
                               f"&view=standard&slice={s}", tar)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(4) as pool:
        futs = []
        for s in range(n_slices):
            words = gen_slice(config, seed, s)
            dense[:, s, :] = words
            for frame, rows in by_frame.items():
                tar = backup_tar([row_home(config, r)[1] for r in rows],
                                 words[rows])
                sent += len(tar)
                futs.append(pool.submit(post, frame, s, tar))
            while len(futs) > 64:
                futs.pop(0).result()
        for f in futs:
            f.result()
    dt = time.perf_counter() - t0
    note("restore", bytesSent=sent, seconds=round(dt, 2),
         MBps=round(sent / dt / 1e6, 1))
    return {"dense": dense}
