"""Data of the chemical-similarity deployment: one molecule per row, one
fingerprint bit per column, 4,096 columns in one slice; restored as one
fragment backup through ``POST /fragment/data`` with every row in its
``cache`` member. Molecules come in families of near copies of a parent
fingerprint, so that a Tanimoto threshold returns more than the probe.
Every size the source does not give is under ``assumed`` in the
configuration's file."""
import struct
import time

import numpy as np

from .segmentation import tar_of

CHUNK = 50_000


def pools(config):
    return {"molecule": [str(i)
                         for i in range(config["shape"]["molecules"])]}


def stage_queries(config):
    """One TopN with a source bitmap builds and uploads the fragment's
    row matrix. Then the exact re-query (TopN's phase 2) as a client
    may send it, with explicit ``ids``, at each power-of-two number of
    ids up to n's: its program compiles once a bucket, and which
    buckets the first probes of a seed meet is chance. Two distinct
    queries a bucket, one after the other: while the engine's path
    model explores a call shape it serves every other call serially,
    and that one compiles nothing."""
    shape, staging = config["shape"], config["staging"]
    frame = shape["frame"]
    out = [f'TopN(Bitmap(frame="{frame}", rowID=0), frame="{frame}", n=5)']
    size = 1
    while size < 2 * staging["n"]:
        for first in (size, 2 * size):
            ids = list(range(first, first + size))
            out.append(
                f'TopN(Bitmap(frame="{frame}", rowID={first}), '
                f'frame="{frame}", ids={ids}, n={staging["n"]}, '
                f'tanimotoThreshold={staging["tanimotoThreshold"]})')
        size *= 2
    return out


def family_sizes(config, n, rng):
    """The same multiset of family sizes for every seed (the cycle in
    ``assumed.family_sizes``, cut to n molecules), in a seeded order."""
    cycle = config["assumed"]["family_sizes"]
    sizes = []
    while sum(sizes) < n:
        sizes.extend(cycle)
    sizes = np.array(sizes)
    over = int(sizes.sum()) - n
    while over > 0:
        cut = min(over, int(sizes[-1]) - 1)
        if cut == 0:
            sizes = sizes[:-1]
            over -= 1
        else:
            sizes[-1] -= cut
            over -= cut
    return rng.permutation(sizes)


def fingerprints(config, seed):
    """bool[molecules, bits] in chunks: yields (first row, chunk).

    A parent is ``k`` positions drawn with replacement (k uniform in
    ``parent_bits``; a repeated position collapses, which costs half a
    bit on average). A member drops a run of up to ``max_bits_dropped``
    of its parent's positions, which are in random order, and adds up
    to ``max_bits_added`` random ones; the first member of a family is
    the parent itself."""
    shape, assumed = config["shape"], config["assumed"]
    n, bits = shape["molecules"], shape["fingerprint_bits"]
    lo, hi = assumed["parent_bits"]
    max_drop, max_add = assumed["max_bits_dropped"], assumed["max_bits_added"]
    rng = np.random.default_rng([seed, 5])
    sizes = family_sizes(config, n, rng)
    n_fam = len(sizes)
    parent_pos = rng.integers(0, bits, size=(n_fam, hi))
    parent_k = rng.integers(lo, hi + 1, size=n_fam)
    family = np.repeat(np.arange(n_fam), sizes)
    is_parent = np.zeros(n, dtype=bool)
    is_parent[np.cumsum(sizes) - sizes] = True
    n_drop = np.where(is_parent, 0, rng.integers(0, max_drop + 1, size=n))
    n_add = np.where(is_parent, 0, rng.integers(0, max_add + 1, size=n))
    drop_at = rng.integers(0, hi, size=n)
    added = rng.integers(0, bits, size=(n, max_add))
    j = np.arange(hi)[None, :]
    for r0 in range(0, n, CHUNK):
        r1 = min(r0 + CHUNK, n)
        fam = family[r0:r1]
        k = parent_k[fam][:, None]
        # Position j of the parent is kept if it is among the first k
        # and outside the run [drop_at, drop_at + n_drop) modulo k.
        off = (j - drop_at[r0:r1, None] % k) % k
        kept = (j < k) & (off >= n_drop[r0:r1, None])
        chunk = np.zeros((r1 - r0, bits), dtype=bool)
        rows = np.broadcast_to(np.arange(r1 - r0)[:, None], kept.shape)
        chunk[rows[kept], parent_pos[fam][kept]] = True
        more = np.arange(max_add)[None, :] < n_add[r0:r1, None]
        rows = np.broadcast_to(np.arange(r1 - r0)[:, None], more.shape)
        chunk[rows[more], added[r0:r1][more]] = True
        yield r0, chunk


def pack_rows(chunk):
    """bool[m, bits] -> uint64[m, bits/64], bit c of a row at word c//64,
    position c%64."""
    return np.packbits(chunk, axis=1, bitorder="little").view(np.uint64)


def roaring_arrays(cols_per_row, counts):
    """The fragment's roaring file with one ARRAY container per row:
    row r's columns all lie in its first 2^16-bit container, key 16 r."""
    n = len(counts)
    hdr = np.zeros(n, dtype=[("key", "<u8"), ("typ", "<u2"), ("n", "<u2")])
    hdr["key"] = np.arange(n, dtype=np.uint64) * 16
    hdr["typ"] = 1
    hdr["n"] = counts - 1
    offs = (8 + 16 * n + 2 * (np.cumsum(counts) - counts)).astype("<u4")
    return (struct.pack("<II", 12348, n) + hdr.tobytes() + offs.tobytes()
            + cols_per_row.astype("<u2").tobytes())


def load(client, config, seed, note):
    shape = config["shape"]
    index, frame, n = shape["index"], shape["frame"], shape["molecules"]
    t0 = time.perf_counter()
    packed, cols, counts = [], [], []
    for _, chunk in fingerprints(config, seed):
        packed.append(pack_rows(chunk))
        counts.append(chunk.sum(axis=1))
        cols.append(np.nonzero(chunk)[1].astype(np.uint16))
    packed, counts = np.concatenate(packed), np.concatenate(counts)
    if int(counts.min()) < 1:
        raise ValueError("a molecule with no bit set")
    body = tar_of(roaring_arrays(np.concatenate(cols), counts),
                  list(range(n)))
    t_gen = time.perf_counter() - t0
    client.json("POST", f"/index/{index}", "{}")
    client.json("POST", f"/index/{index}/frame/{frame}",
                '{"options": {"cacheType": "ranked", "cacheSize": %d}}' % n)
    t0 = time.perf_counter()
    client.request("POST", f"/fragment/data?index={index}&frame={frame}"
                           f"&view=standard&slice=0", body)
    dt = time.perf_counter() - t0
    note("restore", bytesSent=len(body), generateSeconds=round(t_gen, 2),
         seconds=round(dt, 2), MBps=round(len(body) / dt / 1e6, 1))
    return {"packed": packed, "counts": counts.astype(np.int64)}
