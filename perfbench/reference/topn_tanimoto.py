"""The plain reference for ``TopN(Bitmap(rowID=p), n, tanimotoThreshold=T)``
over one fragment, in integers (upstream fragment.go:850-858, :908-918
computes the score in float64 and keeps ceil(score) > T, which for an
integer T is the same rule):

    inter = |row & src|,  denom = |row| + |src| - inter
    keep where 100 * inter > T * denom  (and inter > 0)
    order by (-inter, id), cut at n; the pair's count is inter.

Without a threshold every row with inter > 0 is kept. NumPy over the
packed ``uint64`` matrix the generator made; nothing of the program is
imported. The control (``answers(pqls, control=True)``) takes the score
in bfloat16, the nearest precision below the integers the configuration
states, and gates it as upstream's text reads: ceil(score) > T."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib import pql

BLOCK_ROWS = 16_384
THREADS = 12


def to_bfloat16(x):
    """float32 values rounded to bfloat16's 8 bits of mantissa (round to
    nearest even), returned as float32."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


class Reference:
    def __init__(self, config, data):
        self.packed = data["packed"]
        self.counts = data["counts"]
        self.frame = config["shape"]["frame"]

    def _parse(self, query):
        call = pql.parse(query)
        if call.name != "TopN" or len(call.children) != 1:
            raise ValueError(f"not a TopN with a source bitmap: {query}")
        src = call.children[0]
        if (src.name != "Bitmap" or src.args.get("frame") != self.frame
                or call.args.get("frame") != self.frame):
            raise ValueError(f"TopN outside frame {self.frame}: {query}")
        return (src.args["rowID"], call.args.get("n", 0),
                call.args.get("tanimotoThreshold", 0))

    def intersections(self, p):
        src = self.packed[p]
        out = np.empty(len(self.packed), dtype=np.int64)
        for r0 in range(0, len(self.packed), BLOCK_ROWS):
            blk = self.packed[r0:r0 + BLOCK_ROWS]
            out[r0:r0 + len(blk)] = np.bitwise_count(blk & src).sum(
                axis=1, dtype=np.int64)
        return out

    def answer(self, query, control=False):
        p, n, t = self._parse(query)
        inter = self.intersections(p)
        keep = inter > 0
        if t:
            denom = self.counts + self.counts[p] - inter
            if control:
                # The control: the score in bfloat16.
                score = to_bfloat16(
                    to_bfloat16(100.0 * inter.astype(np.float32))
                    / to_bfloat16(denom.astype(np.float32)))
                keep &= np.ceil(score) > t
            else:
                keep &= 100 * inter > t * denom
        ids = np.nonzero(keep)[0]
        order = np.lexsort((ids, -inter[ids]))
        if n:
            order = order[:n]
        return [{"id": int(i), "count": int(inter[i])} for i in ids[order]]

    def answers(self, pqls, control=False):
        with ThreadPoolExecutor(THREADS) as pool:
            return list(pool.map(lambda q: self.answer(q, control), pqls))

    def explain(self, query, got, want):
        """For each row on which the answers differ: (id, inter, |row|,
        |src|, 100*inter, T*denom), so that a row exactly on the
        threshold shows as such."""
        p, n, t = self._parse(query)
        inter = self.intersections(p)
        as_map = lambda pairs: {d["id"]: d["count"] for d in pairs} \
            if isinstance(pairs, list) else {}
        g, w = as_map(got), as_map(want)
        rows = []
        for i in sorted(set(g) ^ set(w) | {i for i in set(g) & set(w)
                                           if g[i] != w[i]}):
            if not 0 <= i < len(inter):
                rows.append({"id": i, "unknown_row": True})
                continue
            denom = int(self.counts[i] + self.counts[p] - inter[i])
            rows.append({"id": i, "got": g.get(i), "want": w.get(i),
                         "inter": int(inter[i]), "row": int(self.counts[i]),
                         "src": int(self.counts[p]),
                         "100_inter": 100 * int(inter[i]),
                         "T_denom": t * denom,
                         "on_threshold": 100 * int(inter[i]) == t * denom})
        return {"query": query, "n": n, "threshold": t,
                "got_len": len(got) if isinstance(got, list) else None,
                "want_len": len(want), "differing_rows": rows[:40],
                "order_only": not rows}
