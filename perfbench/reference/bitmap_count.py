"""The plain reference for ``Count`` over set operations on dense rows:
the same operations on the same words in NumPy, a block of slices at a
time so that the host keeps its memory (PR 21 ran a 40 GiB host out with
whole-row temporaries on eight threads)."""
import importlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib import pql

BLOCK_SLICES = 8
THREADS = 12

OPS = {
    "Intersect": np.bitwise_and,
    "Union": np.bitwise_or,
    "Xor": np.bitwise_xor,
    "Difference": lambda a, b: np.bitwise_and(a, np.bitwise_not(b)),
}


class Reference:
    def __init__(self, config, data):
        gen = importlib.import_module(f"perfbench.datagen.{config['datagen']}")
        self.dense = data["dense"]
        self.row_of = {}
        for r in range(config["shape"]["rows"]):
            frame, rid, _ = gen.row_home(config, r)
            self.row_of[(frame, rid)] = r

    def _words(self, call, s0, s1):
        if call.name == "Bitmap":
            r = self.row_of[(call.args["frame"], call.args["rowID"])]
            return self.dense[r, s0:s1]
        out = self._words(call.children[0], s0, s1)
        for child in call.children[1:]:
            out = OPS[call.name](out, self._words(child, s0, s1))
        return out

    def per_slice_counts(self, pqls):
        """int64[len(pqls), slices]: each query's count in each slice."""
        trees = []
        for q in pqls:
            call = pql.parse(q)
            if call.name != "Count" or len(call.children) != 1:
                raise ValueError(f"not a Count of one bitmap: {q}")
            trees.append(call.children[0])
        n_slices = self.dense.shape[1]
        out = np.zeros((len(trees), n_slices), dtype=np.int64)

        def block(s0):
            s1 = min(s0 + BLOCK_SLICES, n_slices)
            for i, tree in enumerate(trees):
                out[i, s0:s1] = np.bitwise_count(
                    self._words(tree, s0, s1)).sum(axis=1, dtype=np.int64)

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(block, range(0, n_slices, BLOCK_SLICES)))
        return out

    def answers(self, pqls, control=False):
        """The exact counts; with ``control`` the per-slice counts are
        summed in float32, the nearest precision below the integers the
        configuration states: exact to 2^24, which a billion-column
        count passes."""
        per_slice = self.per_slice_counts(pqls)
        if control:
            acc = np.cumsum(per_slice.astype(np.float32), axis=1,
                            dtype=np.float32)[:, -1]
            return [int(x) for x in acc]
        return [int(x) for x in per_slice.sum(axis=1)]

    def explain(self, query, got, want):
        return {"query": query, "got": got, "want": want,
                "difference": (got - want) if isinstance(got, int) else None}
