"""The Tanimoto gate is the integer rule ``100*inter > T*denom`` on every
TopN path: ``ops.topn.tanimoto_keep`` itself, the per-fragment program
(``tanimoto_masked_counts``), the executor's batched program,
``Fragment.top`` and the executor's serial and batched tiers.

Row d of the test data has its first d bits set, so against the probe
row s a row reads inter = min(d, s) and denom = max(d, s): the probes
1..512 against the rows 1..512 give every (inter, denom) with
denom <= 512, among them each pair that lies exactly on a threshold
(7/14 at T=50, 27/30 at T=90, 225/250 at T=90, ...), which the v5e's
float32 division used to keep (PR 23). The CPU backend never showed the
fault, so what these cases hold is that no path leaves the one rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import topn as topn_ops
from pilosa_tpu.storage.fragment import TopOptions
from pilosa_tpu.storage.holder import Holder

N = 512                               # rows, and the widest probe
THRESHOLDS = np.arange(1, 101)
# Probe sizes for the paths that cost a millisecond a call: those of the
# pairs the v5e got wrong (PR 23's probe) and the powers of two.
PROBES = (1, 2, 3, 5, 7, 8, 15, 16, 27, 30, 54, 63, 64, 100, 108, 127,
          128, 216, 225, 250, 255, 256, 511, 512)
SMALL = 64                            # rows of the executor's cases
SMALL_PROBES = (1, 2, 3, 5, 7, 8, 15, 16, 27, 30, 32, 33, 54, 60, 63, 64)
SMALL_T = (1, 10, 25, 33, 50, 60, 66, 70, 75, 80, 90, 99, 100)


def want_counts(d, s, t):
    """The integer rule in NumPy: masked inter for rows ``d`` against a
    probe of ``s`` bits."""
    inter, denom = np.minimum(d, s), np.maximum(d, s)
    return np.where(100 * inter > t * denom, inter, 0)


def prefix_rows(n):
    """uint32[n, n // 32]: row d-1 has its first d bits set."""
    bits = np.tril(np.ones((n, n), dtype=np.uint8))
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint32)


def keep_numpy():
    d = np.arange(1, N + 1)
    for s in range(1, N + 1):
        inter, denom = np.minimum(d, s), np.maximum(d, s)
        for t in (1, 50, 70, 90, 100):
            got = topn_ops.tanimoto_keep(inter, d, np.int64(s), t)
            assert (got == (100 * inter > t * denom)).all(), (s, t)
    # A pair of empty rows has no score and is dropped.
    assert not topn_ops.tanimoto_keep(np.int64(0), np.int64(0),
                                      np.int64(0), 1)


def program_fragment():
    rows = jnp.asarray(prefix_rows(N))
    d = np.arange(1, N + 1)
    row_n = jnp.asarray(d, dtype=jnp.int32)
    every_t = jax.vmap(topn_ops.tanimoto_masked_counts,
                       in_axes=(None, None, None, None, 0))
    ts = jnp.asarray(THRESHOLDS, dtype=jnp.int32)
    for s in range(1, N + 1):
        got = np.asarray(every_t(rows, rows[s - 1], row_n, s, ts))
        want = np.stack([want_counts(d, s, t) for t in THRESHOLDS])
        assert (got == want).all(), s


def program_batched(gathered):
    """The slice axis carries the rows: one candidate whose slice d-1
    is row d, against a probe stack that repeats the probe; as the one
    gathered ``[S, 1, W]`` operand and as a ``[S, W]`` leaf stack."""
    ex = Executor.__new__(Executor)
    ex._batched_cache, ex.BATCHED_FN_CACHE_MAX = {}, 8
    import threading
    ex._cache_mu = threading.Lock()
    fn, hit = ex._batched_topn_tanimoto_fn(1, N, gathered)
    assert not hit and ex._batched_topn_tanimoto_fn(1, N, gathered)[1]
    rows = jnp.asarray(prefix_rows(N))
    d = np.arange(1, N + 1)
    every_t = jax.vmap(fn, in_axes=(None, 0, None))
    ts = jnp.asarray(THRESHOLDS, dtype=jnp.int32)
    cand = rows[:, None, :] if gathered else rows
    for s in range(1, N + 1):
        src = jnp.broadcast_to(rows[s - 1], rows.shape)
        got = np.asarray(every_t(src, ts, cand))[:, 0, :]
        want = np.stack([want_counts(d, s, t) for t in THRESHOLDS])
        assert (got == want).all(), s


def program_batched_gathered():
    program_batched(True)


def program_batched_per_row():
    program_batched(False)


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    h.create_index("i").create_frame("f")
    yield h
    h.close()


def load_prefix_rows(holder, n):
    rows, cols = np.nonzero(np.tril(np.ones((n, n), dtype=np.uint8)))
    holder.index("i").frame("f").import_bits(rows + 1, cols)


def fragment_top(holder):
    load_prefix_rows(holder, N)
    frag = holder.fragment("i", "f", "standard", 0)
    d = np.arange(1, N + 1)
    for s in PROBES:
        src = np.array(frag.row_words(s))
        for t in THRESHOLDS:
            got = frag.top(TopOptions(src=src, tanimoto_threshold=int(t)))
            want = want_counts(d, s, t)
            ids = np.nonzero(want)[0]
            order = np.lexsort((ids, -want[ids]))
            assert got == [(int(i) + 1, int(want[i]))
                           for i in ids[order]], (s, t)


def executor_paths(holder):
    """Both phases on the serial tier and on the batched tier (its
    candidates gathered into one operand, and staged a stack a row as
    past ``TOPN_GATHER_MAX_ROWS``), and an explicit-ids re-query on
    each: the same lists, the rule's."""
    load_prefix_rows(holder, SMALL)
    ex = Executor(holder)
    d = np.arange(1, SMALL + 1)
    every = list(range(1, SMALL + 1))
    for s in SMALL_PROBES:
        for t in SMALL_T:
            want = want_counts(d, s, t)
            ids = np.nonzero(want)[0]
            order = np.lexsort((ids, -want[ids]))
            pairs = [(int(i) + 1, int(want[i])) for i in ids[order]]
            for path, gather_max in (("serial", 0), ("batched", 1024),
                                     ("batched", 0)):
                ex._force_path, ex.TOPN_GATHER_MAX_ROWS = path, gather_max
                src = f'Bitmap(frame="f", rowID={s})'
                assert ex.execute(
                    "i", f'TopN({src}, frame="f", n={SMALL}, '
                         f'tanimotoThreshold={t})')[0] == pairs, \
                    (s, t, path, gather_max)
                assert ex.execute(
                    "i", f'TopN({src}, frame="f", ids={every}, '
                         f'tanimotoThreshold={t})')[0] == pairs, \
                    (s, t, path, gather_max)


@pytest.mark.parametrize("path", [
    keep_numpy, program_fragment, program_batched_gathered,
    program_batched_per_row, fragment_top, executor_paths],
    ids=lambda f: f.__name__)
def test_the_gate_is_the_integer_rule_on_every_path(path, request):
    needs_holder = path in (fragment_top, executor_paths)
    path(request.getfixturevalue("holder")) if needs_holder else path()


def test_no_float_gate_is_left():
    """One function holds the rule; the score and its ceil are gone."""
    import inspect

    from pilosa_tpu import executor as executor_mod

    assert not hasattr(topn_ops, "tanimoto_score_counts")
    for mod in (topn_ops, executor_mod):
        assert ".ceil(" not in inspect.getsource(mod), mod.__name__
