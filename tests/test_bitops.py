"""Golden tests: XLA bit kernels vs NumPy reference semantics.

Mirrors the reference's exhaustive roaring container-pair op tests
(roaring/roaring_test.go) — here every op is one dense kernel so the
matrix of container-type pairs collapses to randomized dense vectors of
varying density (dense≈bitmap containers, sparse≈array, runs≈runs).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pilosa_tpu.ops import bitops
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.ops import topn as topn_ops

W = 2048  # words per test vector (64 KiB of bits)


def mk(rng, density):
    bits = rng.random(W * 32) < density
    return np.packbits(bits, bitorder="little").view(np.uint32)


def np_count(a):
    return int(np.unpackbits(a.view(np.uint8), bitorder="little").sum())


def test_binary_ops(rng):
    for da, db in [(0.5, 0.5), (0.01, 0.9), (0.0, 0.3), (1.0, 1.0)]:
        a, b = mk(rng, da), mk(rng, db)
        ja, jb = jnp.asarray(a), jnp.asarray(b)
        assert np.array_equal(np.asarray(bitops.bitmap_and(ja, jb)), a & b)
        assert np.array_equal(np.asarray(bitops.bitmap_or(ja, jb)), a | b)
        assert np.array_equal(np.asarray(bitops.bitmap_xor(ja, jb)), a ^ b)
        assert np.array_equal(np.asarray(bitops.bitmap_andnot(ja, jb)), a & ~b)


def test_counts(rng):
    a, b = mk(rng, 0.3), mk(rng, 0.6)
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    assert int(bitops.count(ja)) == np_count(a)
    assert int(bitops.count_and(ja, jb)) == np_count(a & b)
    assert int(bitops.count_or(ja, jb)) == np_count(a | b)
    assert int(bitops.count_xor(ja, jb)) == np_count(a ^ b)
    assert int(bitops.count_andnot(ja, jb)) == np_count(a & ~b)


def test_reduce_ops(rng):
    m = np.stack([mk(rng, d) for d in (0.1, 0.5, 0.9, 0.0)])
    jm = jnp.asarray(m)
    assert np.array_equal(
        np.asarray(bitops.union_reduce(jm)), np.bitwise_or.reduce(m, axis=0)
    )
    assert np.array_equal(
        np.asarray(bitops.intersect_reduce(jm)), np.bitwise_and.reduce(m, axis=0)
    )
    assert np.array_equal(
        np.asarray(bitops.xor_reduce(jm)), np.bitwise_xor.reduce(m, axis=0)
    )


def test_count_rows(rng):
    m = np.stack([mk(rng, d) for d in (0.1, 0.5, 0.9)])
    got = np.asarray(bitops.count_rows(jnp.asarray(m)))
    want = [np_count(m[i]) for i in range(3)]
    assert list(got) == want


def test_range_mask():
    for start, end in [(0, 0), (0, 1), (5, 37), (32, 64), (0, W * 32),
                       (31, 33), (100, 100), (W * 32 - 1, W * 32)]:
        mask = np.asarray(bitops.range_mask(jnp.zeros(W, jnp.uint32),
                                            jnp.int32(start), jnp.int32(end)))
        bits = np.unpackbits(mask.view(np.uint8), bitorder="little")
        want = np.zeros(W * 32, dtype=np.uint8)
        want[start:end] = 1
        assert np.array_equal(bits, want), (start, end)


def test_count_range(rng):
    a = mk(rng, 0.4)
    bits = np.unpackbits(a.view(np.uint8), bitorder="little")
    for start, end in [(0, 100), (77, 1000), (0, W * 32), (500, 500)]:
        got = int(bitops.count_range(jnp.asarray(a), jnp.int32(start), jnp.int32(end)))
        assert got == int(bits[start:end].sum())


# --------------------------- BSI ------------------------------------------

def bsi_fixture(rng, n=500, depth=12, width_bits=W * 32):
    """Random int field: returns (values dict col->val, planes, exists)."""
    cols = rng.choice(width_bits, size=n, replace=False)
    vals = rng.integers(0, 1 << depth, size=n)
    planes = np.zeros((depth, W), dtype=np.uint32)
    exists = np.zeros(W, dtype=np.uint32)
    for c, v in zip(cols, vals):
        exists[c >> 5] |= np.uint32(1 << (c & 31))
        for i in range(depth):
            if (int(v) >> i) & 1:
                planes[i][c >> 5] |= np.uint32(1 << (c & 31))
    return dict(zip(cols.tolist(), vals.tolist())), planes, exists


def to_cols(bitmap_words):
    return set(np.flatnonzero(
        np.unpackbits(bitmap_words.view(np.uint8), bitorder="little")).tolist())


def test_bsi_sum(rng):
    vals, planes, exists = bsi_fixture(rng)
    counts = np.asarray(bsi_ops.plane_counts(jnp.asarray(planes), jnp.asarray(exists)))
    total = sum((1 << i) * int(c) for i, c in enumerate(counts))
    assert total == sum(vals.values())


def test_bsi_comparisons(rng):
    vals, planes, exists = bsi_fixture(rng)
    jp, je = jnp.asarray(planes), jnp.asarray(exists)
    depth = planes.shape[0]
    for pred in [0, 1, 777, 2048, (1 << 12) - 1]:
        bits = bsi_ops.value_to_bits(pred, depth)
        cases = {
            "eq": (bsi_ops.bsi_eq, lambda v: v == pred),
            "neq": (bsi_ops.bsi_neq, lambda v: v != pred),
            "lt": (bsi_ops.bsi_lt, lambda v: v < pred),
            "lte": (bsi_ops.bsi_lte, lambda v: v <= pred),
            "gt": (bsi_ops.bsi_gt, lambda v: v > pred),
            "gte": (bsi_ops.bsi_gte, lambda v: v >= pred),
        }
        for name, (fn, want_fn) in cases.items():
            got = to_cols(np.asarray(fn(jp, je, bits)))
            want = {c for c, v in vals.items() if want_fn(v)}
            assert got == want, (name, pred)


def test_bsi_between(rng):
    vals, planes, exists = bsi_fixture(rng)
    lo, hi = 100, 3000
    got = to_cols(np.asarray(bsi_ops.bsi_between(
        jnp.asarray(planes), jnp.asarray(exists),
        bsi_ops.value_to_bits(lo, planes.shape[0]),
        bsi_ops.value_to_bits(hi, planes.shape[0]))))
    want = {c for c, v in vals.items() if lo <= v <= hi}
    assert got == want


def test_bsi_extrema(rng):
    vals, planes, exists = bsi_fixture(rng)
    for find_max in (True, False):
        ind, remaining = bsi_ops.bsi_extrema_indicators(
            jnp.asarray(planes), jnp.asarray(exists), find_max)
        val = sum((1 << i) * int(b) for i, b in enumerate(np.asarray(ind)))
        want = max(vals.values()) if find_max else min(vals.values())
        assert val == want
        n_at = sum(1 for v in vals.values() if v == want)
        assert np_count(np.asarray(remaining)) == n_at


# --------------------------- TopN -----------------------------------------

def _select_reference(masked, elig, min_threshold, n, k):
    """What ``_select_top`` must return, but for the order inside a
    count tie: (counts descending, the k'-th value, n_ge)."""
    cand = np.where(elig & (masked >= max(min_threshold, 1)), masked, 0)
    top = np.sort(cand)[::-1][:k]
    nth = max(int(top[min(n, len(top)) - 1]), 1)
    return cand, top, int((cand >= nth).sum())


# (rows, k): a mirror no longer than the bucket (all of it comes back),
# the flat sort, and more than k chunks of 128 rows (chunk maxima
# first), at two bucket sizes.
SELECT_SHAPES = [(16, 64), (1024, 64), (8192, 64), (16384, 64),
                 (32768, 128)]


@pytest.mark.parametrize("rows, k", SELECT_SHAPES,
                         ids=[f"{r}x{k}" for r, k in SELECT_SHAPES])
@pytest.mark.parametrize("spread", [3, 40, 5000],
                         ids=["ties", "some-ties", "distinct"])
def test_select_top_is_an_exact_top_k(rows, k, spread):
    """The K largest eligible counts with rows that hold them, and
    ``n_ge`` counted over every row: against NumPy, with ties in
    droves, a few, and next to none; ineligible rows and rows under
    ``min_threshold`` read 0 and are never named with a count."""
    rng = np.random.default_rng(rows + spread)
    masked = rng.integers(0, spread, rows).astype(np.int32)
    masked[rng.random(rows) < 0.5] = 0
    elig = rng.random(rows) < 0.8
    elig[-rows // 8:] = False               # the mirror's padded tail
    for n, min_threshold in ((1, 0), (k // 2, 1), (k // 2, 2), (k, 1)):
        out = np.asarray(topn_ops._select_top(
            jnp.asarray(masked), jnp.asarray(elig), min_threshold, n, k))
        kk = min(k, rows)
        cand, top, n_ge = _select_reference(masked, elig, min_threshold, n,
                                            kk)
        assert out.shape == (2 * kk + 1,) and out.dtype == np.int32
        counts, at = out[:kk], out[kk:2 * kk]
        assert list(counts) == list(top)
        assert (cand[at] == counts).all()
        kept = at[counts > 0]
        assert len(set(kept.tolist())) == len(kept) and elig[kept].all()
        assert out[-1] == n_ge
        if n_ge <= kk:
            # the check's promise: every row at or above the n-th count
            # is among the pairs, so the host's cut by id is exact
            nth = max(int(top[min(n, kk) - 1]), 1)
            assert set(np.nonzero(cand >= nth)[0].tolist()) \
                <= set(kept.tolist())


def test_select_k_is_the_power_of_two_at_or_above_2n():
    assert [topn_ops.select_k(n) for n in (1, 32, 33, 50, 64, 65, 512, 513)] \
        == [64, 64, 128, 128, 128, 256, 1024, 2048]
    assert topn_ops.select_k(512) == topn_ops.SELECT_MAX_K


def test_the_select_programs_equal_the_masked_counts_selected(rng):
    """``tanimoto_select`` / ``tanimoto_select_at`` are the programs
    that return a count a row, with the tail: one int32[4] of scalars,
    a threshold of 0 no gate at all."""
    m = np.stack([mk(rng, d) for d in rng.random(40)])
    m[30:] = 0
    dm = jnp.asarray(m)
    row_n = np.array([np_count(r) for r in m], dtype=np.int32)
    elig = np.arange(40) < 30
    for phys, t, min_threshold, n in ((3, 0, 1, 5), (7, 30, 1, 40),
                                      (11, 50, 200, 10)):
        src_n = int(row_n[phys])
        masked = np.asarray(topn_ops.tanimoto_masked_counts(
            dm, dm[phys], jnp.asarray(row_n), src_n, t))
        if not t:
            assert (masked == np.asarray(
                bitops.count_and_rows(dm, dm[phys]))).all()
        want = np.asarray(topn_ops._select_top(
            jnp.asarray(masked), jnp.asarray(elig), min_threshold, n, 64))
        at = topn_ops.tanimoto_select_at(
            dm, np.array([phys, t, min_threshold, n], dtype=np.int32),
            jnp.asarray(row_n), jnp.asarray(elig), k=64)
        host = topn_ops.tanimoto_select(
            dm, dm[phys], np.array([src_n, t, min_threshold, n],
                                   dtype=np.int32),
            jnp.asarray(row_n), jnp.asarray(elig), k=64)
        assert list(np.asarray(at)) == list(np.asarray(host)) == list(want)
        assert want[0] == src_n >= 1 or min_threshold > src_n
    for fn in (topn_ops.tanimoto_select, topn_ops.tanimoto_select_at):
        assert fn.__name__.startswith("pilosa_topn_tanimoto_frag")


def test_count_and_rows_and_the_tanimoto_gate(rng):
    m = np.stack([mk(rng, d) for d in (0.2, 0.8, 0.5)])
    src = mk(rng, 0.5)
    inter = bitops.count_and_rows(jnp.asarray(m), jnp.asarray(src))
    row_n = jnp.sum(
        jax.lax.population_count(jnp.asarray(m)).astype(jnp.int32), axis=-1)
    src_n = jnp.sum(jax.lax.population_count(jnp.asarray(src)).astype(jnp.int32))
    for i in range(3):
        a, b, x = np_count(m[i]), np_count(src), np_count(m[i] & src)
        assert int(inter[i]) == x
        # The gate in integers: ceil(100x / (a+b-x)) > t.
        for t in (1, 100 * x // (a + b - x), 100):
            want = 100 * x > t * (a + b - x)
            assert bool(topn_ops.tanimoto_keep(inter, row_n, src_n, t)[i]) \
                == want
            masked = topn_ops.tanimoto_masked_counts(
                jnp.asarray(m), jnp.asarray(src), row_n, src_n, t)
            assert int(masked[i]) == (x if want else 0)


def test_range_mutation(rng):
    """set_range/flip_range/zero_range vs NumPy bit twiddling
    (ref: Flip roaring.go:800, bitmapSetRange/XorRange/ZeroRange
    roaring.go:2292-2360)."""
    W = 64
    a = rng.integers(0, 1 << 32, size=W, dtype=np.uint64).astype(np.uint32)
    bits = np.unpackbits(a.view(np.uint8), bitorder="little")
    for start, end in [(0, 0), (5, 70), (31, 33), (0, W * 32), (100, 100)]:
        mask = np.zeros(W * 32, dtype=np.uint8)
        mask[start:end] = 1
        for fn, expect in [
            (bitops.set_range, bits | mask),
            (bitops.flip_range, bits ^ mask),
            (bitops.zero_range, bits & ~mask & 1),
        ]:
            got = np.asarray(fn(jnp.asarray(a), jnp.int32(start),
                                jnp.int32(end)))
            got_bits = np.unpackbits(got.view(np.uint8), bitorder="little")
            assert np.array_equal(got_bits, expect), (fn.__name__, start, end)
