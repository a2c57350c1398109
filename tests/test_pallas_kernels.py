"""Pallas kernel parity vs NumPy. These tests ask for the interpreter
(``interpret=True``) because the CPU test mesh has no Mosaic; the
compiled form is checked on the chip by ``chip_smoke.py``."""
import numpy as np

from pilosa_tpu.ops import pallas_kernels as pk


def _rand(shape, seed):
    return np.random.default_rng(seed).integers(
        0, 1 << 32, size=shape, dtype=np.uint64).astype(np.uint32)


def test_count_and_matches_numpy():
    a = _rand((8, 512), 0)
    b = _rand((8, 512), 1)
    want = int(np.bitwise_count(a & b).sum())
    assert int(pk.count_and(a, b, interpret=True)) == want


def test_count_and_1d():
    a = _rand((256,), 2)
    b = _rand((256,), 3)
    want = int(np.bitwise_count(a & b).sum())
    assert int(pk.count_and(a, b, interpret=True)) == want


def test_count_rows_matches_numpy():
    m = _rand((16, 384), 4)
    want = np.bitwise_count(m).sum(axis=1)
    got = np.asarray(pk.count_rows(m, interpret=True))
    assert (got == want).all()


def test_count_and_rows_matches_numpy():
    m = _rand((12, 256), 5)
    f = _rand((256,), 6)
    want = np.bitwise_count(m & f).sum(axis=1)
    got = np.asarray(pk.count_and_rows(m, f, interpret=True))
    assert (got == want).all()


def test_non_lane_multiple_width_padded():
    # widths not a multiple of 128 are zero-padded by the wrappers
    m = _rand((8, 192), 7)
    f = _rand((192,), 8)
    assert int(pk.count_and(m, m, interpret=True)) == int(
        np.bitwise_count(m).sum())
    got = np.asarray(pk.count_and_rows(m, f, interpret=True))
    assert (got == np.bitwise_count(m & f).sum(axis=1)).all()
    got = np.asarray(pk.count_rows(m, interpret=True))
    assert (got == np.bitwise_count(m).sum(axis=1)).all()


def test_non_sublane_multiple_rows_padded():
    # row counts not a multiple of 8 are zero-padded and trimmed
    m = _rand((12, 256), 9)
    f = _rand((256,), 10)
    assert int(pk.count_and(m, m, interpret=True)) == int(
        np.bitwise_count(m).sum())
    got = np.asarray(pk.count_and_rows(m, f, interpret=True))
    assert got.shape == (12,)
    assert (got == np.bitwise_count(m & f).sum(axis=1)).all()


def test_interpretation_is_asked_for_never_sniffed():
    """Without ``interpret=True`` the kernels compile for the TPU: on
    the CPU mesh that is an error, not a quiet switch of mode."""
    import pytest

    a = _rand((8, 256), 11)
    with pytest.raises(ValueError, match="interpret mode"):
        pk.count_and(a, a)
