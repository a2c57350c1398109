"""Native C++ runtime parity tests: the ctypes-loaded codec/hashing must
be bit-identical to the pure-Python implementations, and every consumer
must work with the native layer force-disabled (fallback coverage).
The loader itself is tested under concurrent first use, on a copy."""
import ctypes
import os
import shutil
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from pilosa_tpu import native
from pilosa_tpu.roaring import codec
from pilosa_tpu.utils.xxhash import _xxhash64_py, xxhash64

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no g++ on this host")


@pytest.fixture(autouse=True)
def _library_loaded():
    """With a compiler on the host the library must load: a module
    that skipped itself when it did not is how seventeen tests once
    vanished from a clean checkout's count. Fail with the reason."""
    if not native.available():
        native.build()            # raises with the compiler's words
        ctypes.CDLL(native._SO)   # or with the dynamic loader's
        pytest.fail("the library builds and loads now, but load() gave "
                    "up earlier in this process: see its warning")


def test_xxhash_parity(rng):
    for n in (0, 1, 3, 4, 7, 8, 13, 31, 32, 33, 100, 1024, 5000):
        data = rng.integers(0, 256, size=n).astype(np.uint8).tobytes()
        assert native.xxhash64(data, 0) == _xxhash64_py(data, 0), n
        assert native.xxhash64(data, 7) == _xxhash64_py(data, 7), n
    assert xxhash64(b"hello") == _xxhash64_py(b"hello")


def test_extract_positions(rng):
    words = rng.integers(0, 1 << 63, size=64, dtype=np.uint64)
    got = native.extract_positions(words, base=1000)
    want = np.flatnonzero(np.unpackbits(
        words.view(np.uint8), bitorder="little")).astype(np.uint64) + 1000
    assert np.array_equal(got, want)
    assert native.extract_positions(np.zeros(4, np.uint64)).size == 0


def _random_blocks(rng):
    def dense(density):
        bits = rng.random(codec.BITMAP_N * 64) < density
        return np.packbits(bits, bitorder="little").view(np.uint64)

    run_block = np.zeros(codec.BITMAP_N * 64, dtype=np.uint8)
    run_block[100:30000] = 1
    return {
        0: dense(0.001),                 # array container
        2: dense(0.4),                   # bitmap container
        9: np.packbits(run_block, bitorder="little").view(np.uint64),  # run
        (1 << 30): dense(0.01),
    }


def test_serialize_parity(rng, monkeypatch):
    blocks = _random_blocks(rng)
    native_bytes = codec.serialize(blocks)
    monkeypatch.setattr(native, "available", lambda: False)
    python_bytes = codec.serialize(blocks)
    assert native_bytes == python_bytes


def test_cross_deserialize(rng, monkeypatch):
    blocks = _random_blocks(rng)
    data = codec.serialize(blocks)  # native encoder
    ops = codec.op_record(codec.OP_ADD, (5 << 16) | 77)

    native_out, n_ops, torn = codec.deserialize(data + ops)
    monkeypatch.setattr(native, "available", lambda: False)
    python_out, n_ops2, torn2 = codec.deserialize(data + ops)

    assert (n_ops, torn) == (n_ops2, torn2) == (1, False)
    assert set(native_out) == set(python_out)
    for k in python_out:
        assert np.array_equal(native_out[k], python_out[k]), k


def test_native_rejects_corruption():
    with pytest.raises(ValueError, match="magic"):
        codec.deserialize(b"\x01\x02\x03\x04\x05\x06\x07\x08" * 2)


def test_fragment_with_python_fallback(tmp_path, monkeypatch):
    """Full fragment lifecycle must work without the native library."""
    monkeypatch.setattr(native, "available", lambda: False)
    from pilosa_tpu.storage.fragment import Fragment

    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    # duplicate bits + same-word collisions exercise the sort/reduceat
    # OR-fold in the NumPy fallback path
    f.import_bits([0, 1, 0, 0, 1], [5, 6, 5, 7, 70])
    assert f.count() == 4
    assert f.row_count(0) == 2 and f.row_count(1) == 2
    assert [b for b, _ in f.blocks()] == [0]
    f.close()
    f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    assert f2.count() == 4
    f2.close()


# ------------------------------------------------------- CSV + op batch

def test_parse_csv_matches_python():
    data = b"1,2\n3,4,1500000000\n\n10,20\r\n-5,7\n"
    got = native.parse_csv(data)
    assert got.tolist() == [[1, 2, 0], [3, 4, 1500000000],
                            [10, 20, 0], [-5, 7, 0]]


def test_parse_csv_spaces_and_signs():
    got = native.parse_csv(b" 1 , 2 \n+3,-4\n")
    assert got.tolist() == [[1, 2, 0], [3, -4, 0]]


def test_parse_csv_malformed_reports_line():
    import pytest
    with pytest.raises(ValueError, match="line 2"):
        native.parse_csv(b"1,2\n1,x\n")


def test_parse_csv_empty():
    assert native.parse_csv(b"").shape == (0, 3)
    assert native.parse_csv(b"\n\n").shape == (0, 3)


def test_encode_ops_matches_python_records():
    import numpy as np
    from pilosa_tpu.roaring import codec

    typs = np.array([codec.OP_ADD, codec.OP_REMOVE, codec.OP_ADD],
                    dtype=np.uint8)
    vals = np.array([0, 123456789, 2**63 + 5], dtype=np.uint64)
    got = native.encode_ops(typs, vals)
    want = b"".join(codec.op_record(int(t), int(v))
                    for t, v in zip(typs, vals))
    assert got == want
    # and the decoder round-trips it
    assert list(codec.read_ops(got)) == [
        (int(t), int(v)) for t, v in zip(typs, vals)]


def test_parse_csv_trailing_comma_rejected():
    import pytest
    with pytest.raises(ValueError, match="line 1"):
        native.parse_csv(b"1,2,\n")


def test_parse_csv_overflow_rejected():
    import pytest
    with pytest.raises(ValueError, match="line 1"):
        native.parse_csv(b"99999999999999999999,1\n")
    # INT64_MAX itself is accepted
    got = native.parse_csv(b"9223372036854775807,1\n")
    assert got[0, 0] == 2**63 - 1


def test_scatter_or_matches_numpy_reference():
    import numpy as np

    rng = np.random.default_rng(11)
    W = 64
    m = np.zeros((8, W), dtype=np.uint64)
    phys = rng.integers(0, 8, size=5000, dtype=np.int64)
    cols = rng.integers(0, W * 64, size=5000, dtype=np.uint64)
    assert native.scatter_or(m, phys, cols)

    want = np.zeros_like(m)
    for p, c in zip(phys, cols):
        want[p, int(c) >> 6] |= np.uint64(1) << np.uint64(int(c) & 63)
    assert (m == want).all()


def test_popcount_rows_matches_numpy():
    import numpy as np

    rng = np.random.default_rng(12)
    m = rng.integers(0, 2**63, size=(16, 128), dtype=np.uint64)
    rows = [0, 3, 15, 3]
    got = native.popcount_rows(m, rows)
    want = np.bitwise_count(m[rows]).sum(axis=-1, dtype=np.int64)
    assert got.tolist() == want.tolist()


def test_scatter_or_noncontiguous_falls_back():
    import numpy as np

    m = np.zeros((4, 128), dtype=np.uint64)[:, ::2]
    assert not native.scatter_or(m, np.array([0]), np.array([0],
                                                           dtype=np.uint64))


def test_scatter_or_wrong_dtype_falls_back():
    import numpy as np

    m32 = np.zeros((4, 256), dtype=np.uint32)  # device-mirror layout
    assert not native.scatter_or(m32, np.array([0]),
                                 np.array([0], dtype=np.uint64))
    assert native.popcount_rows(m32, [0]) is None


# ------------------------------------------------ the loader, concurrently

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One first user of a copied loader: import, wait for the common start,
# load twice (the second must be the cached answer, and silent).
_FIRST_USER = textwrap.dedent("""
    import logging, sys, time
    logging.basicConfig(stream=sys.stderr, format="LOG %(message)s")
    sys.path[:0] = [sys.argv[1], sys.argv[2]]
    import native_copy
    time.sleep(max(0.0, float(sys.argv[3]) - time.monotonic()))
    print(native_copy.available(), native_copy.available(),
          native_copy.xxhash64(b"one buffer", 7))
""")

# Stands in for g++ on PATH: gets as far as writing its output file,
# then fails, like a compiler that is killed or runs out of disk.
_BROKEN_GXX = textwrap.dedent("""\
    #!/bin/sh
    while [ $# -gt 0 ]; do
        [ "$1" = -o ] && echo half > "$2"
        shift
    done
    echo "stub: no space left on device" >&2
    exit 1
""")


@pytest.mark.parametrize("case", ["cold", "stale", "broken-compiler"])
def test_concurrent_first_use(tmp_path, case):
    """Six processes meet a copy of pilosa_tpu/native/ at once. With no
    library (cold), or one from before pn_serialize_w (stale: all six
    take the rebuild branch), every one of them ends up serving from
    the native library and nothing but it is left beside the source.
    With a compiler that fails, every one logs once and falls back,
    and neither a library nor a temporary is left."""
    pkg = tmp_path / "native_copy"
    pkg.mkdir()
    for name in ("__init__.py", "roaring.cpp"):
        shutil.copy2(os.path.join(os.path.dirname(native.__file__), name),
                     pkg / name)
    env = dict(os.environ)
    if case == "stale":
        so = pkg / "libpilosa_native.so"
        old = tmp_path / "old.cpp"
        old.write_text('extern "C" long pn_popcount() { return 0; }\n')
        subprocess.run(["g++", "-shared", "-fPIC", "-o", str(so), str(old)],
                       check=True)
        assert so.stat().st_mtime >= (pkg / "roaring.cpp").stat().st_mtime
    elif case == "broken-compiler":
        stub = tmp_path / "bin" / "g++"
        stub.parent.mkdir()
        stub.write_text(_BROKEN_GXX)
        stub.chmod(0o755)
        env["PATH"] = f"{stub.parent}{os.pathsep}{env['PATH']}"

    start = time.monotonic() + 1.0  # one clock for every process
    procs = [subprocess.Popen(
        [sys.executable, "-c", _FIRST_USER, str(tmp_path), _REPO,
         repr(start)], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE) for _ in range(6)]
    try:
        outs = [p.communicate(timeout=120) + (p.returncode,) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    left = sorted(p.name for p in pkg.iterdir() if p.name != "__pycache__")
    for out, err, rc in outs:
        assert rc == 0, err
        warned = err.count("LOG native runtime unavailable")
        if case == "broken-compiler":
            assert out.split() == ["False", "False", "None"], (out, err)
            assert warned == 1 and "no space left" in err, err
        else:
            assert out.split() == ["True", "True",
                                   str(_xxhash64_py(b"one buffer", 7))], \
                (out, err)
            assert warned == 0, err
    want = ["__init__.py", "roaring.cpp"]
    if case != "broken-compiler":
        want.insert(1, "libpilosa_native.so")
    assert left == want
