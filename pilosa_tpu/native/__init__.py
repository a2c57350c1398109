"""ctypes loader for the native host runtime (roaring.cpp).

Compiles on demand with g++ (cached beside the source). Every consumer
keeps a bit-identical pure-Python implementation, so a host without a
toolchain still serves — but never quietly: a failed build or load is
logged, and ``nativeLoaded`` in the /debug/vars ``device`` block says
which implementation is running. :func:`build` is the strict entry
(``make native``, the Dockerfile, ``chip_smoke.py``): it raises.
"""
import ctypes
import logging
import os
import subprocess
import threading

from pilosa_tpu import lockcheck

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "roaring.cpp")
_SO = os.path.join(_HERE, "libpilosa_native.so")

_LOG = logging.getLogger("pilosa_tpu.native")

_lock = lockcheck.register("native._lock", threading.Lock())
_lib = None
_tried = False


def _own(path, suffix):
    """A sibling of ``path`` that only this call writes. Processes
    that meet a tree with no library at the same moment (xdist workers,
    the worker tier's first boot) each compile into a file of their own
    and install it by rename, so ``path`` is only ever absent or
    whole. The random part covers containers that share a checkout
    and a pid."""
    return f"{path}.{os.getpid()}.{os.urandom(4).hex()}{suffix}"


def _unlink(path):
    try:
        os.unlink(path)
    except OSError:
        pass


def build(out=_SO):
    """Compile roaring.cpp into ``out``; raise RuntimeError with the
    compiler's own words when g++ is missing or refuses. Installed by
    rename, so a process that has the old object mapped keeps it."""
    tmp = _own(out, ".tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    except FileNotFoundError as exc:
        raise RuntimeError(f"native build impossible: {exc}") from exc
    except subprocess.CalledProcessError as exc:
        _unlink(tmp)  # whatever the compiler got as far as writing
        raise RuntimeError(
            f"native build failed (rc={exc.returncode}): "
            f"{exc.stderr.strip()[-2000:]}") from exc


def _unavailable(exc):
    _LOG.warning("native runtime unavailable, serving from pure "
                 "Python: %s", exc)
    return None


def load():
    """Return the loaded library or None."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                build()
            lib = ctypes.CDLL(_SO)
            lib.pn_serialize_w  # newest symbol: stale .so (equal mtimes
        except AttributeError:  # after checkout) -> force one rebuild
            # dlopen dedups by path against the stale handle already
            # mapped above, so the rebuild must load from a fresh
            # path; the fresh build also replaces _SO for next time.
            rebuilt = _own(_SO, ".rebuild.so")
            try:
                build(rebuilt)
                lib = ctypes.CDLL(rebuilt)
                lib.pn_serialize_w
                os.replace(rebuilt, _SO)
            except (OSError, RuntimeError, AttributeError) as exc:
                _unlink(rebuilt)
                return _unavailable(exc)
        except (OSError, RuntimeError) as exc:
            return _unavailable(exc)

        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)

        lib.pn_xxhash64.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint64]
        lib.pn_xxhash64.restype = ctypes.c_uint64
        lib.pn_fnv32a.argtypes = [u8p, ctypes.c_size_t]
        lib.pn_fnv32a.restype = ctypes.c_uint32
        lib.pn_extract_positions.argtypes = [u64p, ctypes.c_int64,
                                             ctypes.c_uint64, u64p]
        lib.pn_extract_positions.restype = ctypes.c_int64
        lib.pn_popcount.argtypes = [u64p, ctypes.c_int64]
        lib.pn_popcount.restype = ctypes.c_int64
        lib.pn_serialized_size_w.argtypes = [u64p, ctypes.c_int64,
                                             ctypes.c_int64, u8p, i32p,
                                             i32p]
        lib.pn_serialized_size_w.restype = ctypes.c_int64
        lib.pn_serialize_w.argtypes = [u64p, u64p, ctypes.c_int64,
                                       ctypes.c_int64, u8p, i32p,
                                       i32p, u8p]
        lib.pn_serialize_w.restype = ctypes.c_int64
        lib.pn_header_info.argtypes = [u8p, ctypes.c_int64]
        lib.pn_header_info.restype = ctypes.c_int64
        lib.pn_deserialize.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64,
                                       u64p, u64p]
        lib.pn_deserialize.restype = ctypes.c_int64
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.pn_parse_csv.argtypes = [u8p, ctypes.c_int64, i64p,
                                     ctypes.c_int64]
        lib.pn_parse_csv.restype = ctypes.c_int64
        lib.pn_encode_ops.argtypes = [u8p, u64p, ctypes.c_int64, u8p]
        lib.pn_encode_ops.restype = None
        lib.pn_popcount_rows.argtypes = [u64p, ctypes.c_int64, i64p,
                                         ctypes.c_int64, i64p]
        lib.pn_popcount_rows.restype = None
        lib.pn_scatter_or.argtypes = [u64p, ctypes.c_int64, i64p, u64p,
                                      ctypes.c_int64]
        lib.pn_scatter_or.restype = None
        _lib = lib
        return _lib


def available():
    return load() is not None


# ------------------------------------------------------- numpy front-ends

def _u8(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _u64(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def xxhash64(data: bytes, seed: int = 0):
    lib = load()
    if lib is None:
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) if data else \
        (ctypes.c_uint8 * 1)()
    return int(lib.pn_xxhash64(
        ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)), len(data), seed))


def extract_positions(words, base=0):
    """np.uint64 packed words -> np.uint64 sorted set-bit positions."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint64)
    n = int(lib.pn_popcount(_u64(words), words.size))
    out = np.empty(n, dtype=np.uint64)
    k = int(lib.pn_extract_positions(_u64(words), words.size, base,
                                     _u64(out)))
    return out[:k]


def serialize(keys, blocks):
    """(np.uint64[n], np.uint64[n, stride]) -> roaring file bytes.

    ``blocks`` may be NARROW (stride < 1024 words per container):
    words beyond the stride are implicitly zero, and the native side
    scans only the true span — on row-heavy narrow fragments the
    zero-padded scan was up to 16x the memory bandwidth of the data.
    """
    import numpy as np

    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    blocks = np.ascontiguousarray(blocks, dtype=np.uint64)
    n = keys.size
    stride = blocks.shape[1] if blocks.ndim == 2 and n else 1024
    if stride > 1024:
        # A wider-than-container block would overrun the 8 KiB bitmap
        # payload slot in the native writer — reject loudly rather
        # than corrupt the heap.
        raise ValueError(f"container blocks are at most 1024 words, "
                         f"got {stride}")
    types = np.zeros(n, dtype=np.uint8)
    sizes = np.zeros(n, dtype=np.int32)
    cards = np.zeros(n, dtype=np.int32)
    total = int(lib.pn_serialized_size_w(
        _u64(blocks), n, stride, _u8(types),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cards.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))))
    out = np.empty(total, dtype=np.uint8)
    written = int(lib.pn_serialize_w(
        _u64(keys), _u64(blocks), n, stride, _u8(types),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cards.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), _u8(out)))
    return out[:written].tobytes()


def deserialize(data: bytes):
    """roaring file bytes -> (keys np.uint64[n], blocks np.uint64[n,1024],
    oplog_offset) or None (fallback) ; raises ValueError on bad file."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    count = int(lib.pn_header_info(_u8(buf), buf.size))
    if count == -1:
        raise ValueError("invalid roaring file, magic number mismatch")
    if count == -2:
        raise ValueError("wrong roaring version")
    keys = np.zeros(count, dtype=np.uint64)
    blocks = np.zeros((count, 1024), dtype=np.uint64)
    end = int(lib.pn_deserialize(_u8(buf), buf.size, count, _u64(keys),
                                 _u64(blocks)))
    if end < 0:
        raise ValueError("corrupt roaring container data")
    return keys, blocks, end


def parse_csv(data: bytes):
    """Numeric CSV bytes -> np.int64[n, 3] (missing fields 0), or None
    (no native lib). Raises ValueError with the 1-based line number on
    a malformed line — matching the CLI's int() failure behavior."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    # upper bound: one record per line
    max_rec = int(np.count_nonzero(buf == ord("\n"))) + 1
    out = np.zeros((max_rec, 3), dtype=np.int64)
    n = int(lib.pn_parse_csv(
        _u8(buf), buf.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), max_rec))
    if n < 0:
        raise ValueError(f"malformed CSV at line {-n}")
    return out[:n]


def encode_ops(typs, values):
    """Batch-encode op-log records: (np.uint8[n], np.uint64[n]) ->
    13n bytes, or None (no native lib)."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    typs = np.ascontiguousarray(typs, dtype=np.uint8)
    values = np.ascontiguousarray(values, dtype=np.uint64)
    out = np.empty(13 * typs.size, dtype=np.uint8)
    lib.pn_encode_ops(_u8(typs), _u64(values), typs.size, _u8(out))
    return out.tobytes()


def _i64(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def popcount_rows(matrix, rows):
    """Per-row popcount of a C-contiguous np.uint64[cap, W] matrix:
    returns np.int64[len(rows)], or None (no native lib)."""
    import numpy as np

    # gate on available(): it is the monkeypatch seam the fallback
    # tests use to force-disable the native layer (load() is cached,
    # so the extra call is a dict check)
    lib = load() if available() else None
    if (lib is None or not matrix.flags["C_CONTIGUOUS"]
            or matrix.dtype != np.uint64):
        return None
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    out = np.empty(rows.size, dtype=np.int64)
    lib.pn_popcount_rows(_u64(matrix), matrix.shape[-1], _i64(rows),
                         rows.size, _i64(out))
    return out


def scatter_or(matrix, phys, cols):
    """matrix[phys[i]][cols[i]>>6] |= 1 << (cols[i]&63), in place.
    Returns False (caller must fall back) when the lib is missing or
    the matrix is not C-contiguous."""
    import numpy as np

    # available() is the test seam; see popcount_rows
    lib = load() if available() else None
    if (lib is None or not matrix.flags["C_CONTIGUOUS"]
            or matrix.dtype != np.uint64):
        return False
    phys = np.ascontiguousarray(phys, dtype=np.int64)
    cols = np.ascontiguousarray(cols, dtype=np.uint64)
    lib.pn_scatter_or(_u64(matrix), matrix.shape[-1], _i64(phys),
                      _u64(cols), phys.size)
    return True
