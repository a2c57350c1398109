"""Multi-process serving: worker HTTP frontends + master plan service.

The reference serves every connection on its own goroutine across all
cores (ref: server.go:205-217 http.Serve). A single CPython process
cannot do that — HTTP parsing, routing, and response encoding all hold
the GIL, which capped round-3 serving at ~700 q/s no matter the client
count (BASELINE.md "GIL analysis"). The TPU-native shape of the fix
splits serving across processes around the one resource that must stay
singly-owned — the accelerator:

- N WORKER processes bind the SAME public port via ``SO_REUSEPORT``
  (the kernel load-balances accepted connections, the moral equivalent
  of Go's shared listener + goroutine-per-conn). Workers do the
  GIL-heavy transport half: HTTP parse, header handling, response
  write. Phase 2 (`PILOSA_TPU_WORKER_EXEC`, see worker.py) moves
  read-only query execution into the workers too, against their own
  holder replica refreshed by a shared mutation epoch.
- The MASTER keeps exclusive ownership of the device, the holder, and
  every write path. Workers relay requests over persistent unix-domain
  sockets as length-prefixed binary frames; the master answers with
  ``Handler.dispatch`` directly — no HTTP parsing ever touches its
  GIL. Cross-query count coalescing happens in the master exactly as
  before, now fed by genuinely concurrent worker streams.

Trust boundary: the unix socket lives in a freshly-created 0700
directory with 0600 socket permissions — an INTERNAL transport between
processes of the same installation, never exposed on the network. The
frames themselves are nevertheless a closed, data-only codec (below):
no pickle, so a reachable socket is at worst a request-forgery surface,
never code execution.

Frame codec: a deliberately tiny self-describing binary format for the
relay tuples (method, path, query-params, body, headers) and responses
(status, content-type, payload[, extra headers]). Tags: N one=None,
T/F=bool, I=int64, S=utf-8 string, B=bytes, L=list, U=tuple, D=dict —
each length-prefixed. Unlike pickle it can only ever produce these
eight shapes; truncated/oversized/garbage input raises ``FrameError``
(fuzzed in tests/test_workers.py). The discipline mirrors the schema'd
internal/private.proto data plane (ref: internal/private.proto).
"""
import os
import socket
import struct
import subprocess
import sys
import threading

_LEN = struct.Struct("<I")
_I64 = struct.Struct("<q")
MAX_FRAME = 1 << 30
_MAX_DEPTH = 16


class FrameError(ValueError):
    """Malformed relay frame (truncated, oversized, or garbage)."""


# Integer tag constants: the codec sits on the per-request relay hot
# path, so both directions dispatch on small-int compares over a
# bytes/bytearray buffer (no per-token slicing or struct round trips
# beyond the length words).
_T_NONE, _T_TRUE, _T_FALSE = ord("N"), ord("T"), ord("F")
_T_INT, _T_STR, _T_BYTES = ord("I"), ord("S"), ord("B")
_T_LIST, _T_TUPLE, _T_DICT = ord("L"), ord("U"), ord("D")


def _pack_into(obj, out, depth=0):
    if depth > _MAX_DEPTH:
        raise FrameError("frame nesting too deep")
    t = type(obj)
    if t is str:
        raw = obj.encode()
        out.append(_T_STR)
        out += _LEN.pack(len(raw))
        out += raw
    elif t is bytes:
        out.append(_T_BYTES)
        out += _LEN.pack(len(obj))
        out += obj
    elif t is bool:  # before int: bool is an int subclass
        out.append(_T_TRUE if obj else _T_FALSE)
    elif t is int:
        out.append(_T_INT)
        out += _I64.pack(obj)
    elif obj is None:
        out.append(_T_NONE)
    elif t is list or t is tuple:
        out.append(_T_LIST if t is list else _T_TUPLE)
        out += _LEN.pack(len(obj))
        for item in obj:
            _pack_into(item, out, depth + 1)
    elif t is dict:
        out.append(_T_DICT)
        out += _LEN.pack(len(obj))
        for k, v in obj.items():
            _pack_into(k, out, depth + 1)
            _pack_into(v, out, depth + 1)
    # Subclass fallbacks (slow path; bool needs none — it is final).
    # Coerce through the BASE type's methods, never subclass hooks, so
    # an adversarial override can't recurse or change the bytes.
    elif isinstance(obj, str):
        raw = str.encode(obj)
        out.append(_T_STR)
        out += _LEN.pack(len(raw))
        out += raw
    elif isinstance(obj, (bytes, bytearray, memoryview)):
        raw = bytes(obj)
        out.append(_T_BYTES)
        out += _LEN.pack(len(raw))
        out += raw
    elif isinstance(obj, int):
        out.append(_T_INT)
        out += _I64.pack(obj)
    elif isinstance(obj, (list, tuple)):
        out.append(_T_LIST if isinstance(obj, list) else _T_TUPLE)
        out += _LEN.pack(len(obj))
        for item in obj:
            _pack_into(item, out, depth + 1)
    elif isinstance(obj, dict):
        out.append(_T_DICT)
        out += _LEN.pack(len(obj))
        for k, v in obj.items():
            _pack_into(k, out, depth + 1)
            _pack_into(v, out, depth + 1)
    else:
        raise TypeError(f"frame cannot carry {type(obj).__name__}")


def pack(obj):
    out = bytearray()
    _pack_into(obj, out)
    return bytes(out)


def _unpack_from(data, pos, end, depth=0):
    """data: bytes; returns (obj, new_pos). Bounds-checked against
    ``end`` before every read; any violation raises FrameError."""
    if depth > _MAX_DEPTH:
        raise FrameError("frame nesting too deep")
    if pos >= end:
        raise FrameError("truncated frame")
    tag = data[pos]
    pos += 1
    if tag == _T_STR or tag == _T_BYTES:
        if pos + 4 > end:
            raise FrameError("truncated frame")
        (n,) = _LEN.unpack_from(data, pos)
        pos += 4
        if pos + n > end:
            raise FrameError("truncated frame")
        raw = data[pos:pos + n]
        pos += n
        if tag == _T_BYTES:
            return raw, pos
        try:
            return raw.decode(), pos
        except UnicodeDecodeError as exc:
            raise FrameError(f"bad utf-8 in frame: {exc}") from None
    if tag == _T_NONE:
        return None, pos
    if tag == _T_TRUE:
        return True, pos
    if tag == _T_FALSE:
        return False, pos
    if tag == _T_INT:
        if pos + 8 > end:
            raise FrameError("truncated frame")
        val = _I64.unpack_from(data, pos)[0]
        return val, pos + 8
    if tag == _T_LIST or tag == _T_TUPLE:
        if pos + 4 > end:
            raise FrameError("truncated frame")
        (n,) = _LEN.unpack_from(data, pos)
        pos += 4
        if n > end - pos:  # every element costs ≥ 1 byte
            raise FrameError("collection count exceeds frame")
        items = []
        for _ in range(n):
            item, pos = _unpack_from(data, pos, end, depth + 1)
            items.append(item)
        return (items if tag == _T_LIST else tuple(items)), pos
    if tag == _T_DICT:
        if pos + 4 > end:
            raise FrameError("truncated frame")
        (n,) = _LEN.unpack_from(data, pos)
        pos += 4
        if n > (end - pos) // 2:  # a pair costs ≥ 2 bytes
            raise FrameError("dict count exceeds frame")
        d = {}
        for _ in range(n):
            k, pos = _unpack_from(data, pos, end, depth + 1)
            v, pos = _unpack_from(data, pos, end, depth + 1)
            try:
                d[k] = v
            except TypeError:  # e.g. a tuple key wrapping a list
                raise FrameError("unhashable dict key in frame") from None
        return d, pos
    raise FrameError(f"unknown frame tag {chr(tag)!r}")


def unpack(data):
    data = bytes(data)
    try:
        obj, pos = _unpack_from(data, 0, len(data))
    except struct.error as exc:
        raise FrameError(str(exc)) from None
    if pos != len(data):
        raise FrameError(f"{len(data) - pos} trailing bytes in frame")
    return obj


def write_frame(sock, obj):
    data = pack(obj)
    sock.sendall(_LEN.pack(len(data)) + data)


def read_frame(sock):
    hdr = _read_exact(sock, _LEN.size)
    if hdr is None:
        return None
    (n,) = _LEN.unpack(hdr)
    if n > MAX_FRAME:
        raise FrameError(f"frame too large: {n}")
    data = _read_exact(sock, n)
    if data is None:
        return None
    return unpack(data)


def _read_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf.extend(chunk)
    return bytes(buf)


class PlanServer:
    """Master-side unix-socket service answering worker frames with
    Handler.dispatch. One daemon thread per worker connection — worker
    connections are per-HTTP-client and long-lived, so the thread
    count tracks concurrent clients the same way ThreadingHTTPServer's
    does, minus the HTTP parsing those threads used to do."""

    def __init__(self, dispatch, sock_path):
        self.dispatch = dispatch
        self.sock_path = sock_path
        self._sock = None
        self._closing = threading.Event()

    def open(self):
        # The pre-bind unlink can fail with more than FileNotFoundError
        # (e.g. EPERM on a sticky-dir entry someone else planted):
        # surface anything but "already absent" as a clear startup
        # error instead of crashing later in bind().
        try:
            os.unlink(self.sock_path)
        except FileNotFoundError:
            pass
        except OSError as exc:
            raise RuntimeError(
                f"plan socket path {self.sock_path} is obstructed "
                f"({exc}); refusing to serve") from exc
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        # The bind→chmod window (socket briefly carrying umask-default
        # perms) is closed by PLACEMENT, not umask: callers bind inside
        # a freshly-created 0700 directory (Server.open does), which no
        # other uid can traverse. A process-wide umask flip here would
        # race concurrent threads writing data files.
        s.bind(self.sock_path)
        os.chmod(self.sock_path, 0o600)
        s.listen(128)
        self._sock = s
        t = threading.Thread(target=self._accept_loop, daemon=True)
        t.start()
        return self

    def _accept_loop(self):
        while not self._closing.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn):
        try:
            while not self._closing.is_set():
                req = read_frame(conn)
                if req is None:
                    return
                try:
                    method, path, qp, body, headers = req
                except (TypeError, ValueError):
                    raise FrameError(
                        f"request frame is not a 5-tuple: {type(req)}"
                    ) from None
                try:
                    resp = self.dispatch(method, path, qp, body, headers)
                except Exception as e:  # noqa: BLE001 — mirror handler 500s
                    import json as _json

                    resp = (500, "application/json",
                            _json.dumps({"error": str(e)}).encode())
                write_frame(conn, resp)
        except (OSError, EOFError, FrameError):
            pass
        finally:
            conn.close()

    def close(self):
        self._closing.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        try:
            os.unlink(self.sock_path)
        except FileNotFoundError:
            pass


class WorkerPool:
    """Spawns and supervises the worker frontend processes."""

    def __init__(self, n, bind, sock_path, tls_cert=None, tls_key=None,
                 data_dir=None, exec_reads=False, trace_enabled=False,
                 max_body_size=None, qos_active=False,
                 cluster_epochs=False, plan_cache_entries=None):
        self.n = n
        self.bind = bind
        self.sock_path = sock_path
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        self.data_dir = data_dir
        self.exec_reads = exec_reads
        self.trace_enabled = trace_enabled
        self.max_body_size = max_body_size
        self.qos_active = qos_active
        # Multi-node master: worker response caches must also validate
        # the published CLUSTER epoch version (word 1; 0 = cold).
        self.cluster_epochs = cluster_epochs
        # Master's resolved slice-plan cache capacity (plancache.py):
        # forwarded via env so worker exec processes honor a
        # TOML-configured value (incl. the 0 = off switch), not just
        # an operator-set PILOSA_PLAN_CACHE_ENTRIES.
        self.plan_cache_entries = plan_cache_entries
        self._procs = []

    def open(self):
        args = [sys.executable, "-m", "pilosa_tpu.server.worker",
                "--bind", self.bind, "--socket", self.sock_path,
                "--parent-pid", str(os.getpid())]
        if self.max_body_size is not None:
            # The 413 early-reject happens at the HTTP tier, which in
            # worker mode is the WORKER's listener — the master's limit
            # must ride along or oversized bodies would be buffered and
            # relayed before the master could refuse them.
            args += ["--max-body-size", str(self.max_body_size)]
        if self.tls_cert:
            args += ["--tls-cert", self.tls_cert]
        if self.tls_key:
            args += ["--tls-key", self.tls_key]
        if self.data_dir:
            # Always passed: the epoch-validated response cache needs
            # the published counter even in relay-only mode.
            args += ["--data-dir", self.data_dir]
        if self.exec_reads and self.data_dir:
            args += ["--exec-reads"]
        if self.cluster_epochs:
            args += ["--cluster-epochs"]
        env = dict(os.environ)
        if self.plan_cache_entries is not None:
            env["PILOSA_PLAN_CACHE_ENTRIES"] = str(
                self.plan_cache_entries)
        # One process per chip, and that is the master: workers are
        # pinned to the host backend whatever the master's environment
        # says, or their executors would contend for the chip it owns.
        env["JAX_PLATFORMS"] = "cpu"
        if self.exec_reads:
            # Read-only replica mode for the worker's storage layer
            # (storage/fragment.py REPLICA): no flock, no repair
            # snapshots, no sidecar writes against the master's files.
            env["PILOSA_TPU_READ_ONLY"] = "1"
        if self.trace_enabled:
            # The MASTER owns the tracer: workers must relay every
            # query (no local exec, no response-cache replay) or the
            # worker-served fraction of traffic would silently vanish
            # from /debug/traces and the slow-query metrics.
            env["PILOSA_TPU_MASTER_TRACING"] = "1"
        if self.qos_active:
            # The MASTER owns the QoS tier (admission gate, deadlines,
            # client-quota buckets): worker-local read execution would
            # run ungated and deadline-free, and a worker cache replay
            # would be quota-free — so with QoS enabled workers relay
            # every request, the same discipline as master tracing.
            env["PILOSA_TPU_MASTER_QOS"] = "1"
        for _ in range(self.n):
            self._procs.append(subprocess.Popen(
                args, env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))
        return self

    def alive(self):
        return sum(1 for p in self._procs if p.poll() is None)

    def close(self):
        for p in self._procs:
            if p.poll() is None:
                p.terminate()
        for p in self._procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        self._procs = []
