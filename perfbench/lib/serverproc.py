"""The HTTP client and the one server child: the benchmark's copy of
``chip_smoke.py``'s ``Client`` and ``ServerProc`` (PR 21, proven on the
chip). Later PRs may change the smoke, not this."""
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time


class HarnessFailure(Exception):
    """Set-up could not be completed; the run prints no result."""


def check(cond, what):
    if not cond:
        raise HarnessFailure(what)


class _NoDelay(http.client.HTTPConnection):
    def connect(self):
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


class Client:
    """Keep-alive HTTP client, one connection per thread."""

    def __init__(self, port, timeout=600):
        self.port = port
        self.timeout = timeout
        self._tls = threading.local()

    def send(self, method, path, body=None):
        """(status, bytes). A transport error closes the connection and
        comes back as status 0 with the error's text."""
        conn = getattr(self._tls, "conn", None)
        if conn is None:
            conn = self._tls.conn = _NoDelay("127.0.0.1", self.port,
                                             timeout=self.timeout)
        if isinstance(body, str):
            body = body.encode()
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            return 0, repr(e).encode()

    def request(self, method, path, body=None):
        """Set-up traffic: any answer but 200 ends the run."""
        status, data = self.send(method, path, body)
        check(status == 200,
              f"{method} {path}: HTTP {status}: {data[:400]!r}")
        return data

    def json(self, method, path, body=None):
        return json.loads(self.request(method, path, body) or b"{}")

    def close(self):
        conn = getattr(self._tls, "conn", None)
        if conn is not None:
            conn.close()
            self._tls.conn = None


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerProc:
    """``python -m pilosa_tpu.cli server``: the one child that owns the
    cell's chips. ``env`` is the configuration's server settings on top
    of the caller's environment."""

    def __init__(self, root, data_dir, out_dir, env):
        self.root = root
        self.data_dir = data_dir
        self.out_dir = out_dir
        self.port = free_port()
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = root + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        self.env.update(env)
        self.proc = None

    def start(self):
        os.makedirs(self.out_dir, exist_ok=True)
        self.stdout_path = os.path.join(self.out_dir, "server.out")
        self.stderr_path = os.path.join(self.out_dir, "server.log")
        with open(self.stdout_path, "wb") as out, \
                open(self.stderr_path, "wb") as err:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "-d", self.data_dir, "-b", f"127.0.0.1:{self.port}"],
                cwd=self.root, env=self.env, stdout=out, stderr=err)
        probe = Client(self.port, timeout=5)
        deadline = time.monotonic() + 300
        while True:
            check(self.proc.poll() is None,
                  f"server exited at boot, rc={self.proc.returncode}; "
                  f"see {self.stderr_path}")
            check(time.monotonic() < deadline, "server boot timed out")
            status, _ = probe.send("GET", "/version")
            probe.close()
            if status == 200:
                return Client(self.port)
            time.sleep(0.25)

    def drain(self):
        """SIGTERM, then wait for exit 0: frees the chip and the data."""
        if self.proc is None:
            return
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=300)
        self.proc = None
        check(rc == 0, f"server exit code {rc} after SIGTERM")

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.proc = None


def device_block(client):
    return client.json("GET", "/debug/vars")["device"]


def compile_calls(client):
    """Total ``compileCalls`` over the cells of /debug/kernels, the
    cells that have any, and the device transfer count."""
    out = client.json("GET", "/debug/kernels")
    cells = {f"{c['op']} {c['cell']} {c['bucket']}": c["compileCalls"]
             for c in out["cells"] if c["compileCalls"]}
    return sum(cells.values()), cells, out["transfers"]["count"]


def counters(client):
    """The counters the per-layer metrics read, in one snapshot."""
    v = client.json("GET", "/debug/vars")
    total, cells, transfers = compile_calls(client)
    return {"compileCalls": total, "compileCells": cells,
            "deviceTransfers": transfers,
            "memoryStats": v["device"]["memoryStats"],
            "pathModel": v.get("pathModel", {})}


def wait_warm_quiet(client, deadline_s=600):
    """Block until the background width warmer has nothing in flight;
    a failed warm compile ends the run."""
    deadline = time.monotonic() + deadline_s
    while True:
        warm = client.json("GET", "/debug/vars")["widthWarmer"]
        check(warm["failed"] == 0, f"width warm failed: {warm}")
        if warm["inflight"] == 0:
            return warm
        check(time.monotonic() < deadline, f"warmer never quiet: {warm}")
        time.sleep(0.5)


class HostMemory(threading.Thread):
    """Samples the machine's MemAvailable five times a second; the
    lowest reading goes into the run's notes (PR 21 ran a 40 GiB host
    out of memory from the oracle's side)."""

    def __init__(self):
        super().__init__(daemon=True)
        self.low_mb = self.read()
        self.start()

    @staticmethod
    def read():
        with open("/proc/meminfo") as f:
            return next(int(ln.split()[1]) for ln in f
                        if ln.startswith("MemAvailable")) // 1024

    def run(self):
        while True:
            self.low_mb = min(self.low_mb, self.read())
            time.sleep(0.2)
