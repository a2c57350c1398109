"""Crash soak: acknowledged writes survive a hard kill under
concurrent mixed load.

Spawns the real CLI server as a subprocess, drives concurrent
read/write HTTP traffic (SetBit + SetFieldValue + Count), SIGKILLs the
process mid-serving, restarts it on the same data dir, and asserts
every ACKNOWLEDGED write is present — the durability contract the
op-log flush provides across process death (fsync'd bulk paths cover
machine crashes; a flushed single-op record survives SIGKILL because
the page cache outlives the process). The reference's equivalent
guarantee rides the same roaring op-log design (roaring.go:740)."""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu.testing import free_ports  # noqa: E402


def _post(port, path, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body.encode(),
        method="POST")
    return json.loads(
        urllib.request.urlopen(req, timeout=timeout).read() or b"{}")


def _spawn(data_dir, port, workers=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    args = [sys.executable, "-m", "pilosa_tpu.cli", "server", "-d",
            data_dir, "--bind", f"127.0.0.1:{port}"]
    if workers:
        args += ["--workers", str(workers)]
    proc = subprocess.Popen(
        args, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status", timeout=5).read()
            return proc
        except Exception:  # noqa: BLE001 — still booting
            if proc.poll() is not None:
                raise AssertionError("server died during boot")
            time.sleep(0.5)
    proc.kill()
    raise AssertionError("server did not come up")


@pytest.mark.parametrize("workers", [0, 2])
def test_acked_writes_survive_sigkill(tmp_path, workers):
    """workers=2 additionally proves the multi-process serving stack
    under SIGKILL: writes relayed through worker frontends carry the
    same op-log durability, orphaned workers exit via the parent
    watchdog, and the restart (fresh REUSEPORT group) serves the
    recovered state."""
    port = free_ports(1)[0]
    d = str(tmp_path / "data")
    proc = _spawn(d, port, workers=workers)
    try:
        _post(port, "/index/i", "{}")
        _post(port, "/index/i/frame/f", "{}")
        _post(port, "/index/i/frame/g",
              json.dumps({"options": {"rangeEnabled": True, "fields": [
                  {"name": "v", "type": "int", "min": 0,
                   "max": 100000}]}}))

        acked_bits = []     # (row, col) acknowledged before the kill
        acked_vals = {}     # col -> value
        stop = threading.Event()
        killing = threading.Event()  # set just before SIGKILL
        errs = []

        def writer(tid):
            k = 0
            while not stop.is_set():
                k += 1
                col = tid * 1_000_000 + k
                try:
                    if k % 5 == 0:
                        _post(port, "/index/i/query",
                              f'SetFieldValue(frame="g", columnID={col},'
                              f' v={k % 997})')
                        acked_vals[col] = k % 997
                    else:
                        _post(port, "/index/i/query",
                              f'SetBit(frame="f", rowID={tid},'
                              f' columnID={col})')
                        acked_bits.append((tid, col))
                except Exception as exc:  # noqa: BLE001
                    # Requests in flight when the server dies fail
                    # with resets/short reads — casualties, not bugs;
                    # they were never acknowledged so nothing was
                    # recorded for them.
                    if not killing.is_set() and not stop.is_set():
                        errs.append(repr(exc))
                    return

        def reader():
            while not stop.is_set():
                try:
                    _post(port, "/index/i/query",
                          'Count(Bitmap(frame="f", rowID=1))')
                except Exception:  # noqa: BLE001 — races the kill
                    return

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in (1, 2, 3)] + [
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        time.sleep(4.0)
        # Hard kill MID-LOAD — in-flight (unacknowledged) requests may
        # vanish; everything already acknowledged must not.
        killing.set()
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        stop.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "worker thread failed to stop"
        assert not errs, errs

        # Snapshot the acked sets AFTER all writers stopped.
        bits = list(acked_bits)
        vals = dict(acked_vals)
        assert len(bits) > 50, "load too small to mean anything"

        proc = _spawn(d, port, workers=workers)
        # Every acked bit present (count per row == acked per row, and
        # spot-check membership end-to-end).
        for row in (1, 2, 3):
            want = sum(1 for r, _ in bits if r == row)
            got = _post(port, "/index/i/query",
                        f'Count(Bitmap(frame="f", rowID={row}))')
            assert got["results"][0] >= want, (row, want, got)
        # Bit-exact membership for a sample, against each row's full
        # bitmap (fetched once per row).
        row_cols = {}
        for row in (1, 2, 3):
            bm = _post(port, "/index/i/query",
                       f'Bitmap(frame="f", rowID={row})')
            res = bm["results"][0]
            row_cols[row] = set(res.get("bits", res.get("columns", [])))
        for row, col in bits[:: max(1, len(bits) // 20)]:
            assert col in row_cols[row], (row, col)
        if vals:
            total = sum(vals.values())
            got = _post(port, "/index/i/query", 'Sum(frame="g", field="v")')
            # Exact lower bound: unacked in-flight writes can only
            # INCREASE the sum, so any shortfall is a lost acked write.
            assert got["results"][0]["sum"] >= total, (got, total)
            assert got["results"][0]["count"] >= len(vals)
        if workers:
            # Deterministic watchdog check: after the master dies, NO
            # process (worker orphan included) may keep the port's
            # REUSEPORT group alive — a lingering orphan would fail
            # only as an occasional 503 otherwise.
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                try:
                    c = socket.create_connection(("127.0.0.1", port),
                                                 timeout=1)
                    c.close()
                    time.sleep(0.5)
                except OSError:
                    break
            else:
                raise AssertionError(
                    "port still accepting after master death — "
                    "orphan worker in the REUSEPORT group")
    finally:
        if proc.poll() is None:
            proc.kill()


def _worker_pids(master_pid):
    """Child processes of the master running the worker module."""
    out = subprocess.run(
        ["pgrep", "-P", str(master_pid), "-f", "pilosa_tpu.server.worker"],
        capture_output=True, text=True)
    return [int(p) for p in out.stdout.split()]


def test_worker_sigkill_mid_request_reroutes(tmp_path):
    """VERDICT r4 #8: SIGKILL one WORKER while requests are in flight.
    The kernel drops the dead listener from the SO_REUSEPORT group, so
    new connections land on survivors; in-flight requests on the dead
    worker's connections are unacknowledged casualties. Contract:
    (a) zero FAILED ACKNOWLEDGED writes — everything that returned 200
    is present afterwards (no restart: the master owns the data and
    never died); (b) serving continues — every post-kill retry
    succeeds."""
    port = free_ports(1)[0]
    d = str(tmp_path / "data")
    proc = _spawn(d, port, workers=2)
    try:
        _post(port, "/index/i", "{}")
        _post(port, "/index/i/frame/f", "{}")

        deadline = time.monotonic() + 60
        while len(_worker_pids(proc.pid)) < 2:
            assert time.monotonic() < deadline, "workers never spawned"
            time.sleep(0.2)

        acked = []          # (row, col) acknowledged with HTTP 200
        stop = threading.Event()
        errs = []

        def writer(tid):
            k = 0
            while not stop.is_set():
                k += 1
                col = tid * 1_000_000 + k
                try:
                    _post(port, "/index/i/query",
                          f'SetBit(frame="f", rowID={tid},'
                          f' columnID={col})', timeout=30)
                except Exception:  # noqa: BLE001 — in-flight casualty
                    # The request may have died on the killed worker's
                    # connection — unacknowledged, so nothing recorded.
                    # RETRY on a fresh connection: it must land on a
                    # surviving group member and succeed; a second
                    # failure means serving did NOT re-route.
                    try:
                        _post(port, "/index/i/query",
                              f'SetBit(frame="f", rowID={tid},'
                              f' columnID={col})', timeout=30)
                    except Exception as exc2:  # noqa: BLE001
                        if not stop.is_set():
                            errs.append(repr(exc2))
                        return
                acked.append((tid, col))

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in (1, 2, 3)]
        for t in threads:
            t.start()
        time.sleep(2.0)

        victim = _worker_pids(proc.pid)[0]
        os.kill(victim, signal.SIGKILL)
        # Keep the load running THROUGH the kill.
        time.sleep(3.0)
        stop.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        assert not errs, errs
        bits = list(acked)
        assert len(bits) > 50, "load too small to mean anything"

        # The victim is gone; the survivor + master still serve.
        deadline = time.monotonic() + 10
        while victim in _worker_pids(proc.pid):
            assert time.monotonic() < deadline, "victim survived SIGKILL"
            time.sleep(0.1)
        # (a) zero failed acked writes — every 200'd bit is present.
        for row in (1, 2, 3):
            want = sum(1 for r, _ in bits if r == row)
            got = _post(port, "/index/i/query",
                        f'Count(Bitmap(frame="f", rowID={row}))')
            assert got["results"][0] >= want, (row, want, got)
        sample = bits[:: max(1, len(bits) // 20)]
        row_cols = {}
        for row in (1, 2, 3):
            bm = _post(port, "/index/i/query",
                       f'Bitmap(frame="f", rowID={row})')
            res = bm["results"][0]
            row_cols[row] = set(res.get("bits", res.get("columns", [])))
        for row, col in sample:
            assert col in row_cols[row], (row, col)
        # (b) serving continues: a burst of fresh connections all lands
        # on live members of the group.
        for i in range(20):
            out = _post(port, "/index/i/query",
                        'Count(Bitmap(frame="f", rowID=1))' + " " * i)
            assert out["results"][0] >= 1
    finally:
        if proc.poll() is None:
            proc.kill()
