"""Bring-up surfaces (ISSUE 21): chip_smoke.py's rehearsal and refusal
paths, the compile-cache helper, the device block, the visible
``batched:error`` hop, the strict native build and one process per
chip. The chip side of all of this is ``chip_smoke.py`` itself, run
through the chip tool."""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH, native
from pilosa_tpu.server.server import Server
from pilosa_tpu.utils import compilecache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402 — NumPy + stdlib only, no JAX of its own


def _run(args, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _phases(stdout):
    return {d["phase"]: d for d in map(json.loads, stdout.splitlines())
            if "phase" in d}


# ------------------------------------------------------- chip_smoke.py


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One rehearsal run shared by the tests that read its output, with
    the compile cache placed from outside."""
    cache = str(tmp_path_factory.mktemp("jaxcache"))
    r = _run([SMOKE, "--rehearse"], {"JAX_COMPILATION_CACHE_DIR": cache,
                                     "JAX_ENABLE_COMPILATION_CACHE": "true"})
    return r, cache


def test_rehearsal_exits_zero_and_reports_cpu(rehearsal):
    r, _ = rehearsal
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": last["device"]["count"]}}


def test_rehearsal_drives_every_phase_and_names_the_tiers(rehearsal):
    r, _ = rehearsal
    ph = _phases(r.stdout)
    assert set(ph) >= {"device", "restore", "ingest", "stage", "compile",
                       "served", "coalescer", "planner", "memory",
                       "restart", "lanes", "kernels", "total"}
    assert ph["device"]["nativeLoaded"] is True
    assert ph["restore"]["reduced"]          # a rehearsal is a cut, said so
    assert ph["compile"]["repeatPassCompiles"] == 0
    served = ph["served"]
    for shape in ("count_intersect", "count_nested", "topn_src",
                  "bsi_range_between", "time_range", "count_run_run",
                  "count_widened_window", "count_concurrent"):
        assert served[shape]["servedBy"], shape
        assert not any(h.endswith(":error")
                       for h in served[shape]["fallbackChain"])
    assert "batched" in served["count_intersect"]["servedBy"]
    assert "repeatMs" in served["count_intersect"]
    # After the restart the sparse frames are evicted: the compressed
    # container tier serves them, and says why the fused tier did not.
    assert served["evicted_array_array"]["containerBlocks"]["Array"] > 0
    assert served["evicted_run_run"]["containerBlocks"]["Run"] > 0
    assert served["evicted_run_run"]["fallbackChain"] == [
        "batched:compressed"]
    assert ph["lanes"]["queries"] > 0
    assert ph["kernels"]["pallasInterpreted"] is True  # no Mosaic on CPU


def test_rehearsal_uses_the_cache_directory_it_was_given(rehearsal):
    r, cache = rehearsal
    ph = _phases(r.stdout)
    assert ph["device"]["compileCacheDir"] == cache
    assert ph["device"]["cacheEntriesBefore"] == 0
    assert ph["total"]["cacheEntriesAfter"] > 0
    assert ph["total"]["cacheEntriesAfter"] == chip_smoke.cache_entries(
        cache)


def test_without_flag_on_cpu_fails_before_loading_anything():
    r = _run([SMOKE])
    assert r.returncode != 0
    assert r.stdout == ""            # no phase, no result line
    assert "not 'tpu'" in r.stderr


def test_sizes_below_the_floor_need_the_rehearsal_flag():
    r = _run([SMOKE, "--slices", "8", "--rows", "16"])
    assert r.returncode == 2 and "floor" in r.stderr
    assert r.stdout == ""


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0 and r.stdout == ""


def test_backup_tar_is_what_the_server_restores(tmp_path):
    """The smoke's client-side archive (written without the repo's
    codec) loads through Fragment.read_from bit for bit."""
    import io

    from pilosa_tpu.storage.fragment import Fragment

    words = chip_smoke.gen_slice(seed=5, n_rows=3, s=2)
    tar = chip_smoke.backup_tar([0, 4, 9], words)
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 2).open()
    f.read_from(io.BytesIO(tar))
    for rid, w in zip((0, 4, 9), words):
        assert np.array_equal(f.row_words(rid), w)
        assert f.row_count(rid) == chip_smoke.popcount(w)
    assert sorted(f.cache.ids()) == [0, 4, 9]
    f.close()


def test_oracle_densities_follow_the_deployment():
    """12.5-50 % dense rows, spread over behavior/device/geo."""
    words = chip_smoke.gen_slice(seed=1, n_rows=9, s=0)
    for r in range(9):
        frame, rid, depth = chip_smoke.row_home(r)
        assert frame == chip_smoke.FRAMES[r % 3] and rid == r // 3
        density = chip_smoke.popcount(words[r]) / SLICE_WIDTH
        assert abs(density - 0.5 ** depth) < 0.01


# ------------------------------------------------ compile-cache helper


def _enable_in_fresh_process(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, jax\n"
         "from pilosa_tpu.utils import compilecache\n"
         "d = compilecache.enable()\n"
         "print(json.dumps([d, jax.config.jax_compilation_cache_dir,"
         " jax.config.jax_persistent_cache_min_compile_time_secs]))"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout)


def test_cache_helper_leaves_an_outside_directory_alone(tmp_path):
    want = str(tmp_path / "placed-from-outside")
    got, cfg, min_secs = _enable_in_fresh_process(
        {"JAX_COMPILATION_CACHE_DIR": want})
    assert got == cfg == want
    assert min_secs == 0


def test_cache_helper_defaults_to_the_fixed_checkout_path():
    got, cfg, _ = _enable_in_fresh_process({})
    assert got == cfg == os.path.join(ROOT, ".jax_cache")
    # Fixed means fixed: the path is part of the cache key.
    assert compilecache.DEFAULT_DIR == got
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------- the server says what it is


def _get(host, path):
    with urllib.request.urlopen(f"http://{host}{path}", timeout=30) as r:
        return json.loads(r.read())


def _post(host, path, body):
    req = urllib.request.Request(f"http://{host}{path}",
                                 data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read() or b"{}")


@pytest.fixture
def server(tmp_path):
    s = Server(str(tmp_path / "data"), bind="localhost:0").open()
    yield s
    s.close()


def test_device_block_is_served(server):
    import jax

    dev = _get(server.host, "/debug/vars")["device"]
    assert dev["platform"] == "cpu" == jax.devices()[0].platform
    assert dev["deviceKind"] == jax.devices()[0].device_kind
    assert dev["deviceCount"] == len(jax.devices()) == len(
        dev["memoryStats"])
    assert dev["nativeLoaded"] is native.available()
    assert dev["compileCacheDir"] == jax.config.jax_compilation_cache_dir


def test_device_block_is_logged_at_boot(tmp_path, caplog):
    import logging

    with caplog.at_level(logging.INFO, logger="pilosa_tpu.server"):
        Server(str(tmp_path / "data"), bind="localhost:0").open().close()
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("device: "))
    for key in ("platform", "deviceKind", "deviceCount", "memoryStats",
                "nativeLoaded"):
        assert key in line


def test_width_warmer_block_is_always_served(server):
    warm = _get(server.host, "/debug/vars")["widthWarmer"]
    assert warm == {"compiled": 0, "failed": 0, "inflight": 0}


def test_forced_batch_failure_is_a_visible_error_hop(server, monkeypatch):
    """_try_batch keeps its failover-safety role (the query is served
    per slice, exactly), but never quietly: the hop is in the profile,
    which chip_smoke.py treats as fatal."""
    host = server.host
    _post(host, "/index/i", "{}")
    _post(host, "/index/i/frame/f", "{}")
    _post(host, "/index/i/query",
          "".join(f'SetBit(frame="f", rowID={r}, columnID={c})'
                  for r in (1, 2)
                  for c in (3, SLICE_WIDTH + 5, 2 * SLICE_WIDTH + 7)))
    ex = server.executor
    ex._force_path = "batched"
    pql = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
           'Bitmap(frame="f", rowID=2)))')

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(ex, "_plan_and_stacks", boom)
    out = _post(host, "/index/i/query?profile=true", pql)
    assert out["results"] == [3]
    res = out["profile"]["resources"]
    assert "batched:error" in res["fallbackChain"]
    assert res["servedBy"] == {"serial": 1}

    monkeypatch.undo()
    ex._force_path = "batched"
    res = _post(host, "/index/i/query?profile=true",
                pql.replace("Intersect", "Union"))["profile"]["resources"]
    assert res["fallbackChain"] == [] and res["servedBy"] == {"batched": 1}


# ------------------------------------------------------ strict native build


def test_native_build_failure_raises(tmp_path, monkeypatch):
    bad = tmp_path / "broken.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad))
    with pytest.raises(RuntimeError, match="native build failed"):
        native.build(str(tmp_path / "out.so"))
    assert not (tmp_path / "out.so").exists()


def test_native_build_without_a_compiler_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("PATH", str(tmp_path))
    with pytest.raises(RuntimeError, match="native build impossible"):
        native.build(str(tmp_path / "out.so"))


# ---------------------------------------------------- one process per chip


def test_worker_children_are_pinned_to_the_host_backend(monkeypatch):
    from pilosa_tpu.server import workers

    seen = []

    class FakeProc:
        def poll(self):
            return 0

    def fake_popen(args, env=None, **kw):
        seen.append(env)
        return FakeProc()

    monkeypatch.setattr(workers.subprocess, "Popen", fake_popen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # the master's setting
    workers.WorkerPool(2, "localhost:0", "/tmp/x.sock").open()
    assert len(seen) == 2
    assert all(e["JAX_PLATFORMS"] == "cpu" for e in seen)
    assert not any("PILOSA_TPU_PLATFORM" in e for e in seen)
