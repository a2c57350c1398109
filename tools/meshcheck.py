"""Collective data plane smoke: the mesh peer group on an 8-device
CPU-emulated pod, wired into ``make test`` as ``make meshcheck``.

Phase 1 (collective vs HTTP): an in-process 2-node cluster with
``[mesh] enabled`` serves Count/TopN/Sum over HTTP — every answer must
be bit-exact against the SAME cluster with the plane detached (pure
HTTP fan-out), with nonzero collective launches on /debug/mesh and
live ``pilosa_mesh_*`` series on /metrics.

Phase 2 (live resize): a background query loop runs while a third
node joins via POST /cluster/resize. Hard pass/fail:

- ZERO failed ops for the whole soak (every response 200, every
  count the expected value),
- the plane declined with ``reason=transition`` while the stream was
  in flight (queries fell back to HTTP mid-resize),
- the collective path RESUMED after commit — launches strictly
  increase once the placement settles.

Small and CPU-only by design: a few slices, a few hundred queries.
"""
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The 8-device virtual pod must be configured BEFORE jax initializes.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


N_SLICES = 6
FAILURES = []


def check(ok, msg):
    tag = "PASS" if ok else "FAIL"
    print(f"[meshcheck] {tag}: {msg}")
    if not ok:
        FAILURES.append(msg)


def req(host, method, path, body=None, timeout=30):
    r = urllib.request.Request(
        f"http://{host}{path}",
        data=body.encode() if isinstance(body, str) else body,
        method=method)
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.read()


def query(host, q):
    return json.loads(req(host, "POST", "/index/i/query", q))["results"]


def boot(tmp, hosts, i, cluster_hosts):
    from pilosa_tpu.server.server import Server

    return Server(os.path.join(tmp, f"n{i}"), bind=hosts[i],
                  cluster_hosts=cluster_hosts,
                  anti_entropy_interval=0, polling_interval=0,
                  mesh={"enabled": True}).open()


def seed(host):
    import numpy as np

    from pilosa_tpu import SLICE_WIDTH

    req(host, "POST", "/index/i", "{}")
    req(host, "POST", "/index/i/frame/f", "{}")
    req(host, "POST", "/index/i/frame/g",
        json.dumps({"options": {"rangeEnabled": True, "fields": [
            {"name": "v", "type": "int", "min": 0, "max": 100}]}}))
    rng = np.random.default_rng(11)
    shared = rng.choice(2000, 200, replace=False)
    for s in range(N_SLICES):
        base = s * SLICE_WIDTH
        for r, take in ((1, 60), (2, 50), (3, 30)):
            cols = np.unique(np.concatenate(
                [shared[:take // 2],
                 rng.choice(5000, take, replace=False)])) + base
            body = "\n".join(
                f'SetBit(frame="f", rowID={r}, columnID={c})'
                for c in cols.tolist())
            req(host, "POST", "/index/i/query", body)
        for c in rng.choice(3000, 20, replace=False).tolist():
            req(host, "POST", "/index/i/query",
                f'SetFieldValue(frame="g", columnID={base + c}, '
                f'v={int(rng.integers(0, 101))})')


QUERIES = [
    'Count(Intersect(Bitmap(frame="f", rowID=1), '
    'Bitmap(frame="f", rowID=2)))',
    'Count(Union(Bitmap(frame="f", rowID=1), '
    'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3)))',
    'Count(Difference(Bitmap(frame="f", rowID=1), '
    'Bitmap(frame="f", rowID=3)))',
    'Count(Xor(Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3)))',
    'TopN(frame="f", n=3)',
    'TopN(Bitmap(frame="f", rowID=1), frame="f", n=2)',
    'Sum(frame="g", field="v")',
]


def mesh_snap(host):
    return json.loads(req(host, "GET", "/debug/mesh"))


def phase_collective_vs_http(servers, hosts):
    import jax

    check(len(jax.devices()) == 8,
          f"8-device CPU mesh boots (got {len(jax.devices())})")
    h = hosts[0]
    # Replay tiers off on the coordinator so every query genuinely
    # exercises the routing decision under test.
    servers[0].executor._result_memo_off = True
    servers[0].handler._resp_cache = None

    before = mesh_snap(h)["launches"]
    mesh_answers = [query(h, q) for q in QUERIES]
    after = mesh_snap(h)
    launches = after["launches"]
    check(launches["count"] > before["count"],
          f"collective Count launches recorded ({launches})")
    check(launches["topn"] > before["topn"]
          and launches["sum"] > before["sum"],
          "collective TopN/Sum launches recorded")
    check(len(after["members"]) == 2,
          f"peer group covers both nodes ({sorted(after['members'])})")
    metrics = req(h, "GET", "/metrics").decode()
    check("pilosa_mesh_collective_launches_total" in metrics
          and "pilosa_mesh_fallback_total" in metrics,
          "pilosa_mesh_* series live on /metrics")

    planes = [s.executor.meshplane for s in servers]
    try:
        for s in servers:
            s.executor.meshplane = None
        http_answers = [query(h, q) for q in QUERIES]
    finally:
        for s, p in zip(servers, planes):
            s.executor.meshplane = p
    check(mesh_answers == http_answers,
          "collective answers bit-exact vs the HTTP fan-out path")
    return mesh_answers


def phase_live_resize(servers, hosts, tmp, expected):
    h = hosts[0]
    count_q = QUERIES[0]
    want = expected[0]
    stop = threading.Event()
    failures = []
    served = [0]

    def loop():
        while not stop.is_set():
            try:
                out = query(h, count_q)
                if out != want:
                    failures.append(f"wrong answer {out} != {want}")
            except Exception as exc:  # noqa: BLE001 — the soak records it
                failures.append(repr(exc))
            served[0] += 1

    threads = [threading.Thread(target=loop) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.3)

    servers.append(boot(tmp, hosts, 2, hosts))
    fallbacks0 = mesh_snap(h)["fallbacks"]["transition"]
    body = req(h, "POST", "/cluster/resize",
               json.dumps({"hosts": hosts}))
    gen = json.loads(body)["generation"]
    deadline = time.monotonic() + 60
    snap = None
    while time.monotonic() < deadline:
        snap = json.loads(req(h, "GET", "/debug/rebalance"))
        if (not snap["running"]
                and snap["placement"]["phase"] == "stable"
                and snap["placement"]["generation"] == gen):
            break
        time.sleep(0.05)
    check(snap is not None and snap["placement"]["generation"] == gen
          and snap.get("lastError") is None,
          f"resize committed generation {gen}")

    at_commit = mesh_snap(h)["launches"]["count"]
    time.sleep(0.5)  # a few more queries post-commit
    stop.set()
    for t in threads:
        t.join(timeout=30)
    check(not failures,
          f"zero failed ops across {served[0]} queries during the "
          f"live resize (failures: {failures[:3]})")
    snap = mesh_snap(h)
    check(snap["fallbacks"]["transition"] > fallbacks0,
          "queries fell back to HTTP during TRANSITION "
          f"({snap['fallbacks']})")
    check(snap["launches"]["count"] > at_commit,
          "collective path resumed after commit "
          f"({snap['launches']['count']} > {at_commit})")
    check(query(h, count_q) == want,
          "post-resize counts bit-exact")


def main():
    import shutil
    import tempfile

    from pilosa_tpu.testing import free_ports

    tmp = tempfile.mkdtemp(prefix="meshcheck-")
    hosts = [f"127.0.0.1:{p}" for p in free_ports(3)]
    servers = [boot(tmp, hosts, 0, hosts[:2]),
               boot(tmp, hosts, 1, hosts[:2])]
    try:
        seed(hosts[0])
        answers = phase_collective_vs_http(servers, hosts)
        phase_live_resize(servers, hosts, tmp, answers)
    finally:
        for s in servers:
            s.close()
        shutil.rmtree(tmp, ignore_errors=True)

    if FAILURES:
        print(f"[meshcheck] {len(FAILURES)} failure(s)")
        return 1
    print("[meshcheck] all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
