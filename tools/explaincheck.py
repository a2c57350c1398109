"""Query-inspector smoke (PR 15), wired into ``make test`` as
``make explaincheck``.

Phase 1 (single node): boot a server with the observatory + cost
model on, drive the batched-dense, serial-compressed, memo, and
coalesced-lane tiers, and assert:

- ``?explain=true`` reports the correct tier + decline-reason chain
  for each path (batched served; serial with ``batched:compressed``;
  a coalesced member carrying ``coalesced_lane``);
- ``?explain=only`` plans without executing (results null, plan-only
  mode, and the plan cache is byte-identical before/after);
- ``?profile=true&explain=true`` compose — one response, both blocks;
- ``GET /debug`` catalogs every ``/debug/*`` route;
- ``GET /debug/costmodel`` shows nonzero calibration samples with
  median |predicted/actual| error ≤ 2× on the warm engine paths;
- the full ``/metrics`` exposition (``pilosa_cost_model_*`` included)
  passes promlint.

Phase 2 (two nodes, in-process pod): the mesh-served and mesh-declined
→ HTTP tiers — ``servedBy: mesh`` with a leading mesh-served chain
hop, then (after node b's plane unregisters) ``servedBy: http`` with a
``mesh:not_resident`` fallback hop, bit-exact across both.

What the inspector's serving-path machinery costs a request with
explain off is not measured here: a timing from this sandbox's CPU
backend is not a speed (see PERF.md).
"""
import json
import os
import sys
import tempfile
import threading
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The 2-node pod shares one JAX runtime; a few virtual devices make
# the mesh shard_map path realistic (set BEFORE jax initializes).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()


from pilosa_tpu import SLICE_WIDTH  # noqa: E402

ATTEMPTS = 3
ERROR_FACTOR_BAR = 2.0

FAILURES = []


def check(ok, msg):
    tag = "PASS" if ok else "FAIL"
    print(f"[explaincheck] {tag}: {msg}")
    if not ok:
        FAILURES.append(msg)


def req(base, method, path, body=None, timeout=30):
    r = urllib.request.Request(
        f"{base}{path}",
        data=body.encode() if isinstance(body, str) else body,
        method=method)
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return resp.read()


def post(base, path, body):
    return req(base, "POST", path, body)


def get(base, path):
    return json.loads(req(base, "GET", path))


def seed_single(base, holder):
    import numpy as np

    post(base, "/index/i", "{}")
    post(base, "/index/i/frame/d", "{}")
    post(base, "/index/i/frame/c", "{}")
    rng = np.random.default_rng(17)
    idx = holder.index("i")
    for s in range(3):
        b = s * SLICE_WIDTH
        for rid in (1, 2, 3):
            cols = rng.choice(60_000, size=4000, replace=False) + b
            idx.frame("d").import_bits([rid] * len(cols),
                                       cols.tolist())
            sp = rng.choice(SLICE_WIDTH, size=400, replace=False) + b
            idx.frame("c").import_bits([rid] * len(sp), sp.tolist())
    for v in idx.frame("c").views.values():
        for frag in list(v.fragments.values()):
            frag.snapshot()
            frag.unload()


Q_DENSE = ('Count(Intersect(Bitmap(frame="d", rowID=1), '
           'Bitmap(frame="d", rowID=2)))')
Q_COMP = ('Count(Union(Bitmap(frame="c", rowID=1), '
          'Bitmap(frame="c", rowID=2)))')


def phase_single_node():
    from pilosa_tpu.server.server import Server
    from tools.promlint import lint_text

    with tempfile.TemporaryDirectory(prefix="explaincheck-") as tmp:
        server = Server(os.path.join(tmp, "d"), bind="127.0.0.1:0",
                        observe={"kernel-sample-rate": 4}).open()
        try:
            base = f"http://{server.host}"
            seed_single(base, server.holder)
            # Replay tiers off so every driven query genuinely takes
            # the routing decision under test.
            server.executor._result_memo_off = True
            server.handler._resp_cache = None

            # --- batched dense tier
            out = json.loads(post(base,
                                  "/index/i/query?explain=true",
                                  Q_DENSE))
            exp = out.get("explain") or {}
            check(exp.get("servedBy") == "batched",
                  f"dense Count servedBy=batched "
                  f"(got {exp.get('servedBy')})")
            chain = {t["tier"]: t for t in exp["calls"][0]["tiers"]}
            check(chain.get("batched", {}).get("decision") == "served",
                  "dense chain: batched served")
            plain = json.loads(post(base, "/index/i/query", Q_DENSE))
            check(plain["results"] == out["results"],
                  "bit-exact with explain on vs off (dense)")

            # --- serial compressed tier
            out = json.loads(post(base,
                                  "/index/i/query?explain=true",
                                  Q_COMP))
            exp = out["explain"]
            check(exp["servedBy"] == "serial",
                  f"compressed Count servedBy=serial "
                  f"(got {exp['servedBy']})")
            check("batched:compressed" in exp["fallbackChain"],
                  f"compressed decline reason in chain "
                  f"({exp['fallbackChain']})")
            plain = json.loads(post(base, "/index/i/query", Q_COMP))
            check(plain["results"] == out["results"],
                  "bit-exact with explain on vs off (compressed)")

            # --- explain-only: plans, never executes, never mutates
            plans0 = get(base, "/debug/plans")
            only = json.loads(post(base,
                                   "/index/i/query?explain=only",
                                   Q_DENSE))
            plans1 = get(base, "/debug/plans")
            check(only["results"] is None
                  and only["explain"]["mode"] == "plan-only",
                  "explain-only plans without executing")
            check(plans0["entries"] == plans1["entries"]
                  and plans0["entriesByKind"]
                  == plans1["entriesByKind"],
                  "explain-only left the plan cache untouched")

            # --- profile + explain compose
            both = json.loads(post(
                base, "/index/i/query?profile=true&explain=true",
                Q_DENSE))
            check("profile" in both and "explain" in both,
                  "?profile=true and ?explain=true compose")
            check(both["profile"]["resources"].get("servedBy"),
                  "profile resources carry the tier tags")

            # --- coalesced lane tier (concurrent compressed load).
            # Connections are pre-opened so the 4 arrivals land
            # within the accumulation window instead of spreading
            # over TCP connect jitter.
            import http.client

            server.executor._co_enabled_memo = True
            server.executor._co_route_all = True
            server.executor.set_coalesce_config(max_wait_us=50000,
                                                max_group=8)
            # The earlier LONE compressed drives taught the path
            # model "structurally ineligible" (a solo tick member
            # serves singly through the batched decline) — pin the
            # batched arm so the concurrent drive reaches the tick
            # instead of the model's serial shortcut.
            server.executor._force_path = "batched"
            lane_seen = False
            for _attempt in range(6):
                tiers = []
                conns = []
                for _ in range(4):
                    c = http.client.HTTPConnection(server.host,
                                                   timeout=30)
                    c.request("GET", "/version")
                    c.getresponse().read()
                    conns.append(c)
                barrier = threading.Barrier(4)

                def drive(conn):
                    barrier.wait()
                    conn.request(
                        "POST", "/index/i/query?explain=true",
                        body=Q_COMP.encode())
                    doc = json.loads(conn.getresponse().read())
                    tiers.append(doc["explain"].get("servedBy"))
                    conn.close()

                threads = [threading.Thread(target=drive, args=(c,))
                           for c in conns]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if any(t == "coalesced_lane" for t in tiers):
                    lane_seen = True
                    break
            check(lane_seen,
                  "coalesced_lane attribution under concurrent "
                  "compressed load")
            server.executor._force_path = None
            server.executor._co_route_all = False
            server.executor._co_enabled_memo = False

            # --- memo tier
            server.executor._result_memo_off = False
            post(base, "/index/i/query", Q_DENSE)
            doc = json.loads(post(base,
                                  "/index/i/query?explain=true",
                                  Q_DENSE))
            check(doc["explain"]["servedBy"] == "memo",
                  "memo-replayed query attributes servedBy=memo")
            server.executor._result_memo_off = True

            # --- /debug catalog
            cat = get(base, "/debug")
            routes = {e["path"] for e in cat["endpoints"]}
            expected = set()
            for _m, pattern, _fn in server.handler.routes:
                p = pattern.strip("^$")
                if p.startswith("/debug") and p != "/debug":
                    expected.add(p)
            check(routes == expected,
                  f"/debug catalog complete "
                  f"({len(routes)}/{len(expected)} routes)")

            # --- cost-model calibration on the warm engine paths.
            # The median ring is recency-weighted, so when an attempt
            # misses the bar (noisy shared core), more warm driving
            # lets the learned overheads converge and retries.
            cm = None
            for attempt in range(ATTEMPTS):
                for _ in range(40):
                    post(base, "/index/i/query?profile=true", Q_DENSE)
                    post(base, "/index/i/query?profile=true", Q_COMP)
                cm = get(base, "/debug/costmodel")
                bad = [
                    t for t in ("batched", "serial")
                    if cm["tiers"].get(t, {}).get("samples")
                    and (cm["tiers"][t]["medianErrorFactor"] is None
                         or cm["tiers"][t]["medianErrorFactor"]
                         > ERROR_FACTOR_BAR)]
                if not bad:
                    break
            check(cm["enabled"] and cm["samples"] > 40,
                  f"cost model live with {cm['samples']} samples")
            warm = 0
            for tier in ("batched", "serial"):
                st = cm["tiers"].get(tier)
                if not st or not st["samples"]:
                    continue
                check(st["medianErrorFactor"] is not None
                      and st["medianErrorFactor"] <= ERROR_FACTOR_BAR,
                      f"{tier} median |error| "
                      f"{st['medianErrorFactor']}x <= "
                      f"{ERROR_FACTOR_BAR}x "
                      f"({st['samples']} samples)")
                warm += 1
            check(warm > 0, "warm engine tiers calibrated")

            # --- exposition: promlint-clean incl. the new families
            text = req(base, "GET", "/metrics").decode()
            findings = lint_text(text)
            check(not findings,
                  f"promlint clean ({findings[:2] if findings else 'ok'})")
            for family in ("pilosa_cost_model_samples_total",
                           "pilosa_cost_model_error_bucket"):
                check(family in text,
                      f"{family} live on /metrics")
        finally:
            server.close()


def phase_mesh_tiers():
    from pilosa_tpu.server.server import Server

    with tempfile.TemporaryDirectory(prefix="explaincheck-m-") as tmp:
        import socket

        socks = []
        for _ in range(2):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        hosts = [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
        for s in socks:
            s.close()
        servers = [
            Server(os.path.join(tmp, f"n{i}"), bind=hosts[i],
                   cluster_hosts=hosts, anti_entropy_interval=0,
                   polling_interval=0,
                   mesh={"enabled": True}).open()
            for i in range(2)]
        try:
            base = f"http://{hosts[0]}"
            post(base, "/index/i", "{}")
            post(base, "/index/i/frame/f", "{}")
            import numpy as np

            rng = np.random.default_rng(23)
            for s in range(4):
                b = s * SLICE_WIDTH
                for rid in (1, 2):
                    cols = rng.choice(3000, 120, replace=False) + b
                    body = "\n".join(
                        f'SetBit(frame="f", rowID={rid}, columnID={c})'
                        for c in cols.tolist())
                    post(base, "/index/i/query", body)
            servers[0].executor._result_memo_off = True
            servers[0].handler._resp_cache = None
            q = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
                 'Bitmap(frame="f", rowID=2)))')

            out = json.loads(post(base, "/index/i/query?explain=true",
                                  q))
            exp = out["explain"]
            check(exp["servedBy"] == "mesh",
                  f"2-node Count servedBy=mesh "
                  f"(got {exp['servedBy']})")
            chain = exp["calls"][0]["tiers"]
            check(chain and chain[0]["tier"] == "mesh"
                  and chain[0]["decision"] == "served",
                  "mesh chain hop: served")
            mesh_result = out["results"]

            # Node b's plane leaves the group → not_resident → the
            # query falls to the HTTP fan-out tier, bit-exact.
            servers[1].executor.meshplane.close()
            out = json.loads(post(base, "/index/i/query?explain=true",
                                  q))
            exp = out["explain"]
            check(exp["servedBy"] == "http",
                  f"after plane leaves: servedBy=http "
                  f"(got {exp['servedBy']})")
            check(any(h.startswith("mesh:")
                      for h in exp["fallbackChain"]),
                  f"mesh decline hop recorded "
                  f"({exp['fallbackChain']})")
            check(out["results"] == mesh_result,
                  "bit-exact across mesh vs HTTP serving")
        finally:
            for s in servers:
                s.close()


def main():
    print("explaincheck phase 1: single-node tiers + cost model "
          "(live server)")
    phase_single_node()
    print("explaincheck phase 2: mesh-served / mesh-declined tiers")
    phase_mesh_tiers()
    if FAILURES:
        print("\nexplaincheck: FAIL")
        for f in FAILURES:
            print(f"  - {f}")
        return 1
    print("explaincheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
