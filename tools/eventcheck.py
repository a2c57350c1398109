"""Flight-recorder + replica-vitals smoke (PR 16), wired into
``make test`` as ``make eventcheck``.

Boot a real-socket 2-node cluster with the recorder and vitals on,
and assert over HTTP that the surfaces are genuinely live:

- each node's ``/debug/events`` journals its own boot and the control
  transitions driven here (a full breaker open→half-open→close cycle
  against a real peer);
- ``?scope=cluster`` merges both journals into one causally-ordered
  timeline;
- ``/debug/replicas`` carries per-peer latency quantiles fed by the
  real fan-out, and the slow-replica watchdog fires
  ``replica.degraded`` under an injected ``executor.slice.delay``
  then ``replica.recovered`` once the fault clears;
- the full ``/metrics`` exposition (``pilosa_events_total``,
  ``pilosa_replica_*`` included) passes promlint.

What recorder and vitals cost a request is not measured here: a
timing from this sandbox's CPU backend is not a speed (see PERF.md).

Small and CPU-only by design.
"""
import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402


def post(base, path, body):
    req = urllib.request.Request(f"{base}{path}", data=body.encode(),
                                 method="POST")
    return urllib.request.urlopen(req, timeout=30).read()


def get(base, path):
    return urllib.request.urlopen(f"{base}{path}", timeout=30).read()


def phase_surfaces(fails):
    from pilosa_tpu import faults
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.testing import free_ports
    from tools.promlint import lint_text

    # Enabled before boot so the servers wire the registry's journal
    # hook (the watchdog drill arms/clears it below).
    faults.disable()
    reg = faults.enable()
    hosts = [f"127.0.0.1:{p}" for p in free_ports(2)]
    a_h, b_h = hosts
    observe = {"vitals-window": 1.5, "watchdog-min-ms": 20.0}
    with tempfile.TemporaryDirectory(prefix="eventcheck-") as tmp:
        servers = [
            Server(os.path.join(tmp, f"n{i}"), bind=hosts[i],
                   cluster_hosts=hosts, anti_entropy_interval=0,
                   polling_interval=0, observe=observe,
                   qos={"enabled": True} if i == 0 else None).open()
            for i in range(2)]
        try:
            base = f"http://{a_h}"
            post(base, "/index/i", "{}")
            post(base, "/index/i/frame/f", "{}")
            for s in range(4):
                post(base, "/index/i/query",
                     f'SetBit(frame="f", rowID=1, '
                     f'columnID={s * SLICE_WIDTH + 3})')
            vt = servers[0].vitals
            rec = servers[0].events
            seq = iter(range(1, 1_000_000))

            def drive_until(pred, what, timeout=45):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    # Distinct rows bypass the result memo, so every
                    # query genuinely fans out to peer B.
                    post(base, "/index/i/query",
                         f'Count(Bitmap(frame="f", rowID={next(seq)}))')
                    vt.watchdog_tick()
                    if pred():
                        return True
                    time.sleep(0.005)
                fails.append(f"timeout waiting for {what}: "
                             f"{vt.snapshot()['peers'].get(b_h)}")
                return False

            def peer():
                return vt.snapshot()["peers"].get(b_h) or {}

            # Warm the engines, then drop cold-start samples so the
            # watchdog baseline learns steady state only.
            for _ in range(30):
                post(base, "/index/i/query",
                     f'Count(Bitmap(frame="f", rowID={next(seq)}))')
            with vt._mu:
                vt._peers.clear()
                vt._digests.clear()

            ok = drive_until(
                lambda: (peer().get("baselineP99") or 0) > 0,
                "vitals baseline window")
            if ok:
                reg.configure("executor.slice.delay=delay(0.15)")
                if drive_until(lambda: peer().get("degraded"),
                               "replica.degraded under injected delay"):
                    print(f"  watchdog: degraded at "
                          f"p99={peer()['windowP99']:.3f}s over "
                          f"baseline={peer()['baselineP99']:.3f}s")
                reg.clear("executor.slice.delay")
                if drive_until(
                        lambda: peer().get("degraded") is False,
                        "replica.recovered after fault cleared"):
                    print("  watchdog: recovered after clear")
                kinds = [e["kind"] for e in rec.recent(kinds=["replica"])]
                if kinds[:1] != ["replica.degraded"] \
                        or kinds[-1:] != ["replica.recovered"]:
                    fails.append(f"watchdog event pair wrong: {kinds}")

            # A real breaker cycle on A against peer B.
            brk = servers[0].qos.breakers
            for _ in range(brk.threshold):
                brk.record_failure(b_h)
            brk._b[b_h].opened_at -= brk.cooldown + 1
            if brk.allow(b_h) != brk.PROBE:
                fails.append("breaker did not admit half-open probe")
            brk.record_success(b_h)

            # Per-node journal, then the cluster-merged timeline.
            ev = json.loads(get(base, "/debug/events"))
            if not (ev.get("enabled") and ev.get("events")):
                fails.append(f"node journal empty: {ev}")
            doc = json.loads(get(
                base, "/debug/events?scope=cluster&limit=512"))
            evs = doc.get("events", [])
            if sorted(doc.get("nodes", [])) != sorted(hosts):
                fails.append(f"cluster merge missing nodes: {doc}")
            if doc.get("errors"):
                fails.append(f"cluster merge errors: {doc['errors']}")
            if {e["host"] for e in evs} != set(hosts):
                fails.append("merged timeline lacks both nodes' events")
            order = [e["kind"] for e in evs
                     if e["kind"].startswith("breaker.")]
            if order != ["breaker.open", "breaker.half_open",
                         "breaker.close"]:
                fails.append(f"breaker cycle out of causal order: "
                             f"{order}")
            starts = [e for e in evs if e["kind"] == "server.start"]
            if {e["host"] for e in starts} != set(hosts):
                fails.append("server.start missing from a node")
            print(f"  timeline: {len(evs)} merged events from "
                  f"{len(doc.get('nodes', []))} nodes, "
                  f"{len(ev['events'])} local")

            # Vitals surface: the fan-out fed peer B's digests.
            rp = json.loads(get(base, "/debug/replicas"))
            pb = rp.get("peers", {}).get(b_h)
            if not pb or not pb["requests"]:
                fails.append(f"replica vitals never fed: {rp}")
            else:
                print(f"  replicas: peer {b_h} n={pb['requests']} "
                      f"p50={pb['p50'] * 1e3:.1f}ms "
                      f"health={pb['healthScore']}")

            # Exposition: new families live and promlint-clean.
            text = get(base, "/metrics").decode()
            findings = lint_text(text)
            if findings:
                fails.append(f"promlint findings on live /metrics: "
                             f"{findings[:3]}")
            for family in ("pilosa_events_total{",
                           "pilosa_replica_requests_total{",
                           "pilosa_replica_latency_seconds{",
                           "pilosa_replica_health_score{"):
                if family not in text:
                    fails.append(f"family missing from /metrics: "
                                 f"{family}")
        finally:
            faults.disable()
            for s in servers:
                s.close()


def main():
    fails = []
    print("eventcheck: flight recorder + vitals (2-node live)")
    phase_surfaces(fails)
    if fails:
        print("\neventcheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("eventcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
