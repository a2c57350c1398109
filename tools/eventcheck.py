"""Flight-recorder + replica-vitals smoke (PR 16), wired into
``make test`` as ``make eventcheck``.

Phase 1 (surfaces, HTTP): boot a real-socket 2-node cluster with the
recorder and vitals on, and assert the surfaces are genuinely live:

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

Phase 2 (overhead, in-process dispatch): warm serving-path QPS with
recorder+vitals ON must be within 2% of the SAME measurement with
them OFF — the instrumentation-creep gate, obscheck's paired
interleaved-A/B method (median-of-round ratios, noisy-box retries).

Small and CPU-only by design.
"""
import json
import os
import statistics
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402

OVERHEAD_BAR = 0.02          # on-QPS may lag off-QPS by at most 2%
ROUNDS = 7                   # A/B rounds per arm (median taken)
ATTEMPTS = 3                 # noisy-box retries before failing


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


def _build_serving(tmp):
    """Warm single-node serving path (handler dispatch, no sockets)
    sized so a warm query costs enough for a 2% delta to be
    measurable above timer noise."""
    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server.handler import Handler
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(os.path.join(tmp, "ov")).open()
    idx = holder.create_index("ov")
    idx.create_frame("d")
    rng = np.random.default_rng(3)
    for s in range(8):
        b = s * SLICE_WIDTH
        for rid in range(1, 9):
            cols = rng.choice(50_000, size=2000, replace=False)
            idx.frame("d").import_bits([rid] * len(cols),
                                       (b + cols).tolist())
    e = Executor(holder)
    e._force_path = "batched"
    e._result_memo_off = True  # every query must reach the engine
    return holder, Handler(holder, e)


def _qps(handler, queries, seconds=0.6):
    t_end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < t_end:
        status, _, _ = handler.dispatch(
            "POST", "/index/ov/query", {},
            queries[n % len(queries)], {})[:3]
        if status != 200:
            raise RuntimeError(f"query failed: HTTP {status}")
        n += 1
    return n / seconds


def _measure(handler, holder, queries, seconds=0.6):
    """Median warm QPS for recorder+vitals ON and OFF, interleaved
    with alternating arm order per round; paired per-round ratios
    cancel slow thermal/GC drift."""
    from pilosa_tpu.observe import events as events_mod
    from pilosa_tpu.observe import replica as replica_mod

    rec = events_mod.EventRecorder(host="ov")
    vt = replica_mod.ReplicaVitals()

    def run_on():
        handler.events = rec
        handler.vitals = vt
        holder.events = rec
        holder.governor.events = rec
        return _qps(handler, queries, seconds)

    def run_off():
        handler.events = events_mod.NOP
        handler.vitals = replica_mod.NOP
        holder.events = None
        holder.governor.events = None
        return _qps(handler, queries, seconds)

    on, off, ratios = [], [], []
    for i in range(ROUNDS):
        if i % 2:
            a = run_on()
            b = run_off()
        else:
            b = run_off()
            a = run_on()
        on.append(a)
        off.append(b)
        ratios.append(a / b)
    return (statistics.median(on), statistics.median(off),
            statistics.median(ratios))


def phase_overhead(fails):
    with tempfile.TemporaryDirectory(prefix="eventcheck-ov-") as tmp:
        holder, handler = _build_serving(tmp)
        try:
            queries = [
                (f'Count(Intersect(Bitmap(frame="d", rowID={a}), '
                 f'Bitmap(frame="d", rowID={b})))').encode()
                for a in range(1, 9) for b in range(a + 1, 9)]
            # Warm plan/compile tiers before any timed round.
            for q in queries:
                handler.dispatch("POST", "/index/ov/query", {}, q, {})
                handler.dispatch("POST", "/index/ov/query", {}, q, {})
            best = on_qps = off_qps = None
            for attempt in range(ATTEMPTS):
                on_qps, off_qps, ratio = _measure(handler, holder,
                                                  queries)
                best = max(best or 0.0, ratio)
                if ratio >= 1.0 - OVERHEAD_BAR:
                    break
            print(f"  serving: warm on={on_qps:,.0f} q/s "
                  f"off={off_qps:,.0f} q/s "
                  f"overhead={100 * (1 - best):.2f}% "
                  f"(bar {100 * OVERHEAD_BAR:.0f}%)")
            if best < 1.0 - OVERHEAD_BAR:
                fails.append(
                    f"recorder+vitals overhead {100 * (1 - best):.2f}% "
                    f"exceeds {100 * OVERHEAD_BAR:.0f}% "
                    f"(on={on_qps:.0f}, off={off_qps:.0f})")
        finally:
            holder.close()


def main():
    fails = []
    print("eventcheck phase 1: flight recorder + vitals (2-node live)")
    phase_surfaces(fails)
    print("eventcheck phase 2: serving-path overhead gate")
    phase_overhead(fails)
    if fails:
        print("\neventcheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("eventcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
