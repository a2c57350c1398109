"""Heat-driven autopilot smoke (PR 17), wired into ``make test`` as
``make autopilotcheck``.

A real-socket 2-node cluster with the controller armed must close the
loop end to end, with every safety property observable:

1. injected heat skew (hot slices pinned to the degraded peer) makes
   ``POST /cluster/autopilot/plan`` produce a placement action with
   its sensor evidence inline — and the dry-run preview mutates
   NOTHING: no resize, no budget token, no apply journal;
2. one ``tick()`` applies the plan through the real rebalancer; the
   merged cluster timeline shows ``autopilot.plan`` →
   ``rebalance.begin`` (stamped ``reason="autopilot"``) →
   ``autopilot.apply`` in causal order, and the placement converges
   to the planned host order;
3. an immediate second action is BLOCKED by the rate limiter
   (``autopilot.cooldown`` journaled, counters bumped, actuator never
   invoked);
4. a wedged apply (armed ``autopilot.apply.slow``) aborted by the
   mid-flight kill switch journals ``autopilot.abort``, releases its
   budget token, and leaves placement exactly where it was — never
   mid-transition;
5. the live ``/metrics`` exposition carries the ``pilosa_autopilot_*``
   families and stays promlint-clean.

Small and CPU-only by design.
"""
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402

HEAT_TOUCHES = 400     # injected skew per hot slice
RESIZE_TIMEOUT = 60.0


def post(base, path, body):
    req = urllib.request.Request(f"{base}{path}", data=body.encode(),
                                 method="POST")
    return urllib.request.urlopen(req, timeout=30).read()


def get(base, path):
    return urllib.request.urlopen(f"{base}{path}", timeout=30).read()


def wait_for(pred, what, timeout=RESIZE_TIMEOUT):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    raise AssertionError(f"timeout waiting for {what}")


def main():
    from pilosa_tpu import faults
    from pilosa_tpu.observe import heatmap as heatmap_mod
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.testing import free_ports
    from tools.promlint import lint_text

    fails = []
    faults.disable()
    hosts = [f"127.0.0.1:{p}" for p in free_ports(2)]
    a_h, b_h = hosts
    autopilot = {"enabled": True, "interval": 0, "min-dwell": 60.0,
                 "max-actions-per-window": 2, "window": 300.0,
                 "heat-imbalance": 1.3}
    print("autopilotcheck: 2-node cluster, controller armed")
    with tempfile.TemporaryDirectory(prefix="autopilotcheck-") as tmp:
        servers = [
            Server(os.path.join(tmp, f"n{i}"), bind=hosts[i],
                   cluster_hosts=hosts, anti_entropy_interval=0,
                   polling_interval=0, observe={"enabled": True},
                   autopilot=autopilot).open()
            for i in range(2)]
        ap = servers[0].autopilot
        try:
            base = f"http://{a_h}"
            post(base, "/index/i", "{}")
            post(base, "/index/i/frame/f", "{}")
            for s in range(6):
                post(base, "/index/i/query",
                     f'SetBit(frame="f", rowID=1, '
                     f'columnID={s * SLICE_WIDTH + 3})')

            # --- injected skew: all the heat on peer B's slices, and
            # B marked degraded (half capacity) so moving its hot
            # positions to A is genuine relief the planner can find.
            cluster = servers[0].cluster
            from pilosa_tpu.cluster.placement import PlacementMap
            b_slices = []
            for s in range(6):
                pid = cluster.partition("i", s)
                owners = PlacementMap.preview_owners(
                    hosts, pid, cluster.replica_n, cluster.hasher)
                if owners[0] == b_h:
                    b_slices.append(s)
            if not b_slices:
                raise AssertionError("no slice primary on peer B")
            for s in b_slices:
                heatmap_mod.ACTIVE.touch_slice("i", s, n=HEAT_TOUCHES)
            servers[0].vitals._peer(b_h).degraded = True

            # --- 1. dry-run preview: plan produced, nothing mutated.
            gen0 = cluster.placement.generation
            plan = json.loads(post(base, "/cluster/autopilot/plan",
                                   "{}"))
            acts = [a for a in plan.get("actions", [])
                    if a["loop"] == "placement"]
            if not acts:
                fails.append(f"no placement action planned: {plan}")
            else:
                act = acts[0]
                ev = act["evidence"]
                print(f"  plan: imbalance={ev['imbalance']} -> "
                      f"projected={ev['projected']}, hosts "
                      f"{hosts} -> {act['hosts']}")
                if act["hosts"] == hosts:
                    fails.append("planned host order is a no-op")
                if ev["degraded"] != [b_h]:
                    fails.append(f"evidence missed degraded peer: "
                                 f"{ev['degraded']}")
            snap = json.loads(get(base, "/debug/autopilot"))
            if cluster.placement.generation != gen0 \
                    or servers[0].rebalancer.is_running():
                fails.append("dry-run preview mutated placement")
            if snap["budget"]["used"] != 0:
                fails.append(f"dry-run consumed a budget token: "
                             f"{snap['budget']}")
            applied = [e for e in servers[0].events.recent(
                kinds=["autopilot.apply"])]
            if applied:
                fails.append(f"dry-run journaled an apply: {applied}")

            # --- 2. one real tick applies through the rebalancer.
            if not fails:
                ap.tick()
                wait_for(lambda: not servers[0].rebalancer.is_running()
                         and cluster.placement.phase == "stable"
                         and cluster.placement.generation > gen0,
                         "autopilot-driven resize to converge")
                new_hosts = list(cluster.placement.current_hosts())
                if new_hosts != act["hosts"]:
                    fails.append(f"placement converged to {new_hosts}, "
                                 f"planned {act['hosts']}")
                print(f"  applied: generation "
                      f"{cluster.placement.generation}, hosts "
                      f"{new_hosts}")

                doc = json.loads(get(
                    base, "/debug/events?scope=cluster&limit=1024"))
                evs = doc.get("events", [])
                begins = [e for e in evs
                          if e["kind"] == "rebalance.begin"]
                if not begins or begins[-1].get("reason") != "autopilot":
                    fails.append(f"rebalance.begin not stamped "
                                 f"reason=autopilot: {begins[-1:]}")
                order = [e["kind"] for e in evs if e["kind"] in
                         ("autopilot.plan", "rebalance.begin",
                          "autopilot.apply")]
                want = ["autopilot.plan", "rebalance.begin",
                        "autopilot.apply"]
                # The planned-then-applied sequence must appear as a
                # subsequence of the merged timeline, in that order.
                it = iter(order)
                if not all(k in it for k in want):
                    fails.append(f"apply out of causal order vs "
                                 f"rebalance events: {order}")
                else:
                    print(f"  timeline: causal order ok ({order})")

            # --- 3. rate limiter blocks an immediate second action.
            before = json.loads(get(base, "/debug/autopilot"))
            blocked = ap.apply({"_actions": [{
                "loop": "placement", "kind": "rebalance",
                "hosts": hosts, "evidence": {}}]})
            if not blocked or blocked[0]["applied"]:
                fails.append(f"rate limiter admitted a second action: "
                             f"{blocked}")
            after = json.loads(get(base, "/debug/autopilot"))
            if after["counters"]["cooldownBlockedTotal"] \
                    <= before["counters"]["cooldownBlockedTotal"]:
                fails.append("cooldown counter did not move")
            cools = servers[0].events.recent(
                kinds=["autopilot.cooldown"])
            if not cools:
                fails.append("autopilot.cooldown never journaled")
            else:
                print(f"  rate limiter: blocked "
                      f"({cools[-1]['reason']})")

            # --- 4. wedged apply + mid-flight kill switch.
            ap2 = servers[1].autopilot
            rec2 = servers[1].events
            faults.enable("autopilot.apply.slow=delay(0.5)")
            gen_b = servers[1].cluster.placement.generation
            out = {}

            def run():
                out["r"] = ap2.apply({"_actions": [{
                    "loop": "placement", "kind": "rebalance",
                    "hosts": hosts, "evidence": {}}]})

            t = threading.Thread(target=run)
            t.start()
            time.sleep(0.1)          # inside the injected delay
            ap2.disable()
            t.join(timeout=10)
            faults.disable()
            r = (out.get("r") or [{}])[0]
            if not r.get("aborted"):
                fails.append(f"wedged apply did not abort: {r}")
            if servers[1].cluster.placement.phase != "stable" \
                    or servers[1].cluster.placement.generation != gen_b:
                fails.append("kill switch left placement "
                             "mid-transition")
            if ap2._budget_remaining(time.monotonic()) \
                    != autopilot["max-actions-per-window"]:
                fails.append("aborted action kept its budget token")
            aborts = rec2.recent(kinds=["autopilot.abort"])
            if not aborts:
                fails.append("autopilot.abort never journaled on B")
            else:
                print(f"  kill switch: clean abort "
                      f"({aborts[-1]['reason']}), token released")

            # --- 5. exposition: families live and promlint-clean.
            text = get(base, "/metrics").decode()
            findings = lint_text(text)
            if findings:
                fails.append(f"promlint findings on live /metrics: "
                             f"{findings[:3]}")
            for family in ("pilosa_autopilot_plans_total",
                           "pilosa_autopilot_actions_total{",
                           "pilosa_autopilot_budget_remaining",
                           "pilosa_autopilot_cooldown_blocked_total"):
                if family not in text:
                    fails.append(f"family missing from /metrics: "
                                 f"{family}")
        finally:
            faults.disable()
            for s in servers:
                s.close()

    if fails:
        print("\nautopilotcheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("autopilotcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
