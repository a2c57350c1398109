"""Hedge-tail benchmark: hold read p99 through a slow replica
(ISSUE 18): a real-socket 2-node replica_n=2 cluster (subprocess
nodes, the soak_cluster harness idiom) with ``executor.slice.delay``
armed on one replica at runtime. Node B is pinned to the serial
execution path (``PILOSA_TPU_FORCE_PATH=serial``) so the armed delay
keeps firing instead of the per-shape path model learning its way
around the injected slowness, and boots with ``PILOSA_FAULTS=1``
(enabled, nothing armed) so ``POST /debug/faults`` can arm/clear the
point mid-run without restarting the node.

Two arms, both coordinated through the healthy node A:

Arm 1 — legacy preferred-owner assignment + hedged reads
  (``PILOSA_HEDGE_READS=1``, routing off): the slice hash makes B the
  preferred owner of roughly half the slices, so the armed delay is
  the classic slow replica on the primary leg. Asserts the hedge race
  rescues (hedged queries settle near the healthy latency while
  budget-suppressed ones pay the full slow leg), the winner
  accounting balances (fired == wonPrimary + wonHedge, in-flight
  gauge back to zero), the metastability guard engages
  (``suppressed{budget}`` > 0) and structurally bounds extra backend
  legs under 15% (ratio x primary legs + burst), and p99 recovers to
  within 2x the healthy baseline after the fault clears. The live
  /metrics exposition must stay promlint-clean with the
  ``pilosa_hedge_*`` families present.

Arm 2 — replica-aware routing + hedged reads (the production
  posture, ``PILOSA_HEDGE_ROUTING=1`` too): the vitals-scored router
  serves every replica-owned slice from the healthy local owner
  (``routedNonPreferred`` > 0 proves it engaged), so the faulted p99
  holds within 2x the healthy-cluster p99 at ~zero extra backend
  legs — the acceptance gate.

Every read in both arms is bit-exact against the acknowledged write
count, and a freshness probe (a write landed mid-fault must be
visible to the very next read — writes fan out synchronously to every
replica owner) makes "zero stale reads" a live assertion rather than
a vacuous one. Reads carry ``?profile=true``: it bypasses the
response-replay and result-memo tiers on every node in the chain
(each read exercises the real fan-out) and returns the querystats
footer whose ``hedgeLegs`` entries classify each query as hedged /
suppressed for the rescue assertion.

Flags: ``--reads`` baseline phase size, ``--faulted-reads`` the arm-1
faulted window (sized so burst + ratio x legs keeps the overall hedge
ratio under 15%), ``--slices``, ``--delay`` per-slice injected
seconds, ``--hedge-delay-ms`` the hedge timer floor.

Exit code 0 = pass; 1 = fail with the reasons on stderr. Emits
bench-style ``{"metric": ...}`` JSON lines on stdout.
"""
import argparse
import http.client
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402
from pilosa_tpu.testing import free_ports  # noqa: E402

PROBE_TTL = "0.4"          # children's PILOSA_EPOCH_PROBE_TTL
COUNT_Q = 'Count(Bitmap(frame="f", rowID=1))'
# p99 ratios never divide by a sub-jitter baseline: loopback HTTP on a
# loaded CI box sees multi-ms scheduler noise that would make a 2x
# bound on a 3 ms denominator meaningless.
JITTER_FLOOR_S = 0.025


def http_req(host, method, path, body=None, timeout=30, headers=None):
    h, _, p = host.rpartition(":")
    conn = http.client.HTTPConnection(h, int(p), timeout=timeout)
    try:
        conn.request(method, path,
                     body=body.encode() if isinstance(body, str) else body,
                     headers=headers or {})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def wait_ready(host, timeout=120):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if http_req(host, "GET", "/version", timeout=5)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise RuntimeError(f"node {host} never became ready")


def pctl(xs, q):
    s = sorted(xs)
    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]


class Node:
    def __init__(self, host, data_dir, cluster_hosts, extra_env=None):
        self.host = host
        self.data_dir = data_dir
        self.cluster_hosts = cluster_hosts
        self.extra_env = extra_env or {}
        self.proc = None

    def start(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PILOSA_EPOCH_PROBE_TTL"] = PROBE_TTL
        env.update(self.extra_env)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "-d", self.data_dir, "-b", self.host,
             "--cluster-hosts", ",".join(self.cluster_hosts),
             "--replicas", "2"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        return self

    def stop(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


class HedgeTail:
    def __init__(self, opts):
        self.opts = opts
        self.fails = []
        self.tmp = tempfile.mkdtemp(prefix="hedge_tail_")
        self.nodes = []
        self.expected = 0
        self.probe_i = 0
        self.stale_reads = 0
        self.inexact_reads = 0
        self.read_errors = []

    # ------------------------------------------------------------ utils

    def fail(self, msg):
        print(f"FAIL: {msg}", file=sys.stderr)
        self.fails.append(msg)

    def metric(self, name, value, unit):
        print(json.dumps({"metric": name, "value": value, "unit": unit}),
              flush=True)

    def boot(self, label, routing):
        hedge_env = {
            "PILOSA_HEDGE_READS": "1",
            "PILOSA_HEDGE_DELAY_MS": str(self.opts.hedge_delay_ms),
            "PILOSA_HEDGE_MAX_PER_REQUEST": "8",
            # Result-memo off on every node: a memo replay would serve
            # the repeated Count without any fan-out, measuring nothing.
            "PILOSA_TPU_RESULT_MEMO": "0",
        }
        if routing:
            hedge_env["PILOSA_HEDGE_ROUTING"] = "1"
        b_env = dict(hedge_env)
        b_env["PILOSA_FAULTS"] = "1"
        b_env["PILOSA_TPU_FORCE_PATH"] = "serial"
        hosts = [f"127.0.0.1:{p}" for p in free_ports(2)]
        self.nodes = [
            Node(hosts[0], os.path.join(self.tmp, f"{label}_a"), hosts,
                 extra_env=hedge_env).start(),
            Node(hosts[1], os.path.join(self.tmp, f"{label}_b"), hosts,
                 extra_env=b_env).start(),
        ]
        for node in self.nodes:
            wait_ready(node.host)
        self.expected = 0
        return self.nodes[0].host, self.nodes[1].host

    def stop_nodes(self):
        for node in self.nodes:
            node.stop()
        self.nodes = []

    def seed(self, a):
        assert http_req(a, "POST", "/index/hedge", "{}")[0] == 200
        assert http_req(a, "POST", "/index/hedge/frame/f", "{}")[0] == 200
        for s in range(self.opts.slices):
            st, _, body = http_req(
                a, "POST", "/index/hedge/query",
                f'SetBit(frame="f", rowID=1, columnID={s * SLICE_WIDTH + 1})')
            assert st == 200, body
        self.expected = self.opts.slices

    def write_probe(self, a, label):
        """One fresh acknowledged bit — the very next read must count
        it (zero stale reads through whatever routing/hedging does)."""
        s = self.probe_i % self.opts.slices
        col = s * SLICE_WIDTH + 1000 + self.probe_i
        self.probe_i += 1
        st, _, body = http_req(
            a, "POST", "/index/hedge/query",
            f'SetBit(frame="f", rowID=1, columnID={col})')
        if st != 200:
            self.fail(f"{label}: probe write HTTP {st}: {body[:120]!r}")
            return
        self.expected += 1

    def read(self, a, label):
        """-> (latency_s, hedgeLegs) for one profiled Count, checking
        bit-exactness (and stale == behind the acked count) in-line."""
        t0 = time.perf_counter()
        try:
            st, _, body = http_req(a, "POST",
                                   "/index/hedge/query?profile=true",
                                   COUNT_Q)
        except OSError as e:
            self.read_errors.append(f"{label}: {e}")
            return None, []
        lat = time.perf_counter() - t0
        if st != 200:
            self.read_errors.append(f"{label}: HTTP {st}: {body[:120]!r}")
            return None, []
        doc = json.loads(body)
        got = doc["results"][0]
        if got != self.expected:
            self.inexact_reads += 1
            if got < self.expected:
                self.stale_reads += 1
            if self.inexact_reads <= 3:
                self.fail(f"{label}: read {got} != acked {self.expected}")
        legs = doc.get("profile", {}).get("resources", {}) \
                  .get("hedgeLegs", [])
        return lat, legs

    def phase(self, a, label, n, probe_every=0):
        """-> (lats, all hedgeLegs entries paired with their query's
        latency)."""
        lats, leg_lats = [], []
        for i in range(n):
            if probe_every and i % probe_every == probe_every - 1:
                self.write_probe(a, label)
            lat, legs = self.read(a, label)
            if lat is None:
                continue
            lats.append(lat)
            for leg in legs:
                leg_lats.append((leg, lat))
        return lats, leg_lats

    def arm_fault(self, b):
        st, _, body = http_req(
            b, "POST", "/debug/faults",
            json.dumps({"spec":
                        f"executor.slice.delay=delay({self.opts.delay})"}))
        assert st == 200, (st, body)

    def clear_fault(self, b):
        st, _, body = http_req(b, "POST", "/debug/faults",
                               json.dumps({"clear": True}))
        assert st == 200, (st, body)

    def hedge_snap(self, a):
        st, _, body = http_req(a, "GET", "/debug/hedge")
        assert st == 200, (st, body)
        return json.loads(body)

    def wait_settled(self, a, label, timeout=10):
        """In-flight hedge gauge back to zero (loser legs run out)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.hedge_snap(a).get("inflight", 0) == 0:
                return True
            time.sleep(0.2)
        self.fail(f"{label}: hedge inflight gauge never settled to 0")
        return False

    # ------------------------------------------------------------- arms

    def run_arm1(self):
        """Legacy assignment + hedging: the hedge race is what holds
        the queries it covers, the budget is what bounds it."""
        a, b = self.boot("legacy", routing=False)
        try:
            self.seed(a)
            self.phase(a, "arm1 warmup", 5)  # compile/cache fills
            healthy, _ = self.phase(a, "arm1 healthy", self.opts.reads,
                                    probe_every=10)
            p99_healthy = pctl(healthy, 0.99)
            self.metric("hedge_healthy_p99_ms",
                        round(p99_healthy * 1e3, 2),
                        f"ms (legacy+hedge arm, {len(healthy)} reads)")

            base_snap = self.hedge_snap(a)
            if base_snap.get("legsPrimary", 0) == 0:
                self.fail("arm1: no remote primary legs in the healthy "
                          "phase — preferred-owner hash sent nothing "
                          "to the peer?")

            self.arm_fault(b)
            faulted, leg_lats = self.phase(a, "arm1 faulted",
                                           self.opts.faulted_reads,
                                           probe_every=25)
            self.clear_fault(b)

            p99_faulted = pctl(faulted, 0.99)
            hedged = [lat for leg, lat in leg_lats
                      if leg.get("hedged") and leg.get("winner")]
            starved = [lat for leg, lat in leg_lats
                       if leg.get("suppressed") == "budget"]
            self.metric("hedge_faulted_legacy_p99_ms",
                        round(p99_faulted * 1e3, 2),
                        f"ms (slow replica, {len(hedged)} hedged / "
                        f"{len(starved)} budget-suppressed of "
                        f"{len(faulted)} reads)")

            snap = self.hedge_snap(a)
            fired = snap.get("fired", 0)
            if fired < 5:
                self.fail(f"arm1: only {fired} hedges fired under a "
                          "sustained slow replica")
            if snap.get("wonHedge", 0) < 1:
                self.fail("arm1: no hedge ever won against a leg "
                          f"{self.opts.delay * 1e3:.0f} ms/slice slow")
            settled = snap.get("wonPrimary", 0) + snap.get("wonHedge", 0)
            if settled != fired:
                self.fail(f"arm1: winner accounting drifted: "
                          f"fired={fired} settled={settled}")
            if snap.get("suppressed", {}).get("budget", 0) < 1:
                self.fail("arm1: the hedge budget never ran dry over "
                          f"{self.opts.faulted_reads} slow reads — "
                          "metastability guard untested")
            if hedged and starved:
                resc, full = pctl(hedged, 0.5), pctl(starved, 0.5)
                self.metric("hedge_rescue_p50_ms", round(resc * 1e3, 2),
                            "ms (hedged reads; budget-suppressed p50 "
                            f"{full * 1e3:.1f} ms)")
                if resc >= full / 2:
                    self.fail(f"arm1: hedged reads (p50 {resc * 1e3:.1f} "
                              "ms) not clearly faster than "
                              f"budget-suppressed ({full * 1e3:.1f} ms)")
            elif not hedged:
                self.fail("arm1: no read was classified hedged via "
                          "?profile hedgeLegs")

            self.wait_settled(a, "arm1")
            self.promlint(a, "arm1")

            recovered, _ = self.phase(a, "arm1 recovered",
                                      self.opts.reads, probe_every=10)
            p99_rec = pctl(recovered, 0.99)
            self.metric("hedge_recovered_p99_ms",
                        round(p99_rec * 1e3, 2),
                        "ms (fault cleared, same cluster)")
            bound = 2 * max(p99_healthy, JITTER_FLOOR_S)
            if p99_rec > bound:
                self.fail(f"arm1: recovered p99 {p99_rec * 1e3:.1f} ms "
                          f"> 2x healthy ({bound * 1e3:.1f} ms)")

            end = self.hedge_snap(a)
            legs_p = end.get("legsPrimary", 0)
            legs_h = end.get("legsHedge", 0)
            burst = end.get("budget", {}).get("burst", 8.0)
            ratio = end.get("budget", {}).get("ratio", 0.1)
            if legs_h > ratio * legs_p + burst:
                self.fail(f"arm1: hedge legs {legs_h} exceed the "
                          f"structural budget bound "
                          f"{ratio} x {legs_p} + {burst}")
            extra = legs_h / max(1, legs_p)
            self.metric("hedge_extra_leg_ratio",
                        round(extra, 4),
                        f"hedge/primary backend legs ({legs_h}/{legs_p})")
            if extra >= 0.15:
                self.fail(f"arm1: extra backend legs {extra:.1%} >= 15%")
        finally:
            self.stop_nodes()

    def run_arm2(self):
        """Replica-aware routing + hedging (the production posture):
        the acceptance gate — faulted p99 within 2x healthy."""
        a, b = self.boot("routed", routing=True)
        try:
            self.seed(a)
            self.phase(a, "arm2 warmup", 5)  # compile/cache fills
            healthy, _ = self.phase(a, "arm2 healthy", self.opts.reads,
                                    probe_every=10)
            p99_healthy = pctl(healthy, 0.99)
            self.metric("routed_healthy_p99_ms",
                        round(p99_healthy * 1e3, 2),
                        f"ms (routed arm, {len(healthy)} reads)")

            self.arm_fault(b)
            faulted, _ = self.phase(a, "arm2 faulted",
                                    max(self.opts.reads, 60),
                                    probe_every=10)
            p99_faulted = pctl(faulted, 0.99)
            self.metric("routed_faulted_p99_ms",
                        round(p99_faulted * 1e3, 2),
                        "ms (slow replica, routed around)")
            bound = 2 * max(p99_healthy, JITTER_FLOOR_S)
            if p99_faulted > bound:
                self.fail(f"arm2: faulted p99 {p99_faulted * 1e3:.1f} ms "
                          f"> 2x healthy ({bound * 1e3:.1f} ms)")

            snap = self.hedge_snap(a)
            if snap.get("routedNonPreferred", 0) < 1:
                self.fail("arm2: the replica router never overrode a "
                          "preferred owner — routing did not engage")
            legs_h = snap.get("legsHedge", 0)
            total_reads = len(healthy) + len(faulted)
            if legs_h >= 0.15 * total_reads:
                self.fail(f"arm2: {legs_h} hedge legs over "
                          f"{total_reads} reads >= 15% extra load")

            self.clear_fault(b)
            recovered, _ = self.phase(a, "arm2 recovered",
                                      max(self.opts.reads // 2, 10),
                                      probe_every=10)
            p99_rec = pctl(recovered, 0.99)
            if p99_rec > bound:
                self.fail(f"arm2: recovered p99 {p99_rec * 1e3:.1f} ms "
                          f"> 2x healthy ({bound * 1e3:.1f} ms)")
            self.wait_settled(a, "arm2")
        finally:
            self.stop_nodes()

    def promlint(self, a, label):
        """The live exposition must stay promlint-clean WITH the
        pilosa_hedge_* families present and counting."""
        from tools.promlint import exposition_families, lint_text

        st, _, body = http_req(a, "GET", "/metrics")
        assert st == 200, st
        text = body.decode()
        for lineno, msg in lint_text(text):
            self.fail(f"{label}: promlint /metrics:{lineno}: {msg}")
        fams = {f for f in exposition_families(text)
                if f.startswith("pilosa_hedge_")}
        for want in ("pilosa_hedge_legs_primary_total",
                     "pilosa_hedge_legs_hedge_total",
                     "pilosa_hedge_fired_total",
                     "pilosa_hedge_suppressed_total",
                     "pilosa_hedge_budget_tokens"):
            if want not in fams:
                self.fail(f"{label}: {want} missing from the live "
                          "/metrics exposition")

    # -------------------------------------------------------------- run

    def run(self):
        t0 = time.monotonic()
        try:
            self.run_arm1()
            self.run_arm2()
        finally:
            self.stop_nodes()
            shutil.rmtree(self.tmp, ignore_errors=True)
        for err in self.read_errors[:3]:
            self.fail(f"read error: {err}")
        if len(self.read_errors) > 3:
            self.fail(f"... and {len(self.read_errors) - 3} more "
                      "read errors")
        self.metric("hedge_stale_reads", self.stale_reads,
                    "reads behind the acked write count (must be 0)")
        if self.stale_reads:
            self.fail(f"{self.stale_reads} stale reads")
        if self.inexact_reads and not self.fails:
            self.fail(f"{self.inexact_reads} bit-exactness violations")
        self.metric("hedge_tail_wall_s",
                    round(time.monotonic() - t0, 1), "s total")
        return self.fails


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--reads", type=int, default=40,
                   help="reads per healthy/recovery phase")
    p.add_argument("--faulted-reads", type=int, default=150,
                   help="arm-1 faulted-window reads (sized so "
                        "burst + ratio x legs stays under 15%%)")
    p.add_argument("--slices", type=int, default=16)
    p.add_argument("--delay", type=float, default=0.02,
                   help="injected per-slice delay seconds")
    p.add_argument("--hedge-delay-ms", type=float, default=25.0,
                   help="hedge timer floor (above healthy leg "
                        "latency, far below the faulted leg)")
    return p.parse_args(argv)


def main(argv=None):
    fails = HedgeTail(parse_args(argv)).run()
    if fails:
        print(f"\nhedge_tail: {len(fails)} failure(s)", file=sys.stderr)
        return 1
    print("\nhedge_tail: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
