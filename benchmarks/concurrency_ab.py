"""Worker / coalescing A/B over the concurrency benchmark.

Runs benchmarks/concurrency.py under explicit serving configurations,
one child process after another (this parent never touches JAX, so
each arm's server owns the chip in its turn), to answer on the chip
what CPU-validated serving work left open:

  arm A  workers=0            — single-process baseline
  arm B  workers=2            — SO_REUSEPORT transport fan-out; the
                                 master keeps the device
  arm C  workers=0, coalesce=0, count-only
                              — isolates cross-query count coalescing
  arm D  workers=2, exec-reads + cost model, mixed-only
                              — worker-local reads with the
                                 relay-vs-local cost model choosing
                                 per shape (worker_exec.RelayCostModel)

Each arm is a fresh server process (concurrency.py builds its own
index), so arms never share caches. Output lines are the child's
metric JSON, prefixed with the arm tag in the metric name.

Env: CONCURRENCY_AB_SECONDS per point (default 6),
CONCURRENCY_AB_DEADLINE per arm (default 240 s).

``--phases`` (or CONCURRENCY_AB_PHASES=1) runs the PER-PHASE
BREAKDOWN instead of the A/B arms: one traced server, the mixed
read queries driven with ?profile=true at 1 and 8 concurrent
clients, and the span tree aggregated into parse / plan / dispatch /
fanout means — which phase inflates as clients scale (ROADMAP S2).
"""
import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SECONDS = os.environ.get("CONCURRENCY_AB_SECONDS", "6")
DEADLINE = float(os.environ.get("CONCURRENCY_AB_DEADLINE", "240"))

try:
    sys.path.insert(0, HERE)
    import _ledger
except ImportError:  # pragma: no cover — ledger is best-effort
    _ledger = None

# Every varied knob is pinned EXPLICITLY in every arm: an ambient
# operator override (e.g. PILOSA_TPU_COALESCE=0 exported) must not
# silently turn one arm into another and record a wrong conclusion.
ARMS = [
    ("A_solo", {"PILOSA_TPU_WORKERS": "0", "PILOSA_TPU_COALESCE": "1",
                "PILOSA_TPU_WORKER_EXEC": "0",
                "CONCURRENCY_MODES": "both"}),
    ("B_workers2", {"PILOSA_TPU_WORKERS": "2",
                    "PILOSA_TPU_COALESCE": "1",
                    "PILOSA_TPU_WORKER_EXEC": "0",
                    "CONCURRENCY_MODES": "both"}),
    ("C_nocoalesce", {"PILOSA_TPU_WORKERS": "0",
                      "PILOSA_TPU_COALESCE": "0",
                      "PILOSA_TPU_WORKER_EXEC": "0",
                      "CONCURRENCY_MODES": "count"}),
    ("D_workers_exec", {"PILOSA_TPU_WORKERS": "2",
                        "PILOSA_TPU_COALESCE": "1",
                        "PILOSA_TPU_WORKER_EXEC": "1",
                        "CONCURRENCY_MODES": "mixed"}),
]


def _emit(arm, stdout):
    """Forward the child's metric lines, arm-tagged. Returns the
    number of points forwarded."""
    n = 0
    for ln in (stdout or "").splitlines():
        if '"metric"' not in ln:
            continue
        try:
            m = json.loads(ln)
        except ValueError:
            continue
        m["metric"] = f"ab_{arm}_{m['metric']}"
        print(json.dumps(m))
        if _ledger is not None and isinstance(m.get("value"),
                                              (int, float)):
            _ledger.record("concurrency_ab", m["metric"], m["value"],
                           str(m.get("unit", "")), knobs={"arm": arm})
        n += 1
    return n


# ------------------------------------------------- per-phase breakdown

# Span-name → phase buckets. Anything unmatched lands in "other" so
# the buckets always sum to ≤ total and a new span name is visible
# instead of silently vanishing.
_PHASE_OF = (
    ("parse", "parse"),
    ("plan_and_stage", "plan"),
    ("kernel:", "dispatch"),
    ("node.remote", "fanout"),
    ("remote.round", "fanout"),
)
PHASES = ("parse", "plan", "dispatch", "fanout", "other")


def _bucket(span_name):
    for prefix, phase in _PHASE_OF:
        if span_name.startswith(prefix):
            return phase
    return "other"


def _phase_req(host, method, path, body=None):
    import http.client

    h, _, p = host.rpartition(":")
    conn = http.client.HTTPConnection(h, int(p), timeout=60)
    try:
        conn.request(method, path,
                     body=body.encode() if isinstance(body, str) else body)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _aggregate_profile(doc, sums):
    """Fold one ?profile=true span list into per-phase ms sums.
    Leaf-biased: a span's ms counts only the portion not covered by
    its children (so parse isn't double-counted under the root)."""
    spans = doc.get("spans") or []
    child_ms = {}
    for s in spans:
        pid = s.get("parentId")
        if pid is not None and s.get("durationMs") is not None:
            child_ms[pid] = child_ms.get(pid, 0.0) + s["durationMs"]
    for s in spans:
        dur = s.get("durationMs")
        if dur is None:
            continue
        phase = _bucket(s.get("name", ""))
        if phase == "other" and s.get("parentId") is None:
            continue  # the root span: its self-time is transport/misc
        own = max(0.0, dur - child_ms.get(s.get("spanId"), 0.0))
        sums[phase] = sums.get(phase, 0.0) + own
    sums["totalMs"] = sums.get("totalMs", 0.0) + (doc.get("durationMs")
                                                 or 0.0)
    sums["n"] = sums.get("n", 0) + 1


def run_phases():
    """Boot one traced server, drive the mixed read set with
    ?profile=true at 1 and 8 clients, and emit per-phase mean ms —
    the breakdown that explains where a concurrency cliff comes from."""
    import tempfile

    sys.path.insert(0, os.path.dirname(HERE))
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.testing import free_ports

    seconds = float(os.environ.get("CONCURRENCY_AB_PHASE_SECONDS", "5"))
    n_slices = int(os.environ.get("CONCURRENCY_AB_PHASE_SLICES", "32"))
    tmp = tempfile.mkdtemp(prefix="ab_phases_")
    host = f"127.0.0.1:{free_ports(1)[0]}"
    env = dict(os.environ)
    env["PILOSA_TRACE_ENABLED"] = "1"
    env["PILOSA_TPU_RESULT_MEMO"] = "0"   # measure compute, not replays
    env["PILOSA_TPU_RESPONSE_CACHE"] = "0"
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "-d", tmp, "-b", host], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                if _phase_req(host, "GET", "/version")[0] == 200:
                    break
            except OSError:
                pass
            time.sleep(0.25)
        assert _phase_req(host, "POST", "/index/ab", "{}")[0] == 200
        assert _phase_req(host, "POST", "/index/ab/frame/f",
                          "{}")[0] == 200
        for s in range(n_slices):
            _phase_req(host, "POST", "/index/ab/query",
                       f'SetBit(frame="f", rowID=1, '
                       f'columnID={s * SLICE_WIDTH + 7})')
        queries = ['Count(Bitmap(frame="f", rowID=1))',
                   'TopN(frame="f", n=5)',
                   'Count(Intersect(Bitmap(frame="f", rowID=1), '
                   'Bitmap(frame="f", rowID=1)))']

        for clients in (1, 8):
            sums = {}
            lock = threading.Lock()
            stop = time.monotonic() + seconds

            def worker(wid):
                qi = wid
                while time.monotonic() < stop:
                    q = queries[qi % len(queries)]
                    qi += 1
                    st, body = _phase_req(
                        host, "POST", "/index/ab/query?profile=true", q)
                    if st != 200:
                        continue
                    prof = json.loads(body).get("profile")
                    if prof:
                        with lock:
                            _aggregate_profile(prof, sums)

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            n = sums.get("n", 0) or 1
            for phase in PHASES:
                print(json.dumps({
                    "metric": f"ab_phases_{clients}c_{phase}_ms_mean",
                    "value": round(sums.get(phase, 0.0) / n, 3),
                    "unit": f"ms/query over {n} profiled queries"}))
            print(json.dumps({
                "metric": f"ab_phases_{clients}c_total_ms_mean",
                "value": round(sums.get("totalMs", 0.0) / n, 3),
                "unit": "ms/query wall (server-side root span)"}))
            print(json.dumps({
                "metric": f"ab_phases_{clients}c_qps",
                "value": round(n / seconds, 1),
                "unit": f"{clients} clients, profile on"}))
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


# -------------------------------------------- micro-batching A/B
# ``--coalesce`` (or CONCURRENCY_AB_COALESCE=1): the PR-12 acceptance
# capture — mixed Count workload at 1 vs 8 clients through the
# executor engine path on BOTH a dense (resident) index and a
# compressed-container (evicted, count100b sparse shape) index, with
# per-phase coalescer stats (mean/max group size, decline reasons)
# and a bit-exactness cross-check vs coalesce-compressed=false.

def _coalesce_queries():
    pairs = [(1, 2), (1, 3), (2, 3), (1, 4), (2, 4), (3, 4)]
    qs = [f'Count(Intersect(Bitmap(frame="f", rowID={a}), '
          f'Bitmap(frame="f", rowID={b})))' for a, b in pairs]
    qs += [f'Count(Union(Bitmap(frame="f", rowID={a}), '
           f'Bitmap(frame="f", rowID={b})))' for a, b in pairs[:3]]
    qs += [f'Count(Bitmap(frame="f", rowID={r}))' for r in (1, 2, 3)]
    return qs


def _coalesce_measure(ex, index, qs, clients, seconds, want):
    """Closed-loop engine QPS at ``clients`` threads; every observed
    result is checked against the serial oracle (bit-exactness is a
    hard pass/fail, not a sample)."""
    errors = []
    counts = [0] * clients
    start = threading.Barrier(clients + 1)
    stop = [0.0]

    def worker(wid):
        qi = wid * 3
        try:
            start.wait(timeout=60)
            while time.monotonic() < stop[0]:
                q = qs[qi % len(qs)]
                qi += 1
                got = ex.execute(index, q)[0]
                if got != want[q]:
                    raise AssertionError(
                        f"fused result mismatch: {q} -> {got} != "
                        f"{want[q]}")
                counts[wid] += 1
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append(repr(exc)[:200])

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(clients)]
    for t in threads:
        t.start()
    # The end time must be set BEFORE the barrier releases: a worker
    # scheduled ahead of this thread would otherwise read the 0.0
    # placeholder and exit with zero queries, silently undercounting.
    stop[0] = time.monotonic() + seconds
    start.wait(timeout=60)
    t0 = time.perf_counter()
    for t in threads:
        t.join(timeout=seconds + 120)
    if errors:
        raise SystemExit(f"coalesce bench errors: {errors[:3]}")
    return sum(counts) / (time.perf_counter() - t0)


def run_coalesce():
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.dirname(HERE))
    # Executors read this at construction: replays would measure the
    # memo tier, not the dispatch path this A/B is about.
    os.environ["PILOSA_TPU_RESULT_MEMO"] = "0"
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import containers
    from pilosa_tpu.storage.holder import Holder

    seconds = float(os.environ.get("CONCURRENCY_AB_COALESCE_SECONDS",
                                   "5"))
    n_slices = int(os.environ.get("CONCURRENCY_AB_COALESCE_SLICES",
                                  "32"))
    wait_us = int(os.environ.get("CONCURRENCY_AB_COALESCE_WAIT_US",
                                 "400"))
    tmp = tempfile.mkdtemp(prefix="ab_coalesce_")
    holder = Holder(os.path.join(tmp, "data")).open()
    rng = np.random.default_rng(23)

    # Dense 10B-shape: resident fragments, clustered columns (the
    # windowed-dense serving tier).
    idx = holder.create_index("dz")
    idx.create_frame("f")
    frame = holder.index("dz").frame("f")
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        for rid in range(1, 5):
            c = rng.choice(60_000, size=3000, replace=False)
            frame.import_bits([rid] * 3000, (base + c).tolist())

    # Compressed-container index: the count100b sparse capture shape
    # (spread-sparse ARRAY rows + a RUN row), snapshotted + evicted.
    idx = holder.create_index("cz")
    idx.create_frame("f")
    cframe = holder.index("cz").frame("f")
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        for rid, n in ((1, 500), (2, 300), (3, 200)):
            c = rng.choice(SLICE_WIDTH, size=n, replace=False)
            cframe.import_bits([rid] * n, (base + c).tolist())
        start = int(rng.integers(0, SLICE_WIDTH - 3000))
        c = np.arange(start, start + 2000)
        cframe.import_bits([4] * len(c), (base + c).tolist())
    for v in cframe.views.values():
        for frag in list(v.fragments.values()):
            frag.snapshot()
            frag.unload()

    serial = Executor(holder)
    serial._force_path = "serial"
    qs = _coalesce_queries()
    rows_out = []
    for index in ("dz", "cz"):
        # coalesce-compressed=false IS the serial compressed path —
        # the oracle every fused answer is checked against.
        want = {q: serial.execute(index, q)[0] for q in qs}
        ex = Executor(holder)
        ex._force_path = "batched"
        ex._co_enabled_memo = True
        conv0 = containers.conversions_total()
        # 1 client: its best config is no tick window (a lone query
        # must not pay an accumulation wait).
        ex.set_coalesce_config(max_wait_us=0)
        qps1 = _coalesce_measure(ex, index, qs, 1, seconds, want)
        # 8 clients. The tick window is a per-phase tuning knob,
        # recorded in the row: it pays where per-query dispatch cost
        # is high (the compressed tier's serial path = one dispatch
        # PER SLICE; any accelerator backend), and is left at 0 for
        # the dense phase on the CPU backend, whose single-query path
        # is already ONE dispatch sharing the serving core — there the
        # window only adds latency (the dense bar is a chip question,
        # ROADMAP S3).
        phase_wait = wait_us if index == "cz" else 0
        ex.set_coalesce_config(max_wait_us=phase_wait)
        st0 = {k: (dict(v) if isinstance(v, dict) else v)
               for k, v in ex._co_stats.items()}
        qps8 = _coalesce_measure(ex, index, qs, 8, seconds, want)
        st = ex._co_stats
        rounds = st["rounds"] - st0["rounds"]
        fused = st["fused_queries"] - st0["fused_queries"]
        declined = {k: v - st0["declined"].get(k, 0)
                    for k, v in st["declined"].items()
                    if v - st0["declined"].get(k, 0)}
        # 8 clients with coalescing OFF: the per-query dispatch
        # baseline this PR replaces.
        exoff = Executor(holder)
        exoff._force_path = "batched"
        exoff._co_enabled_memo = False
        qps8_off = _coalesce_measure(exoff, index, qs, 8, seconds,
                                     want)
        conv = containers.conversions_total() - conv0
        tag = "dense" if index == "dz" else "compressed"
        mean_group = round(fused / rounds, 2) if rounds else 0.0
        rows_out += [
            {"metric": f"ab_co_{tag}_qps_1c", "value": round(qps1, 1),
             "unit": f"q/s engine, {n_slices} slices, window off"},
            {"metric": f"ab_co_{tag}_qps_8c", "value": round(qps8, 1),
             "unit": f"q/s engine, tick window {phase_wait}us"},
            {"metric": f"ab_co_{tag}_qps_8c_nocoalesce",
             "value": round(qps8_off, 1),
             "unit": "q/s engine, per-query dispatch baseline"},
            {"metric": f"ab_co_{tag}_scaling_8c_over_1c",
             "value": round(qps8 / qps1, 2) if qps1 else 0.0,
             "unit": "x (bar >= 4x; bit-exact vs serial oracle)"},
            {"metric": f"ab_co_{tag}_coalesce_gain_8c",
             "value": round(qps8 / qps8_off, 2) if qps8_off else 0.0,
             "unit": "x vs coalescing off at 8 clients"},
            {"metric": f"ab_co_{tag}_group_mean",
             "value": mean_group,
             "unit": (f"queries/tick over {rounds} ticks; max "
                      f"{st['max_group']}; declines {declined or '{}'}"
                      f"; lanes {st['lane_launches']}; "
                      f"conversions {conv}")},
        ]
    for r in rows_out:
        print(json.dumps(r))
    if _ledger is not None:
        _ledger.record_rows("concurrency_ab", rows_out,
                            knobs={"slices": n_slices,
                                   "wait_us": wait_us,
                                   "seconds": seconds})
    holder.close()
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)


def main():
    if ("--coalesce" in sys.argv[1:]
            or os.environ.get("CONCURRENCY_AB_COALESCE") == "1"):
        run_coalesce()
        return
    if ("--phases" in sys.argv[1:]
            or os.environ.get("CONCURRENCY_AB_PHASES") == "1"):
        run_phases()
        return
    script = os.path.join(HERE, "concurrency.py")
    for arm, env_extra in ARMS:
        env = dict(os.environ)
        env.update(env_extra)
        env["CONCURRENCY_SECONDS"] = SECONDS
        t0 = time.perf_counter()
        try:
            r = subprocess.run([sys.executable, script], env=env,
                               capture_output=True, text=True,
                               timeout=DEADLINE)
        except subprocess.TimeoutExpired as exc:
            # Chip time is budgeted: salvage the points the arm DID
            # measure before the deadline.
            out = exc.stdout
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            got = _emit(arm, out)
            print(json.dumps({"metric": f"ab_{arm}_timeout", "value": 1,
                              "unit": (f"arm exceeded {DEADLINE:.0f}s; "
                                       f"{got} points salvaged")}))
            continue
        dt = time.perf_counter() - t0
        if r.returncode != 0:
            _emit(arm, r.stdout)  # salvage completed points here too
            tail = (r.stderr or "").strip().splitlines()[-2:]
            print(json.dumps({"metric": f"ab_{arm}_failed",
                              "value": r.returncode,
                              "unit": " | ".join(tail)[:200]}))
            continue
        _emit(arm, r.stdout)
        print(json.dumps({"metric": f"ab_{arm}_wall_s",
                          "value": round(dt, 1), "unit": "s"}))


if __name__ == "__main__":
    main()
