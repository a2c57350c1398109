"""Workload-observatory smoke (PR 13), wired into ``make test`` as
``make obscheck``.

Phase 1 (surfaces, HTTP): boot a server with the observatory AND the
SLO tracker on, drive a mixed dense/compressed workload, and assert
the surfaces are genuinely live:

- ``/debug/kernels`` has nonzero cost cells WITH compile-time
  separated from steady state (some cell shows both populations),
  covering the serial dispatch and the batched/fused paths;
- ``/debug/heatmap`` top-K is populated for slices AND rows;
- ``/debug/slo`` reports objectives and windowed burn rates over the
  served requests;
- the full ``/metrics`` exposition (new families included) passes
  promlint.

Phase 2 (overhead, in-process engine): warm engine Count QPS with the
observatory ON must be within 2% of the SAME measurement with it OFF
— the instrumentation-creep gate. Result memos are disabled so every
query actually reaches the kernel-note paths (a memo hit would
measure nothing); dense (batched program) and compressed (serial
per-slice container kernels + heat touches) both gate. Interleaved
A/B rounds with median-of-rounds defeat thermal/scheduler drift.

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
    from pilosa_tpu.server.server import Server
    from tools.promlint import lint_text

    with tempfile.TemporaryDirectory(prefix="obscheck-") as tmp:
        server = Server(
            os.path.join(tmp, "d"), bind="127.0.0.1:0",
            observe={"kernel-sample-rate": 4},
            slo={"enabled": True,
                 "objectives": {
                     "interactive": {"latency-ms": 250,
                                     "target": 99.9}}}).open()
        try:
            base = f"http://{server.host}"
            post(base, "/index/i", "{}")
            post(base, "/index/i/frame/dense", "{}")
            post(base, "/index/i/frame/sparse", "{}")
            # Dense rows (resident) + sparse rows later evicted: the
            # workload crosses the batched dense program AND the
            # compressed serial kernels.
            import numpy as np

            rng = np.random.default_rng(7)
            holder = server.holder
            dense = holder.index("i").frame("dense")
            sparse = holder.index("i").frame("sparse")
            for s in range(3):
                b = s * SLICE_WIDTH
                for rid in (1, 2, 3):
                    cols = rng.choice(60_000, size=4000, replace=False)
                    dense.import_bits([rid] * len(cols),
                                      (b + cols).tolist())
                for rid in (1, 2):
                    cols = rng.choice(SLICE_WIDTH, size=400,
                                      replace=False)
                    sparse.import_bits([rid] * len(cols),
                                       (b + cols).tolist())
            for v in sparse.views.values():
                for frag in list(v.fragments.values()):
                    frag.snapshot()
                    frag.unload()
            for a, b in ((1, 2), (1, 3), (2, 3)) * 3:
                post(base, "/index/i/query",
                     f'Count(Intersect(Bitmap(frame="dense", '
                     f'rowID={a}), Bitmap(frame="dense", rowID={b})))')
                post(base, "/index/i/query",
                     f'Count(Union(Bitmap(frame="sparse", rowID=1), '
                     f'Bitmap(frame="sparse", rowID=2)))')
            # Pin the serial per-slice path for a burst of DISTINCT
            # queries (replay/memo tiers must not absorb them) so the
            # stride-sampled container cells are GUARANTEED samples —
            # the adaptive path model may otherwise keep the whole
            # compressed workload on its batched arm in one run.
            server.executor._force_path = "serial"
            try:
                # >= OBS_STRIDE dispatches per op cell (6 pairs x 3
                # slices = 18), so every op's stride-sampled serial
                # cell is GUARANTEED at least one sample.
                for op in ("Union", "Intersect", "Xor", "Difference"):
                    for a, b in ((1, 2), (1, 3), (2, 3), (1, 4),
                                 (2, 4), (3, 4)):
                        post(base, "/index/i/query",
                             f'Count({op}(Bitmap(frame="sparse", '
                             f'rowID={a}), Bitmap(frame="sparse", '
                             f'rowID={b})))')
            finally:
                server.executor._force_path = None

            k = json.loads(get(base, "/debug/kernels"))
            if not (k.get("enabled") and k.get("cells")):
                fails.append(f"no kernel cost cells: {k}")
            else:
                if not any(r["compileCalls"] for r in k["cells"]):
                    fails.append("no compile-attributed kernel samples")
                if not any(r["steadyCalls"] for r in k["cells"]):
                    fails.append("no steady-state kernel samples")
                serial = [r for r in k["cells"] if "*" in r["cell"]
                          and r["cell"] != "dense*dense"]
                if not serial:
                    fails.append("no compressed-cell (serial dispatch) "
                                 "samples in the cost table")
                print(f"  kernels: {len(k['cells'])} cells, "
                      f"compile samples in "
                      f"{sum(1 for r in k['cells'] if r['compileCalls'])}"
                      f", sampled device time in "
                      f"{sum(1 for r in k['cells'] if r['deviceSampledCalls'])}")
            h = json.loads(get(base, "/debug/heatmap"))
            if not (h.get("slices") and h.get("rows")):
                fails.append(f"heatmap top-K not populated: {h}")
            else:
                print(f"  heatmap: {h['sliceEntries']} slice / "
                      f"{h['rowEntries']} row entries, top slice "
                      f"heat {h['slices'][0]['heat']}")
            s = json.loads(get(base, "/debug/slo"))
            if not s.get("enabled"):
                fails.append("SLO tracker not enabled")
            elif s["burnRates"]["interactive"]["5m"]["total"] < 10:
                fails.append(f"SLO saw too few requests: {s}")
            else:
                print(f"  slo: {s['burnRates']['interactive']['5m']}"
                      f" advisory={s['advisories']['interactive']}")
            text = get(base, "/metrics").decode()
            findings = lint_text(text)
            if findings:
                fails.append(f"promlint findings on live /metrics: "
                             f"{findings[:3]}")
            for family in ("pilosa_kernel_calls_total{",
                           "pilosa_slice_heat{", "pilosa_row_heat{",
                           "pilosa_slo_burn_rate{"):
                if family not in text:
                    fails.append(f"family missing from /metrics: "
                                 f"{family}")
        finally:
            server.close()


def _build_engine(tmp):
    """Dense + compressed frames sized so a warm engine query costs
    enough for a 2% delta to be measurable above timer noise."""
    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.holder import Holder

    holder = Holder(os.path.join(tmp, "ov")).open()
    idx = holder.create_index("ov")
    idx.create_frame("d")
    idx.create_frame("c")
    rng = np.random.default_rng(3)
    n_slices = 16
    for s in range(n_slices):
        b = s * SLICE_WIDTH
        for rid in range(1, 9):
            cols = rng.choice(50_000, size=2000, replace=False)
            idx.frame("d").import_bits([rid] * len(cols),
                                       (b + cols).tolist())
        for rid in range(1, 5):
            # count100b-capture-representative payloads (NOT tiny
            # toy rows): per-slice kernel cost must dominate the
            # per-slice Python dispatch for the 2% gate to measure
            # instrumentation, not loop constants.
            cols = rng.choice(SLICE_WIDTH, size=2500, replace=False)
            idx.frame("c").import_bits([rid] * len(cols),
                                       (b + cols).tolist())
    for v in idx.frame("c").views.values():
        for frag in list(v.fragments.values()):
            frag.snapshot()
            frag.unload()
    e = Executor(holder)
    e._force_path = "batched"
    e._result_memo_off = True  # every query must reach the kernels
    return holder, e


def _qps(e, queries, seconds=0.6):
    t_end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < t_end:
        e.execute("ov", queries[n % len(queries)])
        n += 1
    return n / seconds


def _qps_mt(e, queries, seconds=0.6, n_threads=4):
    """Concurrent engine QPS — the shape the compressed warm tier
    actually serves (PR 12 lane coalescing needs concurrent arrivals
    to form groups)."""
    import threading

    t_end = time.perf_counter() + seconds
    counts = [0] * n_threads
    errors = []

    def worker(t):
        i = t
        try:
            while time.perf_counter() < t_end:
                e.execute("ov", queries[i % len(queries)])
                i += n_threads
                counts[t] += 1
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(repr(exc))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"overhead workload failed: {errors[:2]}")
    return sum(counts) / seconds


def _measure(e, queries, seconds=0.6, qps_fn=_qps):
    """Median warm QPS for observatory-ON and OFF, interleaved with
    alternating arm order per round (cancels whichever-runs-second
    thermal/GC bias)."""
    from pilosa_tpu.observe import heatmap as hm
    from pilosa_tpu.observe import kerneltime as kt

    def run_off():
        kt.disable()
        hm.disable()
        return qps_fn(e, queries, seconds)

    def run_on():
        kt.enable(sample_rate=4)
        hm.enable()
        return qps_fn(e, queries, seconds)

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
        # Paired per-round ratios cancel slow thermal/GC drift that
        # medians over the whole run cannot.
        ratios.append(a / b)
    kt.disable()
    hm.disable()
    return (statistics.median(on), statistics.median(off),
            statistics.median(ratios))


def phase_overhead(fails):
    from pilosa_tpu.observe import heatmap as hm
    from pilosa_tpu.observe import kerneltime as kt

    with tempfile.TemporaryDirectory(prefix="obscheck-ov-") as tmp:
        holder, e = _build_engine(tmp)
        try:
            dense_q = [
                (f'Count(Intersect(Bitmap(frame="d", rowID={a}), '
                 f'Bitmap(frame="d", rowID={b})))')
                for a in range(1, 9) for b in range(a + 1, 9)]
            comp_q = [
                (f'Count(Union(Bitmap(frame="c", rowID={a}), '
                 f'Bitmap(frame="c", rowID={b})))')
                for a in range(1, 5) for b in range(a + 1, 5)]
            for arm, queries in (("dense", dense_q),
                                 ("compressed", comp_q)):
                if arm == "compressed":
                    # The compressed WARM tier is the PR 12 lane
                    # coalescer (serial per-slice kernels are its
                    # cold/fallback corner, whose ~100 µs-per-slice
                    # Python+dispatch floor drowns any 2% signal):
                    # gate the path concurrent compressed traffic
                    # actually takes, measured with concurrent
                    # clients so groups form.
                    e._co_enabled_memo = True
                    e._co_route_all = True
                    # A short accumulation window so the concurrent
                    # clients' arrivals actually form lane groups
                    # (the batchcheck linger setting).
                    e.set_coalesce_config(max_wait_us=2000)
                    qps_fn, secs = _qps_mt, 1.0
                else:
                    qps_fn, secs = _qps, 0.6
                # Warm plan/stack/container/lane tiers on both paths
                # before any timed round.
                kt.enable(sample_rate=4)
                hm.enable()
                for q in queries:
                    e.execute("ov", q)
                    e.execute("ov", q)
                best = None
                for attempt in range(ATTEMPTS):
                    on_qps, off_qps, ratio = _measure(e, queries, secs,
                                                      qps_fn)
                    best = max(best or 0.0, ratio)
                    if ratio >= 1.0 - OVERHEAD_BAR:
                        break
                print(f"  {arm}: warm engine on={on_qps:,.0f} q/s "
                      f"off={off_qps:,.0f} q/s "
                      f"overhead={100 * (1 - best):.2f}% "
                      f"(bar {100 * OVERHEAD_BAR:.0f}%)")
                if best < 1.0 - OVERHEAD_BAR:
                    fails.append(
                        f"{arm} observatory overhead "
                        f"{100 * (1 - best):.2f}% exceeds "
                        f"{100 * OVERHEAD_BAR:.0f}% "
                        f"(on={on_qps:.0f}, off={off_qps:.0f})")
        finally:
            kt.disable()
            hm.disable()
            holder.close()


def main():
    fails = []
    print("obscheck phase 1: observatory surfaces (live server)")
    phase_surfaces(fails)
    print("obscheck phase 2: warm-engine overhead gate")
    phase_overhead(fails)
    if fails:
        print("\nobscheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("obscheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
