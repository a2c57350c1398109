"""Workload-observatory smoke (PR 13), wired into ``make test`` as
``make obscheck``.

Boot a server with the observatory AND the SLO tracker on, drive a
mixed dense/compressed workload over HTTP, and assert the surfaces
are genuinely live:

- ``/debug/kernels`` has nonzero cost cells WITH compile-time
  separated from steady state (some cell shows both populations),
  covering the serial dispatch and the batched/fused paths;
- ``/debug/heatmap`` top-K is populated for slices AND rows;
- ``/debug/slo`` reports objectives and windowed burn rates over the
  served requests;
- the full ``/metrics`` exposition (new families included) passes
  promlint.

What the observatory costs a request is not measured here: a timing
from this sandbox's CPU backend is not a speed (see PERF.md).

Small and CPU-only by design.
"""
import json
import os
import sys
import tempfile
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


def main():
    fails = []
    print("obscheck: observatory surfaces (live server)")
    phase_surfaces(fails)
    if fails:
        print("\nobscheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("obscheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
