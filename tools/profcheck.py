"""Continuous-profiler smoke (PR 19), wired into ``make test`` as
``make profcheck``.

Boot a server with the profiler sampling at 97 Hz (prime — the
anti-phase-lock discipline — and fast enough that a short driven load
yields hundreds of samples) plus the observatory, drive concurrent
query load over HTTP, and assert the surfaces are genuinely live:

- ``GET /debug/profile`` reports samples with at least three
  subsystems nonzero under load (serving + device-dispatch +
  background at minimum);
- ``format=folded`` parses line-for-line as flamegraph folded stacks
  (``subsystem;frame;... count``) with known subsystem roots;
- ``?seconds=`` bounded collection answers from the sample ring;
- ``POST /debug/profile/device`` arms a bounded trace (200), refuses
  a second arm while one is armed (409), or degrades to a clean 501
  where the backend cannot trace — never anything else;
- ``/debug/kernels`` cells carry analytic flops/bytes on the CPU
  backend (the XLA cost_analysis capture), and the live ``/metrics``
  exposition (``pilosa_profile_*`` included) passes promlint.

What the sampler costs a request is not measured here: a timing from
this sandbox's CPU backend is not a speed (see PERF.md).

Small and CPU-only by design.
"""
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402

SAMPLE_HZ = 97               # prime; ~10 ms between sweeps


def post(base, path, body):
    req = urllib.request.Request(f"{base}{path}", data=body.encode(),
                                 method="POST")
    return urllib.request.urlopen(req, timeout=30).read()


def post_status(base, path, body=""):
    """(status, body) — errors returned, not raised (the device
    capture route legitimately answers 409/501)."""
    req = urllib.request.Request(f"{base}{path}", data=body.encode(),
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get(base, path):
    return urllib.request.urlopen(f"{base}{path}", timeout=30).read()


def _drive_load(base, seconds=1.5, n_threads=3):
    """Concurrent mixed queries so the sampler sees serving and
    device-dispatch frames (distinct row pairs defeat the replay
    tiers)."""
    stop = time.perf_counter() + seconds
    errors = []

    def worker(w):
        i = w
        pairs = [(a, b) for a in range(1, 5) for b in range(a + 1, 5)]
        try:
            while time.perf_counter() < stop:
                a, b = pairs[i % len(pairs)]
                post(base, "/index/i/query",
                     f'Count(Intersect(Bitmap(frame="f", rowID={a}), '
                     f'Bitmap(frame="f", rowID={b})))')
                i += n_threads
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(repr(exc)[:200])

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"load workload failed: {errors[:2]}")


def _check_folded(text, fails):
    from pilosa_tpu.observe.profiler import SUBSYSTEMS

    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        fails.append("folded output is empty under load")
        return
    for ln in lines:
        stack, _, count = ln.rpartition(" ")
        if not stack or not count.isdigit() or int(count) < 1:
            fails.append(f"unparseable folded line: {ln!r}")
            return
        sub = stack.split(";", 1)[0]
        if sub not in SUBSYSTEMS:
            fails.append(f"unknown folded subsystem {sub!r}: {ln!r}")
            return
    print(f"  folded: {len(lines)} stacks parse clean")


def phase_surfaces(fails):
    from pilosa_tpu.server.server import Server
    from tools.promlint import lint_text

    with tempfile.TemporaryDirectory(prefix="profcheck-") as tmp:
        server = Server(os.path.join(tmp, "d"), bind="127.0.0.1:0",
                        observe={"kernel-sample-rate": 4},
                        profile={"sample-hz": SAMPLE_HZ}).open()
        try:
            base = f"http://{server.host}"
            post(base, "/index/i", "{}")
            post(base, "/index/i/frame/f", "{}")
            import numpy as np

            rng = np.random.default_rng(7)
            frame = server.holder.index("i").frame("f")
            for s in range(3):
                b = s * SLICE_WIDTH
                for rid in (1, 2, 3, 4):
                    cols = rng.choice(60_000, size=3000, replace=False)
                    frame.import_bits([rid] * len(cols),
                                      (b + cols).tolist())

            # Drive load until >= 3 subsystems have samples (bounded:
            # at 97 Hz a 1.5 s burst yields ~150 sweeps, but a loaded
            # box may need another).
            deadline = time.monotonic() + 20
            snap = {}
            while time.monotonic() < deadline:
                _drive_load(base)
                snap = json.loads(get(base, "/debug/profile"))
                nonzero = [s for s, v in snap.get("subsystems",
                                                  {}).items()
                           if v["samples"] > 0]
                if len(nonzero) >= 3:
                    break
            if not snap.get("enabled"):
                fails.append(f"profiler not enabled: {snap}")
                return
            nonzero = [s for s, v in snap["subsystems"].items()
                       if v["samples"] > 0]
            print(f"  profile: {snap['samples']} samples @ "
                  f"{snap['sampleHz']:g} Hz, subsystems "
                  f"{sorted(nonzero)}, {snap['trieNodes']} trie nodes")
            if len(nonzero) < 3:
                fails.append(f"only {sorted(nonzero)} subsystems "
                             f"sampled under load (need >= 3)")
            if not snap.get("topStacks"):
                fails.append("no top stacks in the profile snapshot")

            _check_folded(
                get(base, "/debug/profile?format=folded").decode(),
                fails)

            win = json.loads(get(base, "/debug/profile?seconds=0.3"))
            if not win.get("enabled") or win.get("seconds", 0) < 0.2:
                fails.append(f"bounded collection did not run: {win}")

            # Device capture: 200 (bounded trace armed; a second arm
            # while armed must 409) or a clean 501 where unsupported.
            trace_dir = os.path.join(tmp, "trace")
            st, body = post_status(
                base, f"/debug/profile/device?seconds=0.3"
                      f"&dir={trace_dir}")
            if st == 200:
                st2, _ = post_status(
                    base, "/debug/profile/device?seconds=0.3")
                if st2 != 409:
                    fails.append(f"second device arm answered {st2}, "
                                 f"not 409")
                time.sleep(0.5)  # watchdog stops the bounded trace
                print("  device capture: armed 200, concurrent arm "
                      "409, watchdog stop")
            elif st == 501:
                print("  device capture: clean 501 (backend cannot "
                      "trace)")
            else:
                fails.append(f"device capture answered {st}: "
                             f"{body[:200]!r}")

            k = json.loads(get(base, "/debug/kernels"))
            analytic = k.get("analytic", {})
            annotated = [r for r in k.get("cells", [])
                         if "analyticFlops" in r]
            if not analytic.get("captured") or not annotated:
                fails.append(f"no analytic flops/bytes on kernel "
                             f"cells: {analytic}, "
                             f"{len(k.get('cells', []))} cells")
            else:
                r = annotated[0]
                print(f"  analytic: {analytic['captured']} cells, "
                      f"e.g. {r['op']}/{r['cell']} flops="
                      f"{r['analyticFlops']:g} bytes="
                      f"{r['analyticBytes']:g}")

            text = get(base, "/metrics").decode()
            findings = lint_text(text)
            if findings:
                fails.append(f"promlint findings on live /metrics: "
                             f"{findings[:3]}")
            for family in ("pilosa_profile_samples_total",
                           "pilosa_profile_sample_hz"):
                if family not in text:
                    fails.append(f"family missing from /metrics: "
                                 f"{family}")
        finally:
            server.close()


def main():
    fails = []
    print(f"profcheck: profiler surfaces (live server, "
          f"{SAMPLE_HZ} Hz)")
    phase_surfaces(fails)
    if fails:
        print("\nprofcheck: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    print("profcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
