#!/usr/bin/env python3
"""perfbench: one run of one cell of BENCHMARK.json on the served path.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that never imports JAX. It builds the native runtime, starts
one ``python -m pilosa_tpu.cli server`` child that owns the cell's chips,
loads the configuration's data from ``--seed`` over HTTP, stages and warms
the cell's shapes (all of that is ``setup_s``), drives the cell's traffic
at ``POST /index/<index>/query`` for ``--seconds``, drains the child, then
compares every answer of the window with the plain NumPy reference and
prints one JSON line. What belongs to one cell, configuration, traffic mix,
generator, reference or metric is a file of its own, found by the name the
JSON gives; this file names none of them.

``--rehearse`` runs the same control flow at the tiny sizes of
``perfbench/rehearsal/<configuration>.json`` on whatever platform the
server's JAX reports (the CPU here), and prints no metric.
"""
import argparse
import importlib
import importlib.util
import json
import math
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from types import SimpleNamespace as Context   # what a metric's reader may read

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench.lib import layer, loadgen, serverproc, xplane  # noqa: E402
from perfbench.lib.serverproc import HarnessFailure, check  # noqa: E402


def read_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_metric(name):
    """The reader of one metric: ``perfbench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench, section, cell):
    """The metrics of one section that this cell reports: those with no
    ``workloads`` key, or with the cell in it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def note(phase, **info):
    print(json.dumps({"phase": phase, **info}), file=sys.stderr, flush=True)


def build_native():
    """Build the native runtime from roaring.cpp where the checkout has
    none or an older one. Built under a name of this process's own and
    installed by rename, so that two runs in one checkout cannot collide
    on the build's temporary file."""
    from pilosa_tpu import native    # the ctypes loader only, no JAX

    so, src = native._SO, native._SRC
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return
    mine = f"{so}.perfbench.{os.getpid()}.so"
    try:
        native.build(mine)
        os.replace(mine, so)
    except RuntimeError as e:
        raise HarnessFailure(str(e))


def warm_up(client, path, traffic, warm):
    """The ladder (each form alone at each group size), then the
    window's own mix on what is left of the reserved queries, then wait
    until nothing has compiled for ``quiet_s`` and the width warmer is
    idle."""
    t0 = time.perf_counter()
    sent = 0

    def play(streams, seconds=3600):
        nonlocal sent
        streams = [[q for q in s if q is not None] for s in streams]
        streams = [s for s in streams if s]
        if not streams:
            return
        log, _ = loadgen.run_closed(client, path, streams, seconds)
        bad = [r for r in log if r["status"] != 200]
        check(not bad, f"warm-up query failed: {bad[:1]}")
        sent += len(log)

    for phase in traffic.ladder(warm["ladder_rounds"]):
        play(phase)
    play(traffic.mixed_warm(), warm["mixed_s"])
    calls, last_change = -1, time.perf_counter()
    while True:
        serverproc.wait_warm_quiet(client)
        now_calls = serverproc.compile_calls(client)[0]
        if now_calls != calls:
            calls, last_change = now_calls, time.perf_counter()
        if time.perf_counter() - last_change >= warm["quiet_s"]:
            break
        check(time.perf_counter() - t0 < warm["max_s"],
              f"compilation never settled in {warm['max_s']} s of warm-up")
        time.sleep(0.25)
    note("warmup", queries=sent, compileCalls=calls,
         seconds=round(time.perf_counter() - t0, 2))


def arm_trace(client, trace_dir, seconds):
    """Arm the server's own bounded device trace; None where the
    backend cannot trace (HTTP 501)."""
    status, body = client.send(
        "POST", f"/debug/profile/device?seconds={seconds}&dir={trace_dir}")
    if status != 200:
        note("trace", armed=False, status=status,
             body=body[:200].decode("utf-8", "replace"))
        return None
    return time.perf_counter()


def collect_trace(trace_dir, t_end):
    """Wait for the profiler to have written its file, then reduce it.
    ``t_end`` is when the capture was due to stop. The server has no way
    to say that a capture is over, and a SIGTERM that finds the profiler
    still tearing down aborts it (exit -6, seen 0.5 s after the file was
    whole): so wait on, twice as long as the stop took to write the
    file, and 3 s at least."""
    deadline = t_end + 120
    path, size = None, -1
    while time.perf_counter() < deadline:
        path = xplane.find_xplane(trace_dir)
        if path:
            now = os.path.getsize(path)
            if now == size and now > 0:
                break
            size = now
        time.sleep(0.5)
    if not path:
        note("trace", file=None)
        return None
    time.sleep(min(30.0, max(3.0, 2 * (time.perf_counter() - t_end))))
    planes = xplane.read_planes(path)
    out = xplane.reduce_device(planes)
    note("trace", file=os.path.relpath(path, ROOT), bytes=size,
         devicePlanes=[p["name"] for p in planes],
         lines=sorted({ln["name"] for p in planes for ln in p["lines"]}),
         busy_s=out and out["busy_s"])
    return out


def verdict(compared, mismatched, failed):
    """``correct``: something was compared, nothing differed, nothing
    failed. The one rule for a run and for its control."""
    return compared > 0 and mismatched == 0 and failed == 0


def judge(reference, picked, want, got):
    """What differs where ``got`` stands in the program's place."""
    return [reference.explain(r["pql"], g, w)
            for r, g, w in zip(picked, got, want) if g != w]


def compare(reference, log, out_dir, sample, seed, control=False):
    """The window's answers against the reference: every one, or where
    the mix states ``compare.sample`` that many drawn from the seed (the
    reference then stays shorter than the window). An answer that was
    not drawn counts as correct; one that never came or did not parse
    is failed whether drawn or not. Returns (the requests compared,
    mismatched, failed) and writes what differs. With ``control`` the control's
    answers are then put in the place of the same requests' and judged
    the same way; a fourth number comes back, how many of them differ."""
    answered = [r for r in log if r["ok"]]
    picked = answered
    if sample and len(answered) > sample:
        picked = random.Random(seed).sample(answered, sample)
    for r in log:
        r["correct"] = r["ok"]
    pqls = [r["pql"] for r in picked]
    want = reference.answers(pqls)
    got = [r["result"] for r in picked]
    wrong = judge(reference, picked, want, got)
    for r, g, w in zip(picked, got, want):
        r["correct"] = g == w
    failed = [r for r in log if not r["ok"]]
    if wrong or failed:
        report = {"mismatched": wrong[:50], "failed": [
            {"query": r["pql"], "status": r["status"],
             "body": r.get("body", b"")[:300].decode("utf-8", "replace")}
            for r in failed[:50]]}
        with open(os.path.join(out_dir, "mismatch.json"), "w") as f:
            json.dump(report, f, indent=1)
        print(json.dumps({"mismatch_report": report})[:6000], flush=True)
    if not control:
        return picked, len(wrong), len(failed)
    stand_in = reference.answers(pqls, control=True)
    return (picked, len(wrong), len(failed),
            len(judge(reference, picked, want, stand_in)))


def dump_requests(log, t_open, out_dir):
    """The window's requests, one line each, for whoever reads a run
    afterwards: when, how long, which form of the mix it was, and in a
    traced run what the server said of it."""
    with open(os.path.join(out_dir, "requests.jsonl"), "w") as f:
        for r in log:
            prof = r.get("profile") or {}
            f.write(json.dumps({
                "client": r["client"], "at_s": r["t0"] - t_open,
                "ms": (r["t1"] - r["t0"]) * 1000.0, "status": r["status"],
                "form": r["pql"].form, "pql": r["pql"],
                "resources": prof.get("resources"),
                "spans": prof.get("spans")}) + "\n")


def breakdown_of(trace, log):
    """Top device operations; and where the time outside them went: the
    device's idle time by the program whose launch ended each gap
    (``before:<program>``, from the trace), and the self time of the
    server's own spans over the profiled requests (``host:<span>``)."""
    host = sorted(layer.self_seconds(log).items(), key=lambda kv: -kv[1])
    gaps = [[f"host:{k}", v] for k, v in host[:5]]
    if not trace:
        return {"device_ops": [], "idle_gaps": gaps}
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1][0])[:10]
    gaps += [[f"before:{k}", v] for k, v in trace["gaps_by_next"][:5]]
    return {"device_ops": [[k[:160], v[0]] for k, v in ops],
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])}


def run(args, out_dir, data_dir):
    bench = read_json(os.pardir, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    check(cell is not None,
          f"BENCHMARK.json lists no workload {args.workload!r}")
    config = read_json("configs", cell["config"] + ".json")
    mix = read_json("traffic", cell["traffic"] + ".json")
    env = dict(config["server"]["env"])
    if args.rehearse:
        tiny = read_json("rehearsal", cell["config"] + ".json")
        config["shape"].update(tiny["shape"])
        env.update(tiny["env"])
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{cell['chips']}")
    gen = importlib.import_module(f"perfbench.datagen.{config['datagen']}")
    ref_mod = importlib.import_module(
        f"perfbench.reference.{config['reference']}")

    build_native()
    server = serverproc.ServerProc(ROOT, data_dir, out_dir, env)
    host = serverproc.HostMemory()
    try:
        client = server.start()
        dev = serverproc.device_block(client)
        note("device", **{k: dev[k] for k in (
            "platform", "deviceKind", "deviceCount", "nativeLoaded",
            "compileCacheDir")}, bootSeconds=round(
                time.perf_counter() - T_START, 2))
        check(args.rehearse or dev["platform"] == "tpu",
              f"the server's platform is {dev['platform']!r}, not 'tpu' "
              "(--rehearse allows a tiny run elsewhere)")
        check(dev["deviceCount"] >= cell["chips"],
              f"the cell asks for {cell['chips']} chips, the server has "
              f"{dev['deviceCount']}")
        check(dev["nativeLoaded"], "nativeLoaded is false")

        data = gen.load(client, config, args.seed, note)
        path = f"/index/{config['shape']['index']}/query"
        t0 = time.perf_counter()
        for q in gen.stage_queries(config):
            client.request("POST", path, q)
        note("stage", seconds=round(time.perf_counter() - t0, 2))
        traffic = loadgen.Traffic(mix, gen.pools(config), args.seed)
        warm_up(client, path, traffic, mix["warmup"])

        before = serverproc.counters(client)
        trace_dir = os.path.join(out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        armed = {}
        trace_s = max(0.5, min(5.0, args.seconds - 2.0))

        def on_open(t_open):
            if args.trace:
                def arm():
                    time.sleep(min(1.0, args.seconds / 4))
                    armed["t"] = arm_trace(client, trace_dir, trace_s)
                    client.close()
                threading.Thread(target=arm, daemon=True).start()

        setup_s = time.perf_counter() - T_START
        log, t_open = loadgen.run_closed(
            client, path + ("?profile=true" if args.trace else ""),
            [traffic.window(k) for k in range(traffic.clients)],
            args.seconds, on_open)
        after = serverproc.counters(client)
        note("window", requests=len(log),
             compiles=after["compileCalls"] - before["compileCalls"],
             compiled={
            k: n - before["compileCells"].get(k, 0)
            for k, n in after["compileCells"].items()
            if n != before["compileCells"].get(k, 0)},
            hbmPeakGB=[round((m or {}).get("peak_bytes_in_use", 0) / 1e9, 3)
                       for m in after["memoryStats"]],
            pathModel=after["pathModel"])
        trace = None
        if args.trace and armed.get("t"):
            trace = collect_trace(trace_dir, armed["t"] + trace_s)
        client.close()
        server.drain()               # the chip and the data are free now
    finally:
        server.kill()

    loadgen.decode(log)
    dump_requests(log, t_open, out_dir)
    t0 = time.perf_counter()
    reference = ref_mod.Reference(config, data)
    picked, mismatched, failed, *control = compare(
        reference, log, out_dir,
        None if args.rehearse else mix.get("compare", {}).get("sample"),
        args.seed, args.control)
    compared = len(picked)
    note("reference", seconds=round(time.perf_counter() - t0, 2),
         answers=len(log), compared=compared,
         hostAvailableLowMB=host.low_mb)

    peaks = [m["peak_bytes_in_use"] for m in after["memoryStats"]
             if m and m.get("peak_bytes_in_use") is not None]
    ctx = Context(log=log, t_open=t_open, seconds=args.seconds,
                  setup_s=setup_s, before=before, after=after, device=dev,
                  trace=trace, trace_t0=armed.get("t"), cell=cell,
                  config=config, mix=mix, memory_peaks=peaks)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, args.workload):
        value = load_metric(m["name"]).read(ctx)
        # A percentile that a failed request pushed to infinity has no
        # number to print; the run is not correct and says so.
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["deviceKind"],
              "count": dev["deviceCount"],
              "memory_peak_bytes": max(peaks) if peaks else None}
    result = {"correct": verdict(compared, mismatched, failed),
              "attempted": len(log), "failed": failed + mismatched}
    if args.rehearse:
        result.update(rehearsal=True, rehearsal_values=metrics, metrics={})
    else:
        result["metrics"] = metrics
    if args.trace:
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        result["breakdown"] = breakdown_of(trace, log)
    result["device"] = device
    result["compared"] = {"mismatched": [mismatched, 0],
                          "failed": [failed, 0],
                          "compared": [compared, "> 0"]}
    if control:
        # The control in the program's place, through the same verdict:
        # it has to come out as not correct.
        result["compared"]["control_mismatched"] = [control[0], "> 0"]
        result["compared"]["control_correct"] = [
            verdict(compared, control[0], failed), False]
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size, any platform; prints no metric")
    ap.add_argument("--control", action="store_true",
                    help="also put the control's answers (the reference in "
                         "the precision below) in the place of the "
                         "window's and judge them the same way; the "
                         "builder's check, not a run's")
    ap.add_argument("--out", default=os.path.join(ROOT, "perfbench_out"),
                    help="directory for the server's log, the trace and "
                         "the mismatch report")
    args = ap.parse_args()
    out_dir = os.path.join(args.out, f"{args.workload}-{args.seed}"
                                     f"-t{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="perfbench_")
    try:
        result = run(args, out_dir, data_dir)
    except HarnessFailure as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    print(json.dumps({"compared": result["compared"]}), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
