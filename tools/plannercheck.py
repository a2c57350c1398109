"""Adaptive-planner smoke (PR 20), wired into ``make test`` as
``make plannercheck``.

Phase 1 (live server): the PQL surface — boolean chains (Intersect /
Union / Difference / Xor, nested), TopN, BSI Range/Sum, time-quantum
Ranges, dense and compressed shapes — must be BIT-EXACT planner-on vs
planner-off on the same engine.

Phase 2 (explain): ``?explain=true`` on a worst-case-ordered chain
must show the reordered operand order (most selective first) and the
tier decision's cost rationale; with the coalesced tier eligible, at
least one workload's chosen tier must DIVERGE from the static chain
(``override: true``) with the predicted margin visible, and the warm
serve must attribute ``servedBy: serial`` with the
``coalesced_dense:planner`` hop in the fallback chain.

Phase 3 (short-circuit): a statically-empty operand must serve the
whole Count at plan time — ``servedBy: {planner: 1}``, zero slices,
zero container blocks — and a runtime-killed Intersect branch must
leave its remaining siblings' containers unfetched (the ?profile=true
block counters prove it).

Phase 4 (exposition): /metrics promlint-clean both ways with the
``pilosa_plan_*`` planner families live.

What planning costs an already-optimal query is not measured here: a
timing from this sandbox's CPU backend is not a speed (see PERF.md).
"""
import json
import os
import sys
import tempfile
import urllib.request
from datetime import datetime

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


from pilosa_tpu import SLICE_WIDTH  # noqa: E402

ATTEMPTS = 3
N_SLICES = 4

FAILURES = []


def check(ok, msg):
    tag = "PASS" if ok else "FAIL"
    print(f"[plannercheck] {tag}: {msg}")
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


def seed(base, holder):
    import numpy as np

    post(base, "/index/p", "{}")
    post(base, "/index/p/frame/f", "{}")
    post(base, "/index/p/frame/d", "{}")
    post(base, "/index/p/frame/b", json.dumps({"options": {
        "rangeEnabled": True,
        "fields": [{"name": "v", "min": 0, "max": 1000}]}}))
    post(base, "/index/p/frame/t", json.dumps({"options": {
        "timeQuantum": "YMD"}}))
    rng = np.random.default_rng(11)
    idx = holder.index("p")
    # f: the compressed worst-case shape — rows 1-5 spread-sparse,
    # row 8 tiny, row 9 never set; snapshotted + evicted so serving
    # runs the container kernels the short-circuit pass engages for.
    for s in range(N_SLICES):
        b = s * SLICE_WIDTH
        rows, cols = [], []
        for rid in (1, 2, 3, 4, 5):
            c = rng.choice(SLICE_WIDTH, size=400, replace=False)
            rows.extend([rid] * len(c))
            cols.extend((b + c).tolist())
        c = rng.choice(SLICE_WIDTH, size=6, replace=False)
        rows.extend([8] * len(c))
        cols.extend((b + c).tolist())
        idx.frame("f").import_bits(rows, cols)
        frag = holder.fragment("p", "f", "standard", s)
        frag.snapshot()
        frag.unload()
    # d: dense rows (batched tier).
    for s in range(2):
        b = s * SLICE_WIDTH
        for rid in (1, 2):
            c = rng.choice(60_000, size=4000, replace=False) + b
            idx.frame("d").import_bits([rid] * len(c), c.tolist())
    # b: BSI values on columns row 1 of f also hits.
    for col in range(0, 400):
        idx.frame("b").set_field_value(col, "v", int(col % 900))
    # t: time-quantum views, row 1 across June days on 2 slices.
    fr_t = idx.frame("t")
    for day in range(1, 13):
        t = datetime(2017, 6, day)
        c = rng.choice(2 * SLICE_WIDTH, size=30, replace=False)
        for col in c.tolist():
            fr_t.set_bit("standard", 1, col, t=t)


Q_WORST = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
           'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=3), '
           'Bitmap(frame="f", rowID=4), Bitmap(frame="f", rowID=5), '
           'Bitmap(frame="f", rowID=9)))')
Q_KILLED = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
            'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=9)))')
Q_STATIC = ('Count(Intersect(Bitmap(frame="f", rowID=1), '
            'Range(frame="b", v > 100000)))')
Q_DENSE = ('Count(Intersect(Bitmap(frame="d", rowID=1), '
           'Bitmap(frame="d", rowID=2)))')

# The bit-exact sweep: every result-shape the planner's rewrite or
# tier decision could touch, plus the surfaces it must leave alone.
SURFACE = [
    Q_WORST,
    Q_KILLED,
    Q_STATIC,
    Q_DENSE,
    ('Count(Intersect(Bitmap(frame="f", rowID=1), '
     'Bitmap(frame="f", rowID=2), Bitmap(frame="f", rowID=8)))'),
    ('Count(Union(Bitmap(frame="f", rowID=8), '
     'Bitmap(frame="f", rowID=1), Range(frame="b", v > 100000)))'),
    'Count(Difference(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))',
    'Count(Xor(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))',
    ('Count(Intersect(Union(Bitmap(frame="f", rowID=1), '
     'Bitmap(frame="f", rowID=8)), Bitmap(frame="f", rowID=2), '
     'Bitmap(frame="f", rowID=3)))'),
    'Bitmap(frame="f", rowID=8)',
    ('Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2), '
     'Bitmap(frame="f", rowID=9))'),
    'TopN(frame="f", n=3)',
    'TopN(Bitmap(frame="f", rowID=1), frame="f", n=2)',
    'Count(Range(frame="b", v > 10))',
    'Sum(frame="b", field="v")',
    'Sum(Bitmap(frame="b", rowID=1), frame="b", field="v")',
    ('Count(Range(frame="t", rowID=1, start="2017-06-02T00:00", '
     'end="2017-06-10T00:00"))'),
    ('Count(Union(Range(frame="t", rowID=1, start="2017-06-01T00:00", '
     'end="2017-06-05T00:00"), Bitmap(frame="f", rowID=8)))'),
]


def phase_bit_exact(base, server):
    pl = server.executor.planner
    for q in SURFACE:
        on = json.loads(post(base, "/index/p/query", q))
        pl.set_config(enabled=False)
        try:
            off = json.loads(post(base, "/index/p/query", q))
        finally:
            pl.set_config(enabled=True)
        check(on == off, f"bit-exact planner on/off: {q[:64]}")


def phase_explain(base, server):
    # --- reordered plan: the empty operand written LAST sorts FIRST,
    # and the whole chain is statically servable to zero.
    doc = json.loads(post(base, "/index/p/query?explain=true", Q_WORST))
    blk = (doc.get("explain") or {}).get("calls", [{}])[0].get(
        "planner") or {}
    check(blk.get("planned") is True and blk.get("reordered") is True,
          f"worst-case chain planned + reordered (got {blk})")
    order = blk.get("order") or []
    check(bool(order) and "rowID=9" in order[0],
          f"empty operand sorted first (order {order[:2]})")
    check(isinstance(blk.get("estimatedCards"), dict)
          and len(blk["estimatedCards"]) >= 2,
          "estimated cardinalities rendered per operand")
    check(doc["results"] == [0], "worst-case chain counts 0")

    # --- tier rationale on a shape with a real candidate set.
    for _ in range(4):
        post(base, "/index/p/query", Q_DENSE)
    doc = json.loads(post(base, "/index/p/query?explain=true", Q_DENSE))
    tier = ((doc.get("explain") or {}).get("calls", [{}])[0]
            .get("planner") or {}).get("tier") or {}
    check(tier.get("static") in ("batched", "serial"),
          f"dense chain reports the static tier ({tier.get('static')})")
    check(isinstance(tier.get("rationale"), str) and tier["rationale"],
          f"tier rationale rendered ({tier.get('rationale')!r})")

    # --- tier divergence: with the coalesced tier eligible, the deep
    # compressed short-circuit chain must be routed to serial BY THE
    # MODEL (the cold densify prior), visibly overriding the static
    # chain — and the warm serve must attribute it.
    ex = server.executor
    ex._co_enabled_memo = True
    pl = ex.planner
    pl.set_config()  # version bump: replan with the new candidate set
    try:
        seen = None
        for _attempt in range(ATTEMPTS):
            for _ in range(12):
                post(base, "/index/p/query", Q_KILLED)
            doc = json.loads(post(
                base, "/index/p/query?profile=true&explain=true",
                Q_KILLED))
            blk = (doc.get("explain") or {}).get(
                "calls", [{}])[0].get("planner") or {}
            seen = blk.get("tier") or {}
            if seen.get("override"):
                break
        check(seen.get("override") is True
              and seen.get("chosen") == "serial"
              and seen.get("static") == "coalesced_dense",
              f"tier choice diverges from the static chain ({seen})")
        est = seen.get("estimatedUsByTier") or {}
        check(est.get("serial", 1e9) < est.get("coalesced_dense", 0),
              f"override wins on predicted cost ({est})")
        check("override" in (seen.get("rationale") or ""),
              f"override rationale visible ({seen.get('rationale')!r})")
        res = (doc.get("profile") or {}).get("resources") or {}
        check((res.get("servedBy") or {}).get("serial", 0) >= 1,
              f"warm serve attributes the overridden tier "
              f"({res.get('servedBy')})")
        check(any(h == "coalesced_dense:planner"
                  for h in res.get("fallbackChain") or ()),
              f"planner hop in the fallback chain "
              f"({res.get('fallbackChain')})")
    finally:
        ex._co_enabled_memo = False
        pl.set_config()


def phase_short_circuit(base, server):
    pl = server.executor.planner

    # --- static empty: plan-time zero. No fan-out, no kernel — the
    # profile counters never tick.
    doc = json.loads(post(base, "/index/p/query?profile=true",
                          Q_STATIC))
    res = (doc.get("profile") or {}).get("resources") or {}
    check(doc["results"] == [0], "static-empty chain counts 0")
    check(res.get("servedBy") == {"planner": 1},
          f"static empty served by the planner ({res.get('servedBy')})")
    check(res.get("slices", 0) == 0 and res.get("blocks", 0) == 0,
          f"zero slices / zero container blocks "
          f"(slices={res.get('slices', 0)} blocks={res.get('blocks', 0)})")

    # --- runtime kill: the empty operand sorts first, the running
    # intermediate dies per slice, and the SIBLINGS' containers are
    # never fetched. Planner-off fetches all three operands.
    doc = json.loads(post(base, "/index/p/query?profile=true",
                          Q_KILLED))
    on_blocks = ((doc.get("profile") or {}).get("resources")
                 or {}).get("blocks", 0)
    pl.set_config(enabled=False)
    try:
        doc_off = json.loads(post(base, "/index/p/query?profile=true",
                                  Q_KILLED))
    finally:
        pl.set_config(enabled=True)
    off_blocks = ((doc_off.get("profile") or {}).get("resources")
                  or {}).get("blocks", 0)
    check(doc["results"] == doc_off["results"] == [0],
          "killed chain counts 0 both ways")
    check(on_blocks <= N_SLICES,
          f"killed branch fetches only the empty operand "
          f"({on_blocks} blocks <= {N_SLICES} slices)")
    check(off_blocks >= 3 * N_SLICES and off_blocks > 2 * on_blocks,
          f"planner-off fetches every operand "
          f"(off={off_blocks} on={on_blocks})")


def phase_metrics(base, server):
    from tools.promlint import lint_text

    pl = server.executor.planner
    text = req(base, "GET", "/metrics").decode()
    findings = lint_text(text)
    check(not findings,
          f"promlint clean planner-on "
          f"({findings[:2] if findings else 'ok'})")
    for family in ("pilosa_plan_reorder_total",
                   "pilosa_plan_shortcircuit_total",
                   "pilosa_plan_tier_override_total"):
        check(family in text, f"{family} live on /metrics")
    check('pilosa_plan_shortcircuit_total{kind="intersect_empty"}'
          in text, "short-circuit kind-tagged child live")
    pl.set_config(enabled=False)
    try:
        text = req(base, "GET", "/metrics").decode()
        findings = lint_text(text)
        check(not findings,
              f"promlint clean planner-off "
              f"({findings[:2] if findings else 'ok'})")
    finally:
        pl.set_config(enabled=True)


def main():
    from pilosa_tpu.server.server import Server

    print("plannercheck phase 1: bit-exact planner on vs off "
          "(live server)")
    with tempfile.TemporaryDirectory(prefix="plannercheck-") as tmp:
        server = Server(os.path.join(tmp, "d"), bind="127.0.0.1:0",
                        observe={"kernel-sample-rate": 4}).open()
        try:
            base = f"http://{server.host}"
            seed(base, server.holder)
            # Replay tiers off so every driven query genuinely takes
            # the planning decision under test.
            server.executor._result_memo_off = True
            server.handler._resp_cache = None

            phase_bit_exact(base, server)
            print("plannercheck phase 2: explain surface")
            phase_explain(base, server)
            print("plannercheck phase 3: short-circuit counters")
            phase_short_circuit(base, server)
            print("plannercheck phase 4: exposition")
            phase_metrics(base, server)
        finally:
            server.close()
    if FAILURES:
        print("\nplannercheck: FAIL")
        for f in FAILURES:
            print(f"  - {f}")
        return 1
    print("plannercheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
