"""Slice-plan cache smoke: boot one in-process server, warm the plan
tier with repeated engine-path Counts (response replay detached, so
every query actually executes), and assert:

- a plan-cache hit rate > 90% across the warm run,
- write invalidation is bit-exact (SetBit -> the very next query
  reflects the write; the invalidation counter moved),
- the ops surfaces agree (GET /debug/plans, pilosa_plan_cache_* on
  /metrics), and
- capacity 0 really is OFF (no entries, still correct).

Wired into ``make test`` as ``make plancheck``. Small and CPU-only by
design: one index, a handful of slices, ~a hundred queries.
"""
import json
import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pilosa_tpu import SLICE_WIDTH  # noqa: E402

WARM_QUERIES = 50


def main():
    fails = []
    from pilosa_tpu.server.server import Server

    d = tempfile.mkdtemp(prefix="plancheck_")
    server = Server(os.path.join(d, "data"), bind="localhost:0").open()
    base = f"http://{server.host}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return r.read().decode()

    def post(path, body):
        req = urllib.request.Request(base + path, data=body.encode(),
                                     method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read().decode()

    def count():
        return json.loads(post(
            "/index/i/query",
            'Count(Bitmap(frame="f", rowID=1))'))["results"][0]

    try:
        # Replay OFF: the engine executes every query (what this
        # smoke is checking; the replay tier has warmcheck).
        server.handler._resp_cache = None
        post("/index/i", "{}")
        post("/index/i/frame/f", "{}")
        bits = 0
        for s in range(4):
            post("/index/i/query",
                 f'SetBit(frame="f", rowID=1, '
                 f'columnID={s * SLICE_WIDTH + 1})')
            bits += 1

        plans = server.executor.plans
        if count() != bits:
            fails.append("seed count wrong")
        m0 = plans.metrics()
        for _ in range(WARM_QUERIES):
            if count() != bits:
                fails.append("warm count wrong")
                break
        m1 = plans.metrics()
        dh = m1["hits"] - m0["hits"]
        dm = m1["misses"] - m0["misses"]
        hit_rate = dh / (dh + dm) if dh + dm else 0.0
        if hit_rate <= 0.9:
            fails.append(f"warm hit rate {hit_rate:.3f} <= 0.9")

        # Write invalidation: bit-exact on the very next query, and
        # the invalidation counter moved.
        post("/index/i/query",
             f'SetBit(frame="f", rowID=1, columnID={SLICE_WIDTH + 9})')
        bits += 1
        if count() != bits:
            fails.append("post-write count stale — plan not dropped")
        if plans.metrics()["invalidations"] <= m1["invalidations"]:
            fails.append("write did not invalidate any plan entry")

        # Ops surfaces.
        snap = json.loads(get("/debug/plans"))
        if not snap.get("enabled") or "i" not in snap.get("perIndex", {}):
            fails.append(f"/debug/plans incomplete: {snap}")
        text = get("/metrics")
        for name in ("pilosa_plan_cache_hits", "pilosa_plan_cache_misses",
                     "pilosa_plan_cache_invalidations",
                     "pilosa_plan_cache_entries"):
            if name not in text:
                fails.append(f"{name} missing from /metrics")

        # Off switch: capacity 0 stores nothing, still bit-exact.
        plans.set_capacity(0)
        if count() != bits or count() != bits:
            fails.append("capacity-0 count wrong")
        if plans.metrics()["entries"] != 0:
            fails.append("capacity-0 cache holds entries")
        if not json.loads(get("/debug/plans")).get("enabled") is False:
            fails.append("/debug/plans claims enabled at capacity 0")
    finally:
        server.close()
        import shutil

        shutil.rmtree(d, ignore_errors=True)

    print(json.dumps({"metric": "plancheck",
                      "planHitRate": round(hit_rate, 4),
                      "failures": fails}))
    if fails:
        print("plancheck FAILED", file=sys.stderr)
        return 1
    print(f"plancheck OK: {hit_rate:.1%} warm plan hit rate, "
          "write invalidation bit-exact")
    return 0


if __name__ == "__main__":
    sys.exit(main())
