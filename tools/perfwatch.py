"""Perf-regression gate over the benchmarks/ledger.jsonl ledger
(`make perfwatch` / `python tools/perfwatch.py`).

For every (bench, metric, backend) group with enough history, the
LATEST row is compared against a trailing baseline and the run fails
on any regression beyond tolerance — the mechanical answer to the
ROADMAP "instrumentation creep" worry: a PR that silently slows a
recorded benchmark turns red here instead of three rounds later.

Noise discipline (the obscheck method, translated to offline rows):

- the baseline is the MEDIAN of the trailing window (last
  ``WINDOW`` rows before the latest) — one hot-box outlier round
  cannot set the bar;
- the group's own dispersion widens the tolerance: effective
  tolerance is ``max(DEFAULT_TOLERANCE, NOISE_MULT * MAD/median)``, so
  a metric that historically swings 20% between healthy runs does
  not false-positive at the 30% default while a 2%-stable metric
  still gates at its floor;
- groups with fewer than ``MIN_BASELINE`` trailing rows are reported
  as "no baseline yet" and never fail — the ledger earns trust by
  accumulating, not by assuming.

Direction comes from the metric: throughput-like names/units (qps,
q/s, rate, hit fraction) regress DOWNWARD; time/size-like (seconds,
ms, bytes, p99) regress UPWARD. Unknown units gate both directions.

Deterministic by construction: the same ledger produces the same
verdict, so an unmodified re-run after a green pass stays green.
Exit 1 on any regression; 0 otherwise (including an absent ledger —
the gate activates once benchmarks record).
"""
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import _ledger  # noqa: E402 — benchmarks/_ledger.py (path above)

DEFAULT_TOLERANCE = 0.30   # fractional regression beyond which we fail
WINDOW = 8                 # trailing rows forming the baseline median
MIN_BASELINE = 3           # rows required before a group gates
NOISE_MULT = 3.0           # tolerance floor vs the group's own MAD

_LOWER_BETTER_TOKENS = ("seconds", "_ms", "latency", "p50", "p99",
                        "_s", "bytes", "build_s", "duration")
_HIGHER_BETTER_TOKENS = ("qps", "q/s", "rate", "hit", "rps",
                         "per_sec", "throughput", "x_speedup",
                         "speedup")


def direction(metric, unit):
    """'higher' | 'lower' | 'both' — which way this metric is allowed
    to move without being a regression."""
    text = f"{metric} {unit}".lower()
    if any(tok in text for tok in _HIGHER_BETTER_TOKENS):
        return "higher"
    if any(tok in text for tok in _LOWER_BETTER_TOKENS):
        return "lower"
    return "both"


def _mad_ratio(values, med):
    """Median-absolute-deviation as a fraction of the median — the
    group's own noise level."""
    if not values or not med:
        return 0.0
    mad = statistics.median([abs(v - med) for v in values])
    return abs(mad / med)


def check(rows):
    """-> (findings, report_lines). ``findings`` non-empty = fail."""
    groups = {}
    for row in rows:
        key = (row["bench"], row["metric"], row["backend"])
        groups.setdefault(key, []).append(row)
    findings, report = [], []
    for key in sorted(groups):
        bench, metric, backend = key
        series = groups[key]
        latest = series[-1]
        trailing = [r["value"] for r in series[:-1]][-WINDOW:]
        label = f"{bench}/{metric}[{backend}]"
        if len(trailing) < MIN_BASELINE:
            report.append(f"  {label}: {len(trailing)} trailing "
                          f"row(s) — no baseline yet")
            continue
        base = statistics.median(trailing)
        if base == 0:
            report.append(f"  {label}: baseline is 0 — skipped")
            continue
        tol = max(DEFAULT_TOLERANCE,
                  NOISE_MULT * _mad_ratio(trailing, base))
        d = direction(metric, latest.get("unit", ""))
        value = latest["value"]
        delta = (value - base) / abs(base)
        regressed = ((d in ("higher", "both") and delta < -tol)
                     or (d in ("lower", "both") and delta > tol))
        verdict = "REGRESSION" if regressed else "ok"
        report.append(
            f"  {label}: latest={value:g} baseline={base:g} "
            f"delta={delta:+.1%} tol=±{tol:.0%} dir={d} "
            f"commit={latest.get('commit')} -> {verdict}")
        if regressed:
            findings.append(
                f"{label}: {value:g} vs baseline {base:g} "
                f"({delta:+.1%}, tolerance {tol:.0%}, "
                f"direction {d}, commit {latest.get('commit')})")
    return findings, report


def main(argv=None):
    args = argv if argv is not None else sys.argv[1:]
    path = args[0] if args else _ledger.ledger_path()
    rows, skipped = _ledger.read_rows(path)
    if not rows:
        print(f"perfwatch: no ledger rows at {path} — nothing to "
              f"gate yet: ok")
        return 0
    print(f"perfwatch: {len(rows)} row(s) from {path}"
          + (f" ({skipped} skipped: malformed/invalid)" if skipped
             else ""))
    findings, report = check(rows)
    for line in report:
        print(line)
    if findings:
        print("\nperfwatch: FAIL")
        for f in findings:
            print(f"  - {f}")
        return 1
    print("perfwatch: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
