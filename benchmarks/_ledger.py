"""Shared perf-regression ledger writer: one schema'd JSONL row per
benchmark metric, appended to ``benchmarks/ledger.jsonl``. (The repo
root's ``PERF_LEDGER.jsonl`` is the PR driver's record and is never
written from here.)

This is the machine record ``tools/perfwatch.py`` gates on:
append-only rows with enough context (backend, commit, knobs) that a
number from an earlier run is comparable to today's, or provably not
(different backend, different knobs: different baseline group).

Row schema (validate_row enforces it; perfwatch skips invalid rows
rather than crashing on a hand-edited ledger):

    {"t": "2026-08-07T12:00:00Z",   # UTC capture time
     "bench":   "count10b",          # benchmark program
     "metric":  "warm_engine_qps",   # metric name within the bench
     "value":   27000.0,             # numeric sample
     "unit":    "q/s ...",           # human unit string
     "backend": "cpu",               # jax.default_backend() or "unknown"
     "commit":  "83f3f35",           # git HEAD at capture (or null)
     "knobs":   {...}}               # optional dict of relevant knobs

Everything is best-effort by design: a benchmark must never fail
because the ledger directory is read-only or git is absent —
``record*`` swallow OSErrors and return what they wrote (or None).
"""
import json
import os
import subprocess
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TS_FMT = "%Y-%m-%dT%H:%M:%SZ"

REQUIRED = ("t", "bench", "metric", "value", "unit", "backend")
OPTIONAL = ("commit", "knobs")

_commit_cache = []  # [value] once resolved (None is a valid answer)


def ledger_path():
    """benchmarks/ledger.jsonl, or wherever ``PILOSA_PERF_LEDGER``
    points (tests, alternate checkouts)."""
    return (os.environ.get("PILOSA_PERF_LEDGER")
            or os.path.join(HERE, "ledger.jsonl"))


def current_backend():
    """jax.default_backend() when the caller already loaded jax (every
    bench that measured something did); "unknown" otherwise. A parent
    that only launches children must not take the chip by asking."""
    import sys

    if "jax" not in sys.modules:
        return "unknown"
    try:
        return str(sys.modules["jax"].default_backend())
    except Exception:  # noqa: BLE001 — gated dep / broken backend
        return "unknown"


def current_commit():
    """Short git HEAD, cached per process; None when unavailable."""
    if _commit_cache:
        return _commit_cache[0]
    commit = None
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           cwd=ROOT, capture_output=True, text=True,
                           timeout=10)
        if r.returncode == 0:
            commit = r.stdout.strip() or None
    except (OSError, subprocess.TimeoutExpired):
        pass
    _commit_cache.append(commit)
    return commit


def make_row(bench, metric, value, unit, backend=None, knobs=None,
             t=None, commit=None):
    row = {
        "t": t or time.strftime(TS_FMT, time.gmtime()),
        "bench": str(bench),
        "metric": str(metric),
        "value": float(value),
        "unit": str(unit),
        "backend": backend or current_backend(),
    }
    row["commit"] = commit if commit is not None else current_commit()
    if knobs:
        row["knobs"] = dict(knobs)
    return row


def validate_row(row):
    """-> list of schema problems (empty = valid)."""
    problems = []
    if not isinstance(row, dict):
        return [f"row is not an object: {type(row).__name__}"]
    for key in REQUIRED:
        if key not in row:
            problems.append(f"missing required key {key!r}")
    for key in ("bench", "metric", "unit", "backend"):
        if key in row and (not isinstance(row[key], str)
                           or not row[key]):
            problems.append(f"{key!r} must be a non-empty string")
    if "value" in row and not isinstance(row["value"], (int, float)):
        problems.append("'value' must be numeric")
    if "knobs" in row and not isinstance(row["knobs"], dict):
        problems.append("'knobs' must be an object")
    if "commit" in row and row["commit"] is not None \
            and not isinstance(row["commit"], str):
        problems.append("'commit' must be a string or null")
    unknown = set(row) - set(REQUIRED) - set(OPTIONAL)
    if unknown:
        problems.append(f"unknown key(s): {sorted(unknown)}")
    return problems


def record(bench, metric, value, unit, backend=None, knobs=None,
           path=None):
    """Append one row; returns the row written, or None when the
    value is non-numeric or the append failed (best-effort — a
    benchmark must never die on its ledger)."""
    try:
        row = make_row(bench, metric, value, unit, backend=backend,
                       knobs=knobs)
    except (TypeError, ValueError):
        return None
    try:
        with open(path or ledger_path(), "a", encoding="utf-8") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except OSError:
        return None
    return row


def record_rows(bench, rows, backend=None, knobs=None, path=None):
    """Append many ``{"metric", "value", "unit"}`` dicts under one
    bench name; returns the count written."""
    n = 0
    for r in rows:
        try:
            metric, value, unit = r["metric"], r["value"], r["unit"]
        except (KeyError, TypeError):
            continue
        if record(bench, metric, value, unit, backend=backend,
                  knobs=knobs, path=path) is not None:
            n += 1
    return n


def read_rows(path=None):
    """Valid ledger rows in file order; malformed lines and
    schema-invalid rows are skipped (counted in the second return
    value) — perfwatch's loader."""
    rows, skipped = [], 0
    try:
        with open(path or ledger_path(), encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return [], 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            skipped += 1
            continue
        if validate_row(row):
            skipped += 1
            continue
        rows.append(row)
    return rows, skipped
