"""Perf-regression gate over the committed benchmark baselines.

Every smoke benchmark writes a ``BENCH_*.json`` trajectory; this checker
compares each one against the committed copy in ``benchmarks/baselines/``
and fails (exit 1) when any **gated metric** worsens by more than the
threshold (default 25%), printing a diff table of everything it compared.

Gated metrics are dimensionless ratios measured within one process on
one machine (incremental-vs-rebuild speedup, sharded byte fraction,
pause reduction, tail-latency ratio …), so they transfer across hosts in
a way raw milliseconds never could — a laptop baseline still gates a CI
runner.  Raw timings in the same files are reported but not gated.

Direction matters: ``speedup`` regressing means it *dropped*,
``bytes_fraction`` regressing means it *rose*.  Rows are matched by
position within each row list and sanity-checked on their identity keys
(``trees``/``layout``/``devices``/``batch``); a bench whose shape
changed should simply refresh its baseline (see CONTRIBUTING.md):

    PYTHONPATH=src python -m benchmarks.bench_<name> --smoke \
        --json benchmarks/baselines/BENCH_<name>.json

Usage (CI runs this from the repo root after the smoke benches):

    python -m benchmarks.check_regression [--current DIR] \
        [--baselines DIR] [--threshold 0.25]
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# metric -> direction a *regression* moves ("down": worse when it drops)
GATED_METRICS: Dict[str, str] = {
    "speedup": "down",            # bench_churn, bench_distributed
    "expand_speedup": "down",     # bench_ragged
    "pause_reduction": "down",    # bench_pause
    "p99_ratio": "down",          # bench_async
    "goodput_ratio": "down",      # bench_faults (faulted / fault-free)
    "healthy_goodput_ratio": "down",   # bench_tenant (healthy / clean)
    "victim_goodput_ratio": "down",    # bench_tenant (victim / clean)
    "bytes_fraction": "up",       # bench_ragged / bench_distributed
}

# keys that identify a row's scenario — a mismatch means the bench's
# shape changed and the baseline must be refreshed, not diffed
IDENTITY_KEYS = ("layout", "trees", "devices", "batch", "hot_factor",
                 "n_requests", "hit_rate")


def _row_lists(payload: Dict) -> List[Tuple[str, List[Dict]]]:
    """Every top-level list-of-dicts in a BENCH payload (the benches use
    different key names: "rows", "churn", "bank", ...)."""
    return [(k, v) for k, v in payload.items()
            if isinstance(v, list) and v
            and all(isinstance(r, dict) for r in v)]


def _ident(row: Dict) -> Tuple:
    return tuple((k, row[k]) for k in IDENTITY_KEYS if k in row)


def compare(name: str, current: Dict, baseline: Dict,
            threshold: float = 0.25) -> Tuple[List[Dict], List[str]]:
    """Diff one BENCH payload against its baseline.

    Returns ``(entries, notes)``: one entry per gated metric per matched
    row — ``entry["regressed"]`` marks a worsening beyond ``threshold``
    — plus human-readable notes for anything skipped."""
    entries: List[Dict] = []
    notes: List[str] = []
    base_lists = dict(_row_lists(baseline))
    for key, cur_rows in _row_lists(current):
        base_rows = base_lists.get(key)
        if base_rows is None:
            notes.append(f"{name}:{key}: no baseline rows — skipped")
            continue
        if len(base_rows) != len(cur_rows):
            notes.append(f"{name}:{key}: row count changed "
                         f"({len(base_rows)} -> {len(cur_rows)}) — "
                         "comparing the common prefix")
        for i, (cur, base) in enumerate(zip(cur_rows, base_rows)):
            if _ident(cur) != _ident(base):
                notes.append(f"{name}:{key}[{i}]: scenario changed "
                             f"({_ident(base)} -> {_ident(cur)}) — "
                             "refresh the baseline")
                continue
            for metric, direction in GATED_METRICS.items():
                if metric not in cur or metric not in base:
                    continue
                b, c = float(base[metric]), float(cur[metric])
                if b <= 0:
                    continue
                if direction == "down" and b < 1.0:
                    # a higher-is-better ratio below 1 means the bench
                    # scenario sits below its crossover point on the
                    # recording host (e.g. a host-mesh shard speedup on
                    # an oversubscribed CPU) — relative noise dominates
                    notes.append(f"{name}:{key}[{i}]:{metric}: baseline "
                                 f"{b:.3f} < 1 (below crossover on the "
                                 "recording host) — not gated")
                    continue
                change = (c - b) / b
                worsened = -change if direction == "down" else change
                entries.append(dict(
                    file=name, rows=f"{key}[{i}]", metric=metric,
                    baseline=b, current=c, change=change,
                    regressed=worsened > threshold))
    return entries, notes


def print_table(entries: List[Dict]) -> None:
    print(f"{'file':>18s} {'row':>10s} {'metric':>16s} "
          f"{'baseline':>9s} {'current':>9s} {'change':>8s}")
    for e in entries:
        flag = "  << REGRESSED" if e["regressed"] else ""
        print(f"{e['file']:>18s} {e['rows']:>10s} {e['metric']:>16s} "
              f"{e['baseline']:9.3f} {e['current']:9.3f} "
              f"{e['change']:+7.1%}{flag}")


def print_snapshot_diff(name: str, current: Dict, baseline: Dict) -> None:
    """The causal trail behind a gate trip: diff the embedded
    observability snapshots (``payload["obs"]`` — counters and gauges)
    of the regressed file against its baseline.  A p99 regression with
    ``serve.hot_recompiles`` up, or a pause regression with
    ``maint.plans{kind=full}`` up, answers "why" without a rerun."""
    cur, base = current.get("obs"), baseline.get("obs")
    if not cur:
        print(f"{name}: no embedded obs snapshot in the current run")
        return
    base = base or {}
    print(f"\n{name}: embedded metrics snapshot "
          f"(current vs baseline{'' if base else ' — none recorded'})")
    print(f"{'metric':>44s} {'baseline':>12s} {'current':>12s}")
    for section in ("counters", "gauges"):
        c = cur.get(section, {})
        b = base.get(section, {})
        for key in sorted(set(c) | set(b)):
            bv, cv = b.get(key, "-"), c.get(key, "-")
            mark = "" if bv == cv else "  <<"
            fmt = lambda v: f"{v:12.4g}" if isinstance(v, (int, float)) \
                else f"{v:>12s}"                          # noqa: E731
            print(f"{key:>44s} {fmt(bv)} {fmt(cv)}{mark}")


def check_dirs(current_dir: str, baseline_dir: str,
               threshold: float = 0.25) -> int:
    """Compare every BENCH_*.json present in both dirs; returns the
    number of regressed metrics (0 = pass)."""
    entries: List[Dict] = []
    notes: List[str] = []
    names = sorted(n for n in os.listdir(baseline_dir)
                   if n.startswith("BENCH_") and n.endswith(".json"))
    if not names:
        print(f"no BENCH_*.json baselines under {baseline_dir}",
              file=sys.stderr)
        return 1
    compared = 0
    payloads: Dict[str, Tuple[Dict, Dict]] = {}
    for name in names:
        cur_path = os.path.join(current_dir, name)
        if not os.path.exists(cur_path):
            notes.append(f"{name}: not produced by this run — skipped")
            continue
        with open(cur_path) as f:
            current = json.load(f)
        with open(os.path.join(baseline_dir, name)) as f:
            baseline = json.load(f)
        payloads[name] = (current, baseline)
        e, n = compare(name, current, baseline, threshold)
        entries.extend(e)
        notes.extend(n)
        compared += 1
    print(f"perf-regression gate: {compared} benchmark file(s), "
          f"{len(entries)} gated metric(s), threshold "
          f"{threshold:.0%} (ratios only — raw timings are not gated)")
    if entries:
        print_table(entries)
    for n in notes:
        print(f"note: {n}")
    bad = sum(e["regressed"] for e in entries)
    if bad:
        # surface the causal trail of every regressed file before failing
        for name in sorted({e["file"] for e in entries if e["regressed"]}):
            cur, base = payloads[name]
            print_snapshot_diff(name, cur, base)
        print(f"\nFAIL: {bad} metric(s) regressed more than "
              f"{threshold:.0%} vs benchmarks/baselines/ — if the change "
              "is intended, refresh the baseline JSON (CONTRIBUTING.md)")
    elif compared:
        print("\nOK: no gated metric regressed beyond the threshold")
    return bad


def main(argv: Optional[List[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    here = os.path.dirname(os.path.abspath(__file__))
    current_dir, threshold = os.getcwd(), 0.25
    baseline_dir = os.path.join(here, "baselines")

    def opt(flag, default):
        if flag in args:
            i = args.index(flag)
            val = args[i + 1]
            del args[i:i + 2]
            return val
        return default

    current_dir = opt("--current", current_dir)
    baseline_dir = opt("--baselines", baseline_dir)
    threshold = float(opt("--threshold", threshold))
    if args:
        print(__doc__)
        return 2
    return 1 if check_dirs(current_dir, baseline_dir, threshold) else 0


if __name__ == "__main__":
    sys.exit(main())
