"""Spread of a set of runs, the way the benchmark's bounds are set.

    python3 benchmarks/chip/tools/spread.py run1.out run2.out ...

Each file holds the standard output of one run; its last line is the
result.  For every metric prints the median and the spread: the distance
between the first and third quartiles (``statistics.quantiles(values,
n=4)``) as a share of the median.  Also prints the same spread with the
run farthest from the median left out, and whether every run was
correct.
"""
from __future__ import annotations

import json
import statistics
import sys


def result(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    runs = [result(p) for p in paths]
    print(f"runs {len(runs)} correct {[r['correct'] for r in runs]}")
    names = sorted({k for r in runs for k in r["metrics"]})
    for n in names:
        vals = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
        med = statistics.median(vals)
        line = f"{n:28s} median {med:.6g}"
        if len(vals) >= 2:
            line += f"  spread {spread(vals):.4f}"
            far = max(range(len(vals)), key=lambda i: abs(vals[i] - med))
            rest = vals[:far] + vals[far + 1:]
            if len(rest) >= 2:
                line += f"  without the farthest {spread(rest):.4f}"
        print(line + "  values " + " ".join(f"{v:.6g}" for v in vals))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
