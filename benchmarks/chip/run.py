"""Run one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints progress and the compared numbers on standard error and, as the
last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, traced, ``breakdown``; ``checks`` comes last.  Exits 2 with no
result line when JAX finds no TPU or fewer chips than the cell needs, and
1 when anything else stops the run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(HERE))
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"no program under {src}: run from a checkout of the repository")
        return 1
    sys.path[:0] = [HERE, src]
    import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 2
    except Exception:                      # any failed phase: no result
        traceback.print_exc()
        return 1
    for note in result.get("notes", []):
        log(note)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
