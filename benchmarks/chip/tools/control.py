"""Run a cell on the chip with its control, or a fault, in the
program's place, on several seeds in one process.

    python3 benchmarks/chip/tools/control.py --workload rag-answer-600 \\
        --fault fp8_control --seeds 11,12,13 --seconds 10

The control and the faults are those of ``entries/faults.py``.  Prints,
for each seed, one JSON line with ``correct`` and each number compared
beside its limit: the control's readings, from which the upper end of a
limit is set.  The benchmark's own runs never plant either.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                       "src")]
    import harness
    from entries.faults import FAULTS
    if args.fault not in FAULTS:
        raise SystemExit(f"unknown fault {args.fault!r}; one of {FAULTS}")
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               fault=args.fault)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
