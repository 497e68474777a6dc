"""Find the knee of an open-loop cell: the highest offered rate whose
completions keep up with its arrivals over the window.

    python3 benchmarks/chip/tools/sweep.py --workload retrieve-scoped-6k \\
        --seed 7 --seconds 10 --rates 250,500,1000,2000,4000

One process sets the cell up once, then offers each rate for
``--seconds`` with the cell's own traffic mix at that rate.  For each
rate it prints one JSON line: the latency median and 95th percentile,
the median latency of the first and the last tenth of the requests (a
backlog that grows over the window shows as a last tenth far above the
first), how late the generator ran, and the requests still unanswered
when the window closed.  The cell's rate is then set, by hand, to about
four fifths of the knee.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                       "src")]
    import numpy as np
    import harness
    from entries.common import generator

    cell, config, traffic, _, _ = harness.resolve(args.workload)
    harness.configure_jax()
    harness.require_chips(int(cell["chips"]))
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds, trace=False)
    entry = harness.load_module(os.path.join(
        HERE, "entries", traffic["entry"] + ".py")).Entry(ctx)
    entry.setup()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate_per_s=rate)
        entry.schedule = generator(mix).generate(
            entry.ref, mix, args.seconds, args.seed + k + 1)
        win = entry.window(args.seconds)
        lat = entry.latencies_ms
        tenth = max(1, lat.size // 10)
        print(json.dumps({
            "rate_per_s": rate, "attempted": win.attempted,
            "failed": win.failed, "unanswered": entry.unanswered,
            "p50_ms": win.e2e["retrieve_p50_ms"],
            "p95_ms": win.stats["retrieve_p95_ms"],
            "first_tenth_p50_ms": float(np.median(lat[:tenth])),
            "last_tenth_p50_ms": float(np.median(lat[-tenth:])),
            "late_p99_ms": win.stats["late_p99_ms"],
            "batches": win.stats["serve.batches"],
            "queries": win.stats["serve.queries"]}), flush=True)
    entry.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
