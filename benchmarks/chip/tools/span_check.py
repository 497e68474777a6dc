"""Run one cell as ``run.py`` does and check the program's spans against
the benchmark's own numbers.

    python3 benchmarks/chip/tools/span_check.py --workload <name> \\
        --seed <n> --seconds <s> --trace <0|1>

Prints the run's result line last, as ``run.py`` does, and before it one
line ``{"span_check": {...}}``:

* the window's spans of the cell's timed path, counted by their clock
  against the window's bounds, beside the count the readers take;
* fan-out: the median ``rag.retrieve`` span beside ``fanout_p50_ms``,
  the share of it its stages cover, and how many spans hold more
  trace, lowering and compile time than their ``device`` stage;
* answer: the median ``prefill + decode`` beside the median time of
  ``ServeEngine.serve`` (``answer_generate_ms``);
* traced: per host thread that carries ``repro/`` events, the share of
  the window those events cover, the longest gaps between them (on the
  scheduler thread, time in neither a batch nor ``repro/serve.wait`` is
  a stall) and a short excerpt of its events.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def threads(planes, t0_ns: float, t1_ns: float, top: int = 5,
            excerpt: int = 14) -> dict:
    """Coverage of each host line by its ``repro/`` events over
    ``[t0_ns, t1_ns]``, its longest uncovered gaps, and an excerpt."""
    out = {}
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            evs = [e for e in ln.events if e.name.startswith("repro/")
                   and e.start_ns + e.duration_ns >= t0_ns
                   and e.start_ns <= t1_ns]
            if not evs:
                continue
            iv = _union([(max(float(e.start_ns), t0_ns),
                          min(float(e.start_ns + e.duration_ns), t1_ns))
                         for e in evs])
            covered = sum(b - a for a, b in iv)
            gaps = sorted(((b2 - a1, a1) for (_, a1), (b2, _) in
                           zip(iv[:-1], iv[1:])), reverse=True)[:top]
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            evs.sort(key=lambda e: e.start_ns)
            mid = len(evs) // 2
            out[f"{plane.name} | {ln.name}"] = {
                "covered_share": covered / max(t1_ns - t0_ns, 1.0),
                "longest_gaps_ms": [[g * 1e-6, (a - t0_ns) * 1e-9]
                                    for g, a in gaps],
                "events": names,
                "excerpt": [[(e.start_ns - t0_ns) * 1e-9,
                             e.duration_ns * 1e-6, e.name]
                            for e in evs[mid:mid + excerpt]]}
    return out


def window_ns(planes):
    """The window on the trace's clock: the benchmark's first to last
    annotation of a timed call."""
    host = [e for p in planes if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events
            if e.name.startswith("bench/")]
    if not host:
        return 0.0, 0.0
    return (min(e.start_ns for e in host),
            max(e.start_ns + e.duration_ns for e in host))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    result, report = check(args.workload, args.seed, args.seconds,
                           bool(args.trace))
    print(json.dumps({"span_check": report}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


def check(workload: str, seed: int, seconds: float, trace: bool):
    """Run the cell through ``harness.run_cell``; returns its result and
    the span report."""
    import harness
    from program_spans import TAKEN, stage_s, watched
    from repro.obs import finished_spans

    t_start = time.perf_counter()
    with watched(workload) as seen:
        result = harness.run_cell(workload, seed, seconds, trace,
                                  t_start=t_start)
    win, (t0, t1) = seen["window"], seen["bounds"]
    name, count = TAKEN[workload]
    n = int(count(win))
    spans = finished_spans(name, 2 * n + 16)
    inside = [s for s in spans if t0 <= s["t0"] and s["t1"] <= t1]
    report = {"cell": workload, "span": name, "taken": n,
              "inside_window": len(inside),
              "after_window": sum(s["t1"] > t1 for s in spans)}
    ms = lambda v: statistics.median(v) * 1e3  # noqa: E731
    if name == "rag.retrieve":
        dur = [s["t1"] - s["t0"] for s in inside]
        med = sorted(inside, key=lambda s: s["t1"] - s["t0"])[
            len(inside) // 2]
        comp = [sum(s["attrs"].get(k, 0.0) for k in
                    ("trace_s", "lower_s", "compile_s")) for s in inside]
        report.update(
            span_p50_ms=ms(dur), fanout_p50_ms=win.e2e["fanout_p50_ms"],
            median_span_stage_cover=sum(
                st["duration_s"] for st in med["stages"])
            / (med["t1"] - med["t0"]),
            median_span_stages_ms={st["stage"]: st["duration_s"] * 1e3
                                   for st in med["stages"]},
            median_span_attrs=med["attrs"],
            compile_work_p50_ms=ms(comp),
            compile_work_over_device=sum(
                c > stage_s(s, "device") for c, s in zip(comp, inside)))
    elif name == "serve.generate":
        gen = [stage_s(s, "prefill", "decode") for s in inside]
        report.update(
            prefill_plus_decode_p50_ms=ms(gen),
            generate_span_p50_ms=ms([s["t1"] - s["t0"] for s in inside]),
            answer_generate_ms=win.stats["answer_serve_s"] * 1e3)
    if "planes" in seen:
        report["threads"] = threads(seen["planes"],
                                    *window_ns(seen["planes"]))
    return result, report


if __name__ == "__main__":
    sys.exit(main())
