"""The program's own trace spans, for the per-layer readers and the
checks of them.

The program keeps its finished spans process-wide, one ring per span
name (``repro.obs.finished_spans``).  Each span that a reader takes is
one call or one batch of the timed path, and nothing an entry does after
its window (``release``, ``verify``) finishes one; so the newest ``n``
spans of a name, ``n`` being the window's own count of those calls or
batches, are the window's.  A program without the span log gives
nothing, and so does a ring that kept fewer than ``n``.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List, Optional

import numpy as np

# the span each cell's readers take, and the window's count of them
TAKEN = {
    "retrieve-fanout-600": ("rag.retrieve", lambda w: w.attempted),
    "rag-answer-600": ("serve.generate", lambda w: w.attempted),
    "retrieve-scoped-6k": ("serve.batch",
                           lambda w: w.stats.get("serve.batches", 0)),
}


def window(name: str, n: float) -> Optional[List[Dict]]:
    try:
        from repro.obs import finished_spans
    except ImportError:
        return None
    n = int(n)
    if n <= 0:
        return None
    spans = finished_spans(name, n)
    return spans if len(spans) == n else None


def stage_s(span: Dict, *names: str) -> float:
    """Seconds the span spent in the named stages."""
    return sum(st["duration_s"] for st in span["stages"]
               if st["stage"] in names)


def median_ms(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.median(v)) * 1e3 if v.size else None


@contextlib.contextmanager
def watched(workload: str):
    """Around one ``harness.run_cell`` of ``workload``, for the checks:
    yields a dict that gets the entry's ``Window`` (``window``), its
    bounds on ``time.perf_counter`` (``bounds``) and, traced, the trace's
    planes (``planes``), read before the harness deletes the trace."""
    import harness
    import trace_reduce
    seen: Dict = {}
    _, _, traffic, _, _ = harness.resolve(workload)
    entry = harness.load_module(os.path.join(
        harness.HERE, "entries", traffic["entry"] + ".py")).Entry
    timed, reduce = entry.window, trace_reduce.reduce_trace

    def kept(self, seconds):
        t0 = time.perf_counter()
        seen["window"] = timed(self, seconds)
        seen["bounds"] = (t0, time.perf_counter())
        return seen["window"]

    def reduce_and_keep(trace_dir, kernels=()):
        from jax.profiler import ProfileData
        seen["data"] = ProfileData.from_file(
            trace_reduce.find_xplane(trace_dir))
        seen["planes"] = list(seen["data"].planes)
        return reduce(trace_dir, kernels)

    entry.window, trace_reduce.reduce_trace = kept, reduce_and_keep
    try:
        yield seen
    finally:
        entry.window, trace_reduce.reduce_trace = timed, reduce
