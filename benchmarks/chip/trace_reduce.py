"""Reduce a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, and gives:

* ``busy_s``: the union of the intervals in which an operation ran on
  each device (its "XLA Ops" line), averaged over the devices;
* ``ops``: device seconds by operation name;
* ``kernel_s`` / ``kernel_events``: the summed device time and count of
  the events whose name or statistics contain a kernel's stable name
  (``cuckoo_probe`` for the probe kernel);
* ``gaps``: idle seconds between device operations, grouped by the
  innermost benchmark annotation (``bench/...``) that the host was inside
  at the middle of the gap, ``host: none`` when it was inside none.

Device and host events of one trace share one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATION_PREFIX = "bench/"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    devices: int
    busy_s: float
    span_s: float                     # first op start to last op end
    ops: Dict[str, float]
    kernel_s: Dict[str, float]
    kernel_events: Dict[str, int]
    gaps: Dict[str, float]

    def top_ops(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in sorted(self.ops.items(),
                                          key=lambda x: -x[1])[:k]]

    def top_gaps(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in sorted(self.gaps.items(),
                                          key=lambda x: -x[1])[:k]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _text(event) -> str:
    parts = [event.name]
    try:
        for _, v in event.stats:
            if isinstance(v, str):
                parts.append(v)
    except (TypeError, ValueError):
        pass
    return " ".join(parts)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def reduce_planes(planes: Iterable, kernels: Sequence[str] = ()
                  ) -> TraceSummary:
    """``planes``: objects with ``name`` and ``lines``; a line has ``name``
    and ``events``; an event has ``name``, ``start_ns``, ``duration_ns``
    and ``stats`` (what ``ProfileData`` gives)."""
    ops: Dict[str, float] = {}
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    device_iv: List[List[Tuple[float, float]]] = []
    host_spans: List[Tuple[float, float, str]] = []
    for plane in planes:
        if _is_device(plane.name):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            iv: List[Tuple[float, float]] = []
            for ln in lines:
                for e in ln.events:
                    a, d = float(e.start_ns), float(e.duration_ns)
                    iv.append((a, a + d))
                    ops[e.name] = ops.get(e.name, 0.0) + d * 1e-9
                    if kernels:
                        text = _text(e)
                        for k in kernels:
                            if k in text:
                                kernel_s[k] += d * 1e-9
                                kernel_n[k] += 1
            if lines:
                device_iv.append(_union(iv))
        else:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        a = float(e.start_ns)
                        host_spans.append((a, a + float(e.duration_ns),
                                           e.name[len(ANNOTATION_PREFIX):]))
    ndev = max(len(device_iv), 1)
    busy = sum(b - a for iv in device_iv for a, b in iv) * 1e-9 / ndev
    starts = [iv[0][0] for iv in device_iv if iv]
    ends = [iv[-1][1] for iv in device_iv if iv]
    span = (max(ends) - min(starts)) * 1e-9 if starts else 0.0
    gaps: Dict[str, float] = {}
    if device_iv and device_iv[0]:
        iv = device_iv[0]
        host_spans.sort()
        span_starts = [s for s, _, _ in host_spans]
        for (_, b), (a2, _) in zip(iv[:-1], iv[1:]):
            mid = 0.5 * (b + a2)
            name, best = "host: none", None
            # spans are nested, so the innermost one holding ``mid``
            # starts shortly before it
            i = bisect.bisect_right(span_starts, mid)
            for s, e, n in reversed(host_spans[max(0, i - 256):i]):
                if e >= mid and (best is None or e - s < best):
                    name, best = n, e - s
            gaps[name] = gaps.get(name, 0.0) + (a2 - b) * 1e-9
    return TraceSummary(devices=len(device_iv), busy_s=busy, span_s=span,
                        ops=ops, kernel_s=kernel_s, kernel_events=kernel_n,
                        gaps=gaps)


def reduce_trace(trace_dir: str, kernels: Sequence[str] = ()
                 ) -> TraceSummary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(data.planes, kernels)


def describe(trace_dir: str, max_events: int = 3) -> str:
    """Planes, lines and a few events of a trace: for looking at one by
    hand before trusting the reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            out.append(f"  line {ln.name!r} events={len(evs)}")
            for e in evs[:max_events]:
                out.append(f"    {e.start_ns:.0f} +{e.duration_ns:.0f} "
                           f"{_text(e)[:160]!r}")
    return "\n".join(out)


def idle_percent(summary: Optional[TraceSummary], window_s: float
                 ) -> Optional[float]:
    """Per cent of ``window_s`` in which no operation ran on the devices;
    ``None`` for a trace with no device plane."""
    if summary is None or not summary.devices or window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / window_s)


def annotation(name: str, on: bool):
    """A host span in the profiler's trace when ``on``; else nothing."""
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)

