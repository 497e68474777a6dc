"""Trace spans over the serving, retrieval and maintenance lifecycles.

A span is one named unit of work (an async batch, a pipeline retrieve,
a generation, a maintenance prepare) carrying attributes (bucket size,
plan kind) and a sequence of timed **stages** — the async request path
decomposes as ``coalesce → pad → dispatch → prepare → device_lookup →
route_back``, a pipeline retrieve as ``recognise → device → harvest →
fetch → render``, a generation as ``prefill → decode``, the maintenance
path as ``maintain → plan → warm`` then ``splice``.

While a profiler trace runs, a span and each of its ``stage()`` blocks
also open a ``jax.profiler.TraceAnnotation`` named ``repro/<span>`` and
``repro/<span>/<stage>``, so the trace shows them on the host thread
that ran them, on the device ops' clock; with no trace running none is
built.  ``add_stage`` records a duration that already passed and stays
on the host clock only.

Each span records its start and end on ``time.perf_counter`` and its
parent: the innermost span open on the same thread when it began.  The
compile listener (:mod:`repro.obs.recompile`) adds JAX's tracing,
lowering and backend-compile durations to the innermost open span of
the compiling thread (attributes ``trace_s``, ``lower_s``,
``compile_s``).

On ``end()`` the span lands twice:

* each stage's duration feeds a registry histogram named
  ``trace.<span>.<stage>`` (plus ``trace.<span>`` for the total);
* the finished span joins the registry's span log (``registry.spans``,
  one bounded ring per span name), which outlives the tracer: readers
  take the newest ``n`` of a name with :func:`finished_spans`.

A disabled registry makes ``Tracer.span`` return a shared no-op span and
construct no annotation, so traced hot paths cost one branch when
observability is off.  Spans belong at call, batch and stage
granularity on the host: never inside a function JAX traces, where the
Python would run again at every trace.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import MetricsRegistry, get_registry

ANNOTATION_PREFIX = "repro/"

_ids = itertools.count(1)
_open = threading.local()              # per thread: the open spans


def _open_spans() -> List["Span"]:
    stack = getattr(_open, "spans", None)
    if stack is None:
        stack = _open.spans = []
    return stack


def current_span() -> Optional["Span"]:
    """The innermost span open on the calling thread, if any."""
    stack = getattr(_open, "spans", None)
    return stack[-1] if stack else None


def _annotation(name: str) -> Optional[TraceAnnotation]:
    """An entered profiler annotation, or None while no trace runs."""
    if not TraceAnnotation.is_enabled():
        return None
    note = TraceAnnotation(name)
    note.__enter__()
    return note


class Span:
    """One traced unit of work; create via :meth:`Tracer.span`."""

    __slots__ = ("name", "attrs", "stages", "id", "parent", "t0",
                 "_tracer", "_note")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.stages: List[tuple] = []
        self._tracer = tracer
        self.id = next(_ids)
        stack = _open_spans()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._note = _annotation(ANNOTATION_PREFIX + name)
        self.t0 = time.perf_counter()

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def stage(self, name: str) -> "_StageTimer":
        """``with span.stage("dispatch"): ...`` — time one stage."""
        return _StageTimer(self, name)

    def add_stage(self, name: str, duration: float) -> "Span":
        """Record an externally-measured stage (e.g. coalesce time,
        which elapsed before the span opened)."""
        self.stages.append((name, float(duration)))
        return self

    def end(self) -> "Span":
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(None, None, None)
        stack = _open_spans()
        if self in stack:
            stack.remove(self)
        self._tracer._finish(self, t1)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _StageTimer:
    __slots__ = ("_span", "_name", "_t0", "_note")

    def __init__(self, span: Span, name: str):
        self._span = span
        self._name = name

    def __enter__(self) -> "_StageTimer":
        self._note = _annotation(
            f"{ANNOTATION_PREFIX}{self._span.name}/{self._name}")
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        d = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(None, None, None)
        self._span.add_stage(self._name, d)


class _NullSpan:
    """Shared do-nothing span handed out while tracing is disabled."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def stage(self, name: str) -> "_NullSpan":
        return self

    def add_stage(self, name: str, duration: float) -> "_NullSpan":
        return self

    def end(self) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


def _as_dict(name: str, rec: tuple) -> Dict:
    sid, parent, t0, t1, attrs, stages = rec
    return dict(span=name, id=sid, parent=parent, t0=t0, t1=t1,
                attrs=dict(attrs),
                stages=[dict(stage=s, duration_s=d) for s, d in stages])


def finished_spans(name: str, n: int,
                   registry: Optional[MetricsRegistry] = None) -> List[Dict]:
    """The newest ``n`` finished spans named ``name``, oldest first, as
    plain dicts (``span``, ``id``, ``parent``, ``t0``, ``t1`` on
    ``time.perf_counter``, ``attrs``, ``stages``); fewer when fewer were
    kept."""
    reg = registry if registry is not None else get_registry()
    return [_as_dict(name, r) for r in reg.spans.newest(name, n)]


class Tracer:
    """Span factory bound to a registry: aggregates stage durations into
    its histograms and keeps finished spans in its span log."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else get_registry()
        from .recompile import ensure_compile_listener
        ensure_compile_listener()

    def span(self, name: str, **attrs):
        if not self.registry.enabled:
            return NULL_SPAN
        return Span(self, name, attrs)

    def annotate(self, name: str):
        """A profiler annotation ``repro/<name>`` and nothing else: no
        span, no histogram (``with tracer.annotate("serve.wait"): ...``).
        The shared no-op while the registry is disabled or no trace
        runs."""
        if not self.registry.enabled or not TraceAnnotation.is_enabled():
            return NULL_SPAN
        return TraceAnnotation(ANNOTATION_PREFIX + name)

    def _finish(self, span: Span, t1: float) -> None:
        reg = self.registry
        reg.histogram(f"trace.{span.name}").observe(t1 - span.t0)
        for stage, d in span.stages:
            reg.histogram(f"trace.{span.name}.{stage}").observe(d)
        # plain tuples of atoms: the collector stops tracking them
        reg.spans.append(span.name, (
            span.id, span.parent, span.t0, t1,
            tuple(span.attrs.items()), tuple(span.stages)))
