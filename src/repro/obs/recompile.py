"""Hot-path recompile sentinel.

PR 6's dominant tail-latency pathology was a silent one: a maintenance
commit that changed a committed array shape (an unpadded CSR, a resized
arena reaching the jitted step with a new geometry) forced XLA to
recompile the serve step *on the next dispatch* — ~650 ms landing on
whichever request was unlucky.  The fix (``pad_csr`` shape stability)
was diagnosed by hand; this module makes the diagnosis permanent:

* **cache-size watching** — ``watch()`` registers jitted callables (the
  serve step) and baselines their compiled-geometry counts
  (``_cache_size``).  ``check()`` reports any growth since the baseline
  as hot-path recompiles (``serve.hot_recompiles`` counter) and
  re-baselines.  ``rebaseline()`` after warmup excludes intentional
  compiles.
* **commit shape classification** — ``note_commit()`` compares the
  committed state's array shapes before/after a maintenance commit.  A
  ``segment``/``full``/``splice`` plan legitimately changes the arena
  geometry (``maint.commit_shape_changes{expected=true}``); a ``delta``
  or ``none`` plan must not change any shape — when one does, that is
  exactly the PR 6 bug reborn (``expected=false``).
* **arming** — ``arm()`` turns both detectors from counters into
  tripwires: an unexpected commit shape change or a post-warmup
  hot-path recompile raises :class:`HotPathRecompileError` instead of
  silently eating the tail.

A process-wide ``jax.monitoring`` listener additionally counts *every*
backend compile in the process (``xla.compiles`` /
``xla.compile_s``) — warmup, maintenance warm-compiles, everything —
giving snapshots the denominator against which zero hot-path
recompiles is meaningful.  It also sums JAX's jaxpr tracing
(``xla.trace_s``) and MLIR lowering (``xla.lower_s``), and adds each of
the three durations to the innermost open trace span of the compiling
thread (``trace_s``, ``lower_s``, ``compile_s`` attributes), so a call
that compiles says how much of it was compiling.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

from .metrics import MetricsRegistry, get_registry
from .tracing import current_span

# plan kinds whose commits legitimately change committed array shapes
# (resized arena segment, full repack / restage)
EXPECTED_SHAPE_CHANGE_KINDS = ("segment", "full", "splice")

# committed arrays whose shapes feed the jitted serve step: any change
# here invalidates the step's cached executable for that geometry
_STATE_FIELDS = ("fingerprints", "temperature", "heads", "masks",
                 "csr_offsets", "csr_nodes", "bucket_offsets",
                 "row_offsets", "tree_starts", "tree_shard")


class HotPathRecompileError(RuntimeError):
    """An armed sentinel observed serve-path compilation work that the
    padding / splice machinery promises never happens."""


def state_shapes(state) -> Dict[str, Tuple[int, ...]]:
    """Shape fingerprint of a device state's jit-relevant arrays."""
    out: Dict[str, Tuple[int, ...]] = {}
    for f in _STATE_FIELDS:
        a = getattr(state, f, None)
        if a is not None and hasattr(a, "shape"):
            out[f] = tuple(int(d) for d in a.shape)
    return out


class RecompileSentinel:
    """Watches jitted serve callables and maintenance commits for
    shape-instability; counts always, raises when armed."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.metrics = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._watched: Dict[str, Tuple[Callable[[], int], int]] = {}
        self._armed = False
        self._forgive = False           # one expected geometry compile
        self._local_recompiles = 0      # this sentinel's lifetime count
        #                                 (the registry counter is
        #                                 process-cumulative)
        self._recompiles = self.metrics.counter(
            "serve.hot_recompiles",
            "post-warmup compilations of watched serve-path callables")
        self._shape_changes = self.metrics.counter(
            "maint.commit_shape_changes",
            "maintenance commits that changed committed array shapes")
        ensure_compile_listener()

    # ------------------------------------------------------ cache sizes
    def watch(self, label: str, fn) -> bool:
        """Track a jitted callable's compiled-geometry count.  Accepts
        anything exposing ``_cache_size()`` (``jax.jit`` products);
        returns False (untracked) otherwise."""
        size = getattr(fn, "_cache_size", None)
        if not callable(size):
            return False
        with self._lock:
            self._watched[label] = (size, int(size()))
        return True

    def rebaseline(self) -> None:
        """Accept current cache sizes as intentional (call after
        warmup, or after an expected-shape-change commit)."""
        with self._lock:
            self._watched = {k: (fn, int(fn()))
                             for k, (fn, _) in self._watched.items()}

    def allow_next(self) -> None:
        """Forgive the next cache growth once — called after a commit
        whose plan kind legitimately changed the serve geometry (the
        step must compile it exactly once)."""
        with self._lock:
            self._forgive = True

    def check(self) -> Dict[str, int]:
        """New compilations per watched callable since the last check;
        counts them, re-baselines, raises when armed and non-empty
        (unless an expected geometry change forgave this growth)."""
        grown: Dict[str, int] = {}
        with self._lock:
            for label, (fn, base) in list(self._watched.items()):
                cur = int(fn())
                if cur > base:
                    grown[label] = cur - base
                    self._watched[label] = (fn, cur)
            forgiven = grown and self._forgive
            if grown:
                self._forgive = False
            if not forgiven:
                self._local_recompiles += sum(grown.values())
        if forgiven:
            self.metrics.counter(
                "serve.expected_recompiles",
                "serve-step compiles of legitimately resized geometries"
            ).inc(sum(grown.values()))
            return {}
        for label, n in grown.items():
            self._recompiles.inc(n, fn=label)
        if grown and self._armed:
            raise HotPathRecompileError(
                f"hot serve path recompiled: {grown} new XLA "
                "compilations on watched jitted callables — a commit "
                "leaked an unpadded / resized shape into the step")
        return grown

    @property
    def recompiles(self) -> int:
        """Hot-path recompiles this sentinel has counted (per-sentinel,
        unlike the process-cumulative registry counter)."""
        with self._lock:
            return self._local_recompiles

    # ---------------------------------------------------------- commits
    def note_commit(self, kind: Optional[str],
                    before: Dict[str, Tuple[int, ...]],
                    after: Dict[str, Tuple[int, ...]]) -> List[str]:
        """Classify one maintenance commit's shape delta.  Returns the
        fields whose shape changed; counts them as expected/unexpected
        by plan ``kind`` and raises when armed on an unexpected one."""
        changed = sorted(k for k in set(before) | set(after)
                         if before.get(k) != after.get(k))
        if not changed:
            return changed
        expected = kind in EXPECTED_SHAPE_CHANGE_KINDS
        self._shape_changes.inc(expected=str(expected).lower(),
                                kind=kind or "unknown")
        if expected:
            # the step must compile the new geometry once — forgive it
            self.allow_next()
            return changed
        if self._armed:
            raise HotPathRecompileError(
                f"{kind!r}-plan commit changed committed array shapes "
                f"{changed} — delta commits must be shape-preserving "
                "(is pad_csr being bypassed?)")
        return changed

    # ------------------------------------------------------------ state
    def arm(self) -> "RecompileSentinel":
        self._armed = True
        return self

    def disarm(self) -> "RecompileSentinel":
        self._armed = False
        return self

    @property
    def armed(self) -> bool:
        return self._armed


# one process-wide jax.monitoring listener, shared by every sentinel and
# tracer; installed once and never unhooked
_listener_lock = threading.Lock()
_listener_installed = False

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
# event -> (process histogram, its help, span attribute)
_PHASES = {
    _TRACE: ("xla.trace_s", "jaxpr tracing, nested traces counted once",
             "trace_s"),
    _LOWER: ("xla.lower_s", "jaxpr to MLIR lowering durations", "lower_s"),
    _COMPILE: ("xla.compile_s", "backend compile durations", "compile_s")}
# per thread: how many events of each phase are open.  JAX traces an
# inner jitted function inside its caller's trace, so only the
# outermost event of a phase counts: nested time is counted once.
_depth = threading.local()


def _on_compile_start(event: str, value: float, **kw) -> None:
    if event in _PHASES:
        d = _depth.__dict__
        d[event] = d.get(event, 0) + 1


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    d = _depth.__dict__
    open_ = d.get(event, 0) - 1
    d[event] = max(open_, 0)
    reg = get_registry()
    if event == _COMPILE:
        reg.counter("xla.compiles",
                    "process-wide backend compilations (any cause)").inc()
    elif open_ > 0:
        return                       # inside an outer event of its phase
    hist, help_, attr = phase
    reg.histogram(hist, help_).observe(duration)
    span = current_span()
    if span is not None:
        span.attrs[attr] = span.attrs.get(attr, 0.0) + duration


def ensure_compile_listener() -> None:
    """Install the process-wide compile listener once."""
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            from jax import monitoring
            monitoring.register_scalar_listener(_on_compile_start)
            monitoring.register_event_duration_secs_listener(
                _on_compile_duration)
            _listener_installed = True
