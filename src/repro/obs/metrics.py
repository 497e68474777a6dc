"""Process-wide metrics registry: counters, gauges, and log-bucketed
latency histograms behind one lock.

The serving stack mutates statistics from at least three threads (the
scheduler, the prepare worker, and whoever calls ``stop()``); before
this module each layer kept ad-hoc dataclass counters with ad-hoc
locking.  The registry centralizes both the storage and the lock:

* **Counter** — monotone ``inc``; optional labels fan a name out into
  cells (``serve.batch_bucket{bucket=32}``).
* **Gauge** — last-write-wins ``set`` (plus ``add`` for deltas).
* **Histogram** — log₂-bucketed observations with exact ``count`` /
  ``sum`` / ``min`` / ``max`` and quantile summaries (p50/p90/p99 read
  off the bucket CDF, so they carry ~2x resolution — tail *ratios*
  across runs are meaningful, individual values are bucket edges).

Every mutation takes the registry lock — the fix for the torn
``AsyncStats`` updates — but a **disabled** registry short-circuits
before the lock, so instrumented hot paths pay one attribute load and
one branch.  ``snapshot()`` returns a plain-Python dict (every leaf
survives ``json.dumps`` untouched) and ``to_prometheus()`` renders the
v0 text exposition format.  Beside the metrics the registry keeps the
finished trace spans (``spans``, a :class:`SpanLog`), so they outlive
the objects that produced them.

One process-wide default registry (``get_registry``) keeps
instrumentation call sites decoupled from construction; tests that need
isolation construct a private ``MetricsRegistry`` and pass it down.
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Dict, List, Tuple

# log2 histogram geometry: bucket i spans [2^(B0+i), 2^(B0+i+1)) seconds
# (or whatever unit the caller observes); 2^-20 s ≈ 1 µs up to 2^19 s.
_BUCKET0 = -20
_NBUCKETS = 40

_LabelKey = Tuple[Tuple[str, object], ...]


def _label_key(labels: Dict[str, object]) -> _LabelKey:
    return tuple(sorted(labels.items()))


def _cell_name(name: str, key: _LabelKey) -> str:
    if not key:
        return name
    inner = ",".join(f"{k}={v}" for k, v in key)
    return f"{name}{{{inner}}}"


class Counter:
    """Monotone counter; ``labels`` fan out into independent cells."""

    __slots__ = ("name", "help", "_reg", "_cells")

    def __init__(self, reg: "MetricsRegistry", name: str, help: str = ""):
        self.name = name
        self.help = help
        self._reg = reg
        self._cells: Dict[_LabelKey, float] = {}

    def inc(self, value: float = 1, **labels) -> None:
        reg = self._reg
        if not reg.enabled:
            return
        key = _label_key(labels)
        with reg.lock:
            self._cells[key] = self._cells.get(key, 0) + value

    def value(self, **labels) -> float:
        with self._reg.lock:
            return self._cells.get(_label_key(labels), 0)

    def cells(self) -> Dict[str, float]:
        """``{rendered-label-suffix: value}`` for every cell."""
        with self._reg.lock:
            return {_cell_name(self.name, k): v
                    for k, v in sorted(self._cells.items())}

    def raw(self) -> Dict[_LabelKey, float]:
        """Unrendered ``{label-key: value}`` — for delta snapshots."""
        with self._reg.lock:
            return dict(self._cells)


class Gauge:
    """Last-write-wins value (``set``) with a delta form (``add``)."""

    __slots__ = ("name", "help", "_reg", "_cells")

    def __init__(self, reg: "MetricsRegistry", name: str, help: str = ""):
        self.name = name
        self.help = help
        self._reg = reg
        self._cells: Dict[_LabelKey, float] = {}

    def set(self, value: float, **labels) -> None:
        reg = self._reg
        if not reg.enabled:
            return
        with reg.lock:
            self._cells[_label_key(labels)] = value

    def add(self, value: float, **labels) -> None:
        reg = self._reg
        if not reg.enabled:
            return
        key = _label_key(labels)
        with reg.lock:
            self._cells[key] = self._cells.get(key, 0) + value

    def value(self, **labels) -> float:
        with self._reg.lock:
            return self._cells.get(_label_key(labels), 0)

    def cells(self) -> Dict[str, float]:
        with self._reg.lock:
            return {_cell_name(self.name, k): v
                    for k, v in sorted(self._cells.items())}


class Histogram:
    """Log₂-bucketed distribution with exact count/sum/min/max."""

    __slots__ = ("name", "help", "_reg", "_buckets", "_count", "_sum",
                 "_min", "_max")

    def __init__(self, reg: "MetricsRegistry", name: str, help: str = ""):
        self.name = name
        self.help = help
        self._reg = reg
        self._buckets = [0] * _NBUCKETS
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    @staticmethod
    def _index(value: float) -> int:
        if value <= 0:
            return 0
        # frexp: value = m * 2^e with m in [0.5, 1) -> floor(log2) = e - 1
        _, e = math.frexp(value)
        return min(max(e - 1 - _BUCKET0, 0), _NBUCKETS - 1)

    def observe(self, value: float) -> None:
        reg = self._reg
        if not reg.enabled:
            return
        i = self._index(value)
        with reg.lock:
            self._buckets[i] += 1
            self._count += 1
            self._sum += value
            if value < self._min:
                self._min = value
            if value > self._max:
                self._max = value

    def time(self) -> "_HistTimer":
        """``with hist.time(): ...`` — observe the block's duration."""
        return _HistTimer(self)

    def _quantile_locked(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile, clamped to
        the exact observed extremes (must hold ``self._reg.lock``)."""
        if self._count == 0:
            return 0.0
        rank = q * self._count
        acc = 0
        for i, n in enumerate(self._buckets):
            acc += n
            if acc >= rank:
                edge = 2.0 ** (_BUCKET0 + i + 1)
                return min(max(edge, self._min), self._max)
        return self._max

    def summary(self) -> Dict[str, float]:
        with self._reg.lock:
            if self._count == 0:
                return dict(count=0, sum=0.0)
            return dict(count=self._count, sum=self._sum,
                        min=self._min, max=self._max,
                        p50=self._quantile_locked(0.50),
                        p90=self._quantile_locked(0.90),
                        p99=self._quantile_locked(0.99))


class _HistTimer:
    __slots__ = ("_hist", "_t0")

    def __init__(self, hist: Histogram):
        self._hist = hist

    def __enter__(self) -> "_HistTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._hist.observe(time.perf_counter() - self._t0)


class SpanLog:
    """Finished spans, kept per span name in rings of ``capacity``: a
    flood of one name never evicts another's.  Records are opaque here
    (``repro.obs.tracing`` writes and reads them)."""

    def __init__(self, capacity: int = 16384):
        self.capacity = capacity
        self._rings: Dict[str, deque] = {}
        self._lock = threading.Lock()

    def append(self, name: str, record) -> None:
        with self._lock:
            ring = self._rings.get(name)
            if ring is None:
                ring = self._rings[name] = deque(maxlen=self.capacity)
            ring.append(record)

    def newest(self, name: str, n: int) -> List:
        """The newest ``n`` records of ``name``, oldest first (fewer when
        the ring holds fewer)."""
        if n <= 0:
            return []
        with self._lock:
            ring = self._rings.get(name, ())
            return list(ring)[-n:]

    def clear(self) -> None:
        with self._lock:
            self._rings.clear()


class MetricsRegistry:
    """Thread-safe named-metric store with JSON / Prometheus exporters.

    ``counter`` / ``gauge`` / ``histogram`` are get-or-create (same name
    → same object, so instrumentation sites never race on registration);
    re-registering a name as a different kind is an error.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.lock = threading.RLock()
        self._metrics: Dict[str, object] = {}
        self.spans = SpanLog()

    def _get(self, cls, name: str, help: str):
        with self.lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(self, name, help)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{type(m).__name__}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "") -> Histogram:
        return self._get(Histogram, name, help)

    def names(self) -> List[str]:
        with self.lock:
            return sorted(self._metrics)

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Drop every registered metric and finished span (tests; not
        for serving use)."""
        with self.lock:
            self._metrics.clear()
        self.spans.clear()

    # -------------------------------------------------------- exporters
    def snapshot(self) -> Dict[str, Dict]:
        """Pure-Python dict of everything registered — every leaf is an
        int/float/str, so ``json.dumps(snapshot())`` always works."""
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        hists: Dict[str, Dict[str, float]] = {}
        with self.lock:
            items = sorted(self._metrics.items())
        for name, m in items:
            if isinstance(m, Counter):
                counters.update(m.cells())
            elif isinstance(m, Gauge):
                gauges.update(m.cells())
            else:
                hists[name] = m.summary()
        return dict(counters=counters, gauges=gauges, histograms=hists)

    def to_prometheus(self) -> str:
        """Prometheus v0 text exposition.  Counters get the ``_total``
        suffix, histograms export as summaries (quantile-labelled
        samples plus ``_sum`` / ``_count``); every registered metric
        emits at least its ``# TYPE`` header and one sample."""
        out: List[str] = []
        with self.lock:
            items = sorted(self._metrics.items())
        for name, m in items:
            pname = _prom_name(name)
            if isinstance(m, Counter):
                out.append(f"# TYPE {pname}_total counter")
                cells = m.cells() or {name: 0}
                for cell, v in cells.items():
                    out.append(f"{_prom_sample(cell, '_total')} {_fmt(v)}")
            elif isinstance(m, Gauge):
                out.append(f"# TYPE {pname} gauge")
                cells = m.cells() or {name: 0}
                for cell, v in cells.items():
                    out.append(f"{_prom_sample(cell, '')} {_fmt(v)}")
            else:
                s = m.summary()
                out.append(f"# TYPE {pname} summary")
                for q in ("p50", "p90", "p99"):
                    if q in s:
                        out.append(f'{pname}{{quantile="0.{q[1:]}"}} '
                                   f"{_fmt(s[q])}")
                out.append(f"{pname}_sum {_fmt(s.get('sum', 0.0))}")
                out.append(f"{pname}_count {_fmt(s.get('count', 0))}")
        return "\n".join(out) + "\n"


def _prom_name(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def _prom_sample(cell: str, suffix: str) -> str:
    """Render one cell name (``a.b{k=v,...}`` or bare) as a Prometheus
    sample name with quoted label values."""
    if "{" not in cell:
        return _prom_name(cell) + suffix
    base, rest = cell.split("{", 1)
    labels = rest[:-1]
    quoted = ",".join(f'{k}="{v}"'
                      for k, v in (p.split("=", 1)
                                   for p in labels.split(",")))
    return f"{_prom_name(base)}{suffix}{{{quoted}}}"


def _fmt(v: float) -> str:
    return repr(int(v)) if float(v).is_integer() else repr(float(v))


_default_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every instrumented layer shares."""
    return _default_registry
