"""The benchmark harness: one call runs one cell.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* the configuration's file (``configs/<config>.json``);
* the traffic mix (``traffic/<traffic>.json``), which names its general
  generator (``traffic/<generator>.py``) and the entry that the timed
  window drives (``entries/<entry>.py``);
* one reader per per-layer metric (``metrics/<metric>.py``).

A run: check the devices, set up the system under test and warm every
shape its traffic uses (``setup_s``, from process start), measure for
``seconds`` (with the profiler on when ``trace``), read the device's
memory peak, free the program's state, compare what the timed path
produced with the plain reference, and return the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
PROBE_KERNEL = "cuckoo_probe"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: the
    run is correct when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Window:
    """What an entry's timed window gives back."""
    seconds: float                      # measured window, host clock
    attempted: int
    failed: int
    e2e: Dict[str, float]               # end-to-end metrics by name
    stats: Dict[str, float]             # raw numbers for per-layer readers
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    cell: dict
    config: dict
    traffic: dict
    window: Window
    trace: Optional[object]             # trace_reduce.TraceSummary
    window_s: float                     # traced window, host clock
    peak: dict


@dataclasses.dataclass
class Context:
    """What an entry gets: the cell's files, the seed, and switches."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    fault: Optional[str] = None         # fault tests plant a broken path

    @property
    def key_seed(self) -> int:
        """A 32-bit seed for JAX's generator, drawn from ``--seed``."""
        return int(np.random.SeedSequence(self.seed).generate_state(1)[0])

    def annotate(self, name: str):
        from trace_reduce import annotation
        return annotation(name, self.trace)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(workload: str, bench: Optional[dict] = None):
    """The cell's entry in BENCHMARK.json, its configuration and traffic
    files, and the metrics it reports."""
    bench = bench or spec()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, confs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    layer = [m for m in bench["per_layer"] if mine(m)]
    return cell, config, traffic, e2e, layer


def configure_jax() -> None:
    """The persistent compile cache as the program's own entry points set
    it (``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR``, or
    else a fixed path in the checkout.  JAX's floors stay as they are, so
    a program that compiles in under a second is compiled again in every
    run's set-up, as in a deployment; only the first run of a cell
    compiles the rest.  The window uses no cache at all
    (``no_persistent_cache``)."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the checkout's own directory keeps every program: a size limit
        # from the environment, meant for a shared cache, would evict
        # this cell's programs as they are written
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_max_size", -1)


@contextlib.contextmanager
def no_persistent_cache():
    """Neither read nor write the persistent compile cache.  The window
    runs under it: whatever the program compiles in the window is
    compiled there, as in a deployment whose cache does not hold it.
    Under JAX's 1 s floor a compile that happened to take longer would be
    written, and every later run of the checkout would load it instead."""
    import jax
    from jax._src import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def require_chips(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def quantile(values, q: float) -> float:
    """``q``-quantile (0..1) of all values, linear between order
    statistics."""
    v = np.asarray(values, np.float64)
    return float(np.quantile(v, q)) if v.size else math.nan


# a sibling process that only sleeps and notes when it woke late: a
# pause it sees too stopped the whole machine, not just this process
PAUSE_WATCH = """
import signal, sys, time
gaps, stop = [], []
signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
print("ready", flush=True)
last = time.monotonic()
while not stop:
    time.sleep(0.002)
    now = time.monotonic()
    if now - last > 0.05:
        gaps.append(now - last)
    last = now
print(len(gaps), max(gaps, default=0.0), flush=True)
"""


class HostWatch:
    """What the host did inside the window besides the work: Python's
    garbage-collector pauses by generation, the pauses over 50 ms that a
    sibling process saw (the whole machine standing still), and how many
    of the backend compilations JAX reports were loads from the
    persistent compile cache.  Read into the run's notes, never into a
    metric."""

    _cache_hits = 0
    _listening = False

    def __init__(self):
        self.pauses: Dict[int, List[float]] = {0: [], 1: [], 2: []}
        self._t0 = None

    @classmethod
    def _on_event(cls, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cls._cache_hits += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self._t0)

    def __enter__(self):
        import gc
        from jax import monitoring
        if not HostWatch._listening:
            monitoring.register_event_listener(HostWatch._on_event)
            HostWatch._listening = True
        self._hits0 = HostWatch._cache_hits
        gc.callbacks.append(self._on_gc)
        self.machine = None
        self._sibling = subprocess.Popen(
            [sys.executable, "-c", PAUSE_WATCH], stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        self._sibling.stdout.readline()
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._on_gc)
        self.cache_hits = HostWatch._cache_hits - self._hits0
        sib = self._sibling
        sib.terminate()
        try:
            count, longest = sib.communicate(timeout=10)[0].split()
            self.machine = (int(count), float(longest))
        except (subprocess.TimeoutExpired, ValueError):
            sib.kill()
            sib.communicate()

    def note(self) -> str:
        parts = []
        for g, p in self.pauses.items():
            ms = np.asarray(p) * 1e3
            parts.append(f"gen{g} {ms.size}" + (
                f" (longest {ms.max():.1f} ms, total {ms.sum():.1f} ms)"
                if ms.size else ""))
        machine = "not read" if self.machine is None else (
            f"{self.machine[0]} (longest {self.machine[1] * 1e3:.1f} ms)")
        return (f"gc pauses {', '.join(parts)}; machine pauses over 50 ms "
                f"seen by a sibling process {machine}; persistent-cache "
                f"loads {self.cache_hits}")


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             config_overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             fault: Optional[str] = None,
             log: Callable[[str], None] = lambda s: None) -> dict:
    """Run one cell and return its result line as a dict.

    ``require_tpu=False``, the overrides and ``fault`` are for the
    harness's own tests: they run the rest of a run on the CPU at a small
    size, with the timed path intact or broken on purpose."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell, config, traffic, e2e_specs, layer_specs = resolve(workload)
    config = _merge(config, config_overrides or {})
    traffic = _merge(traffic, traffic_overrides or {})
    configure_jax()
    import jax
    if require_tpu:
        devs = require_chips(int(cell["chips"]))
    else:
        devs = jax.devices()
    devs = devs[:int(cell["chips"])]
    peaks = load_json(os.path.join(HERE, "peaks.json"))["kinds"]
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    # off the chip (the harness's own tests) the readers still need a
    # row: the first one stands in, and nothing read so is a device number
    peak = peaks.get(kind) or next(iter(peaks.values()))

    ctx = Context(cell=cell, config=config, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, fault=fault)
    entry = load_module(os.path.join(
        HERE, "entries", traffic["entry"] + ".py")).Entry(ctx)
    entry.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    summary, traced_s = None, 0.0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # level 1 keeps the benchmark's annotations and drops the
        # runtime's own host events, which only swell the trace
        opts.host_tracer_level = 1
        t0 = time.perf_counter()
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with HostWatch() as host, no_persistent_cache():
            win = entry.window(seconds)
    finally:
        if trace:
            # the traced window ends here; writing the trace out is not
            # part of it
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
    win.notes.append(host.note())
    mem = memory_peak(devs)
    entry.release()
    checks = entry.verify()
    if trace:
        from trace_reduce import find_xplane, reduce_trace
        size = os.path.getsize(find_xplane(TRACE_DIR))
        summary = reduce_trace(TRACE_DIR, kernels=(PROBE_KERNEL,))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        win.notes.append(f"trace {size} bytes, reduced and deleted")

    metrics: Dict[str, dict] = {}
    if not trace:
        for m in e2e_specs:
            value = setup_s if m["name"] == "setup_s" else \
                win.e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reading = Reading(cell=cell, config=config, traffic=traffic,
                          window=win, trace=summary, window_s=traced_s,
                          peak=peak)
        for m in layer_specs:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": all(c.ok for c in checks),
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = traced_s
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps()}
    result["notes"] = win.notes
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and \
            isinstance(base.get(k), dict) else v
    return out
