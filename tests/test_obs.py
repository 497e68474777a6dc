"""Observability layer: registry semantics, exporters, tracing, the
recompile sentinel (injected shape-instability + healthy padded churn),
and the pure-JSON packing_stats contract."""
import json
import re
import threading

import numpy as np
import pytest

import repro.core.maintenance as maintenance_mod
from repro.core import (CFTDeviceState, MaintenanceEngine,
                        ShardedMaintenanceEngine, build_bank, build_forest,
                        estimate_fpr)
from repro.core import hashing
from repro.obs import (HotPathRecompileError, MetricsRegistry,
                       RecompileSentinel, SpanLog, Tracer, finished_spans,
                       get_registry, state_shapes)
from repro.serving import AsyncServeEngine, RetrievalSession


def _forest(num_trees=4, entities_per_tree=10):
    return build_forest(
        [[(f"root {t}", f"entity {t}_{i}") for i in range(entities_per_tree)]
         for t in range(num_trees)])


def _session(maint=True, forest=None):
    forest = forest or _forest()
    bank = build_bank(forest)
    session = RetrievalSession()
    session.attach(CFTDeviceState.from_bank(bank, forest))
    if maint:
        session.attach_maintenance(MaintenanceEngine(bank), forest)
    return forest, bank, session


# --------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("t.count")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    c.inc(bucket=32)
    c.inc(2, bucket=64)
    assert c.value(bucket=32) == 1 and c.value(bucket=64) == 2
    assert c.value() == 5                      # unlabeled cell untouched

    g = r.gauge("t.gauge")
    g.set(7)
    g.set(3)
    g.add(2)
    assert g.value() == 5

    h = r.histogram("t.lat_s")
    for v in (1e-4, 2e-4, 4e-4, 1e-3, 1e-2):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["min"] == pytest.approx(1e-4)
    assert s["max"] == pytest.approx(1e-2)
    # log2 buckets: quantiles carry <= 2x resolution around the truth
    assert 2e-4 <= s["p50"] <= 8e-4
    assert s["p99"] == pytest.approx(1e-2)

    # get-or-create: same name -> same object; kind conflicts fail loudly
    assert r.counter("t.count") is c
    with pytest.raises(TypeError):
        r.gauge("t.count")


def test_disabled_registry_mutates_nothing():
    r = MetricsRegistry(enabled=False)
    c = r.counter("t.c")
    c.inc(100)
    r.gauge("t.g").set(5)
    r.histogram("t.h").observe(1.0)
    assert c.value() == 0
    snap = r.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"]["t.h"]["count"] == 0
    # spans become the shared no-op while disabled
    t = Tracer(r)
    sp = t.span("t.span")
    with sp.stage("x"):
        pass
    sp.end()
    assert finished_spans("t.span", 10, r) == []
    r.enable()
    c.inc()
    assert c.value() == 1


def test_registry_thread_safety_exact_totals():
    r = MetricsRegistry()
    c = r.counter("t.racy")
    h = r.histogram("t.racy_s")
    n_threads, per = 8, 2000

    def worker():
        for _ in range(per):
            c.inc()
            h.observe(1e-3)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == n_threads * per
    assert h.summary()["count"] == n_threads * per


def test_snapshot_json_round_trip_and_prometheus_completeness():
    r = MetricsRegistry()
    r.counter("serve.batches").inc(3)
    r.counter("serve.batch_bucket").inc(bucket=32)
    r.gauge("serve.compile_cache_size").set(5)
    r.histogram("serve.dispatch_s").observe(2e-3)
    r.histogram("t.empty")                     # registered, no samples

    snap = r.snapshot()
    assert snap == json.loads(json.dumps(snap))   # round-trips untouched

    text = r.to_prometheus()
    # every registered metric emits (counter -> _total, labels quoted)
    assert "serve_batches_total 3" in text
    assert 'serve_batch_bucket_total{bucket="32"} 1' in text
    assert "serve_compile_cache_size 5" in text
    assert 'serve_dispatch_s{quantile="0.50"}' in text
    assert "serve_dispatch_s_count 1" in text
    assert "t_empty_count 0" in text
    for name in r.names():
        assert name.replace(".", "_") in text


# ---------------------------------------------------------------- tracing

def test_tracer_spans_aggregate_into_histograms():
    r = MetricsRegistry()
    t = Tracer(r)
    with t.span("serve.batch", bucket=32) as sp:
        with sp.stage("dispatch"):
            pass
        sp.add_stage("coalesce", 0.25)
    spans = finished_spans("serve.batch", 10, r)
    assert len(spans) == 1
    assert spans[0]["attrs"] == {"bucket": 32}
    assert [s["stage"] for s in spans[0]["stages"]] == ["dispatch",
                                                        "coalesce"]
    assert r.histogram("trace.serve.batch").summary()["count"] == 1
    s = r.histogram("trace.serve.batch.coalesce").summary()
    assert s["count"] == 1 and s["min"] == pytest.approx(0.25)
    assert json.dumps(spans)                   # ring entries are JSON


def test_span_exception_path_records_stage_and_propagates():
    """A raise inside a staged span (the dispatch-fault path) must not
    swallow the exception — and the stage/span histograms still record,
    so fault-window latencies show up in the same telemetry as healthy
    ones."""
    r = MetricsRegistry()
    t = Tracer(r)
    with pytest.raises(KeyError):
        with t.span("serve.batch", bucket=8) as sp:
            with sp.stage("dispatch"):
                raise KeyError("boom")
    assert r.histogram("trace.serve.batch").summary()["count"] == 1
    assert r.histogram("trace.serve.batch.dispatch").summary()["count"] == 1
    spans = finished_spans("serve.batch", 10, r)
    assert len(spans) == 1
    assert [s["stage"] for s in spans[0]["stages"]] == ["dispatch"]
    # an explicit error attribute (what _launch sets) rides the ring
    with pytest.raises(ValueError):
        with t.span("serve.batch") as sp:
            try:
                raise ValueError("boom")
            except ValueError as exc:
                sp.set(error=type(exc).__name__)
                raise
    assert finished_spans("serve.batch", 1, r)[-1]["attrs"] == \
        {"error": "ValueError"}


def test_disabled_registry_exception_path_stays_silent():
    """With metrics off, the error path must cost nothing and record
    nothing — while still re-raising."""
    r = MetricsRegistry(enabled=False)
    t = Tracer(r)
    c = r.counter("t.err")
    with pytest.raises(ValueError):
        with t.span("serve.batch") as sp:
            with sp.stage("dispatch"):
                c.inc(reason="x")              # the error-path counter
                raise ValueError("boom")
    assert finished_spans("serve.batch", 10, r) == []
    assert c.value(reason="x") == 0
    snap = r.snapshot()
    assert snap["counters"] == {} and "trace.serve.batch" \
        not in snap["histograms"]


def test_span_parent_is_the_innermost_open_span_on_its_thread():
    r = MetricsRegistry()
    t = Tracer(r)
    seen = {}
    with t.span("outer") as outer:
        with t.span("middle") as middle:
            with t.span("inner") as inner:
                pass
            # a span on another thread never sees this thread's spans
            th = threading.Thread(
                target=lambda: seen.update(other=t.span("other").end()))
            th.start()
            th.join(timeout=10)
            assert not th.is_alive()
        with t.span("sibling") as sibling:
            pass
    assert outer.parent is None
    assert middle.parent == outer.id and sibling.parent == outer.id
    assert inner.parent == middle.id
    assert seen["other"].parent is None
    by_name = {name: finished_spans(name, 1, r)[0]
               for name in ("outer", "middle", "inner")}
    assert by_name["inner"]["parent"] == by_name["middle"]["id"]
    assert by_name["outer"]["t0"] <= by_name["inner"]["t0"] \
        <= by_name["inner"]["t1"] <= by_name["outer"]["t1"]


def test_compile_work_is_attributed_to_the_open_span():
    """A fresh jitted function compiled inside a span gives the span its
    tracing, lowering and backend-compile seconds; the process-wide
    sums move by exactly what the spans got, nested traces counted
    once."""
    import jax
    import jax.numpy as jnp

    reg = get_registry()
    names = {"trace_s": "xla.trace_s", "lower_s": "xla.lower_s",
             "compile_s": "xla.compile_s"}
    t = Tracer()
    before = {k: reg.histogram(h).summary()["sum"] for k, h in names.items()}
    got = {k: 0.0 for k in names}
    for scale in (2.0, 3.0):
        @jax.jit
        def inner(x):
            return jnp.sin(x) * scale

        @jax.jit
        def outer(x):
            return inner(x) + inner(x * 2)

        with t.span("t.compile") as sp:
            outer(jnp.ones(8)).block_until_ready()
        rec = finished_spans("t.compile", 1)[0]
        for k in names:
            assert sp.attrs[k] > 0, k
            got[k] += sp.attrs[k]
        assert sum(sp.attrs[k] for k in names) <= rec["t1"] - rec["t0"]
    for k, h in names.items():
        moved = reg.histogram(h).summary()["sum"] - before[k]
        assert moved == pytest.approx(got[k], rel=1e-9), k


def test_rag_spans_reach_the_profiler_trace(tmp_path):
    """Under the profiler, ``repro/rag.retrieve`` and its stages are
    host events of the trace; the answer's retrieve links to it."""
    import glob
    import jax
    from repro.data import hospital_corpus
    from repro.serving import RAGPipeline

    rag = RAGPipeline(hospital_corpus(num_trees=6), None, use_bank=True)
    query = f"Where does {rag.forest.entity_names[5]} report?"
    rag.retrieve(query)                        # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        rag.answer(query)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes for ln in p.lines
             for e in ln.events if e.name.startswith("repro/")}
    assert {"repro/rag.answer", "repro/rag.retrieve",
            "repro/rag.retrieve/recognise", "repro/rag.retrieve/device",
            "repro/rag.retrieve/harvest", "repro/rag.retrieve/fetch",
            "repro/rag.retrieve/render"} <= names
    answer, = finished_spans("rag.answer", 1)
    retrieve, = finished_spans("rag.retrieve", 1)
    assert retrieve["parent"] == answer["id"]
    assert [s["stage"] for s in retrieve["stages"]] == [
        "recognise", "device", "harvest", "fetch", "render"]


class _CountingAnnotation:
    """Stands in for ``TraceAnnotation`` as if a profiler trace ran."""
    made = 0

    def __init__(self, name):
        type(self).made += 1

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


def test_disabled_registry_constructs_no_annotation(monkeypatch):
    import repro.obs.tracing as tracing_mod
    monkeypatch.setattr(tracing_mod, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = 0
    r = MetricsRegistry(enabled=False)
    t = Tracer(r)
    with t.span("serve.batch") as sp:
        with sp.stage("pad"):
            pass
    with t.annotate("serve.wait"):
        pass
    assert _CountingAnnotation.made == 0
    r.enable()
    with t.span("serve.batch") as sp:
        with sp.stage("pad"):
            pass
    with t.annotate("serve.wait"):
        pass
    assert _CountingAnnotation.made == 3
    # the trace-only annotation never enters the span log
    assert len(finished_spans("serve.batch", 10, r)) == 1
    assert finished_spans("serve.wait", 10, r) == []


def test_no_annotation_while_no_trace_runs(monkeypatch):
    """Untraced, an enabled registry still records spans but builds no
    profiler annotation."""
    import repro.obs.tracing as tracing_mod

    class Untraced(_CountingAnnotation):
        @staticmethod
        def is_enabled():
            return False
    monkeypatch.setattr(tracing_mod, "TraceAnnotation", Untraced)
    _CountingAnnotation.made = 0
    r = MetricsRegistry()
    t = Tracer(r)
    with t.span("rag.retrieve") as sp:
        with sp.stage("device"):
            pass
    with t.annotate("serve.wait"):
        pass
    assert _CountingAnnotation.made == 0
    assert len(finished_spans("rag.retrieve", 10, r)) == 1
    assert finished_spans("serve.wait", 10, r) == []


def test_span_log_keeps_the_newest_of_each_name():
    r = MetricsRegistry()
    r.spans = SpanLog(capacity=8)
    t = Tracer(r)
    for i in range(5):
        t.span("rag.retrieve", i=i).end()
    for i in range(100):
        t.span("serve.batch", i=i).end()
    kept = finished_spans("rag.retrieve", 5, r)
    assert [d["attrs"]["i"] for d in kept] == list(range(5))
    assert [d["attrs"]["i"] for d in finished_spans("rag.retrieve", 2, r)] \
        == [3, 4]
    flood = finished_spans("serve.batch", 100, r)
    assert [d["attrs"]["i"] for d in flood] == list(range(92, 100))
    assert finished_spans("serve.generate", 3, r) == []


def test_lowered_retrieval_step_carries_no_instrumentation(monkeypatch):
    """Spans live outside what JAX traces: the lowered retrieval step is
    the same text with the registry on and off, names no ``repro/``
    annotation, and lowering it builds no profiler annotation even
    inside an open span."""
    import functools
    import jax
    import jax.numpy as jnp
    import repro.obs.tracing as tracing_mod
    from repro.core import retrieve_device
    from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto

    forest, bank, _ = _session(maint=False)
    state = CFTDeviceState.from_bank(bank, forest)
    hashes = jnp.asarray(hashing.hash_entities(
        [forest.entity_names[i] for i in range(1, 9)]))
    trees = jnp.asarray(np.arange(8, dtype=np.int32) % 4)
    reg = get_registry()
    t = Tracer()

    def lowered():
        step = jax.jit(functools.partial(
            retrieve_device, lookup_fn=cuckoo_lookup_arena_auto))
        with t.span("rag.retrieve") as sp:
            with sp.stage("device"):
                _CountingAnnotation.made = 0
                monkeypatch.setattr(tracing_mod, "TraceAnnotation",
                                    _CountingAnnotation)
                low = step.lower(state, hashes, trees)
                monkeypatch.undo()
                assert _CountingAnnotation.made == 0
        # named scopes show in the locations, beside source files
        scopes = re.sub(r'"[^"]*\.py":[^)]*', '"file"',
                        low.as_text(debug_info=True))
        assert "repro/" not in scopes
        return low.as_text()

    on = lowered()
    reg.disable()
    try:
        off = lowered()
    finally:
        reg.enable()
    assert on == off


# --------------------------------------------------------------- sentinel

def test_sentinel_watch_check_rebaseline_and_arm():
    import jax
    import jax.numpy as jnp
    r = MetricsRegistry()
    s = RecompileSentinel(r)
    f = jax.jit(lambda x: x * 2)
    if not s.watch("f", f):
        pytest.skip("backend does not expose the jit cache size")
    f(jnp.ones(2))
    assert s.check() == {"f": 1}
    assert s.recompiles == 1
    assert s.check() == {}                     # re-baselined
    s.rebaseline()
    f(jnp.ones(3))
    s.arm()
    with pytest.raises(HotPathRecompileError):
        s.check()
    s.disarm()
    # an expected geometry change forgives exactly one growth
    s.allow_next()
    f(jnp.ones(4))
    assert s.check() == {}
    assert s.recompiles == 2                   # the armed one counted too
    f(jnp.ones(5))
    assert s.check() == {"f": 1}               # forgiveness was one-shot


def test_sentinel_commit_shape_classification():
    r = MetricsRegistry()
    s = RecompileSentinel(r)
    a = {"fingerprints": (8, 4), "csr_offsets": (256,)}
    b = {"fingerprints": (16, 4), "csr_offsets": (256,)}
    assert s.note_commit("delta", a, dict(a)) == []
    assert s.note_commit("segment", a, b) == ["fingerprints"]
    assert s.note_commit("delta", a, b) == ["fingerprints"]   # counts only
    c = r.counter("maint.commit_shape_changes")
    assert c.value(expected="true", kind="segment") == 1
    assert c.value(expected="false", kind="delta") == 1
    s.arm()
    with pytest.raises(HotPathRecompileError):
        s.note_commit("delta", a, b)
    s.note_commit("full", a, b)                # expected kinds never raise


def _pump_through_commit(eng, session, reqs, now):
    """Two deterministic pumps: prepare under batch 1, commit after
    batch 2 (commit_every=2)."""
    eng.submit(*reqs[0]); now[0] += 1; eng.pump(now[0])
    assert session.coord.deferring
    eng.submit(*reqs[1]); now[0] += 1; eng.pump(now[0])
    assert not session.coord.deferring


def test_sentinel_catches_unpadded_csr_commit(monkeypatch):
    """The PR 6 pathology, injected: bypassing pad_csr stages a CSR at
    its raw length, the delta commit changes the committed shape, and
    the next dispatch recompiles the hot path — all of which the
    sentinel must report."""
    forest, bank, session = _session(maint=True)
    hashes = hashing.hash_entities(forest.entity_names)
    reqs = [([int(bank.row_tree[i])], [int(hashes[bank.row_entity[i]])])
            for i in range(4)]
    now = [0.0]
    eng = AsyncServeEngine(session, latency_budget=0.0, max_batch=32,
                           min_bucket=4, commit_every=2, commit_deadline=1e9,
                           clock=lambda: now[0], maintenance="inline")
    eng.warmup()
    if session.compile_cache_size() < 0:
        pytest.skip("backend does not expose the jit cache size")
    assert eng.hot_recompiles == 0

    monkeypatch.setattr(
        maintenance_mod, "pad_csr",
        lambda off, nodes, chunk=256: (np.asarray(off, np.int32),
                                       np.asarray(nodes, np.int32)))
    session.maint.queue_insert(0, "unpadded entity", [1])
    before = state_shapes(session.state)
    _pump_through_commit(eng, session, reqs, now)
    after = state_shapes(session.state)
    assert before["csr_nodes"] != after["csr_nodes"]   # the injected leak
    c = session.metrics.counter("maint.commit_shape_changes")
    assert c.value(expected="false", kind="delta") >= 1

    # the next batch pays the recompile; the sentinel attributes it
    eng.submit(*reqs[2]); now[0] += 1; eng.pump(now[0])
    assert eng.hot_recompiles >= 1


def test_armed_sentinel_fails_loudly_on_unpadded_commit(monkeypatch):
    forest, bank, session = _session(maint=True)
    hashes = hashing.hash_entities(forest.entity_names)
    reqs = [([int(bank.row_tree[i])], [int(hashes[bank.row_entity[i]])])
            for i in range(4)]
    now = [0.0]
    eng = AsyncServeEngine(session, latency_budget=0.0, max_batch=32,
                           min_bucket=4, commit_every=2, commit_deadline=1e9,
                           clock=lambda: now[0], maintenance="inline")
    eng.warmup()
    monkeypatch.setattr(
        maintenance_mod, "pad_csr",
        lambda off, nodes, chunk=256: (np.asarray(off, np.int32),
                                       np.asarray(nodes, np.int32)))
    session.sentinel.arm()
    session.maint.queue_insert(0, "loud entity", [1])
    with pytest.raises(HotPathRecompileError):
        _pump_through_commit(eng, session, reqs, now)


def test_padded_churn_never_recompiles():
    """The healthy path: inserts/deletes through the normal pad_csr
    staging keep every committed shape stable — zero hot-path
    recompiles across the whole churn schedule."""
    forest, bank, session = _session(maint=True)
    hashes = hashing.hash_entities(forest.entity_names)
    nrows = len(bank.row_entity)
    reqs = [([int(bank.row_tree[i % nrows])],
             [int(hashes[bank.row_entity[i % nrows]])])
            for i in range(12)]
    now = [0.0]
    eng = AsyncServeEngine(session, latency_budget=0.0, max_batch=32,
                           min_bucket=4, commit_every=2, commit_deadline=1e9,
                           clock=lambda: now[0], maintenance="inline")
    eng.warmup()
    if session.compile_cache_size() < 0:
        pytest.skip("backend does not expose the jit cache size")
    baseline = session.compile_cache_size()
    session.sentinel.arm()                     # any recompile is fatal
    for i, (t, h) in enumerate(reqs):
        if i % 3 == 0:
            session.maint.queue_insert(i % 4, f"churn {i}", [1])
        if i % 3 == 2 and i >= 2:              # delete what i-2 inserted
            session.maint.queue_delete((i - 2) % 4, f"churn {i - 2}")
        eng.submit(t, h)
        now[0] += 1
        eng.pump(now[0])
    assert eng.stats.commits >= 2
    assert eng.hot_recompiles == 0
    assert session.compile_cache_size() == baseline


# ----------------------------------------------------------- packing_stats

def _assert_pure_json(stats):
    assert json.loads(json.dumps(stats)) == stats
    for key in ("load", "tree_nb", "ideal_nb", "est_fpr"):
        assert isinstance(stats[key], list)
        assert all(type(x) in (int, float) for x in stats[key])
    for key in ("arena_rows", "ideal_rows", "dead_rows"):
        assert type(stats[key]) is int
    assert type(stats["overprovision"]) is float


def test_packing_stats_pure_python_replicated_and_sharded():
    forest = _forest(num_trees=6)
    bank = build_bank(forest)
    eng = MaintenanceEngine(bank)
    stats = eng.packing_stats()
    _assert_pure_json(stats)
    assert len(stats["est_fpr"]) == bank.num_trees

    sbank = build_bank(_forest(num_trees=6)).shard(2)
    seng = ShardedMaintenanceEngine(sbank)
    sstats = seng.packing_stats()
    _assert_pure_json(sstats)
    assert len(sstats["load"]) == 6            # global tree order
    assert sstats["arena_rows"] == stats["arena_rows"]


def test_estimate_fpr_formula_and_monotonicity():
    assert estimate_fpr(0.0, 4) == 0.0
    lo, hi = estimate_fpr(0.25, 4), estimate_fpr(0.95, 4)
    assert 0.0 < lo < hi < 1.0
    # matches the closed form at a spot value
    p = 1.0 / (2 ** hashing.FP_BITS - 1)
    want = 1.0 - (1.0 - p) ** (2 * 4 * 0.5)
    assert estimate_fpr(0.5, 4) == pytest.approx(want)
    arr = estimate_fpr(np.array([0.1, 0.9]), 4)
    assert arr.shape == (2,) and arr[0] < arr[1]
    # per-tree estimates ride in packing_stats (the ROADMAP's surface)
    bank = build_bank(_forest())
    stats = MaintenanceEngine(bank).packing_stats()
    np.testing.assert_allclose(
        stats["est_fpr"], estimate_fpr(bank.load_factors, bank.slots))


# ----------------------------------------------------- engine integration

def test_async_engine_stats_are_registry_deltas():
    """Two sequential engines on the shared process registry must not
    see each other's counts (the compat shim subtracts its baseline)."""
    forest, bank, session = _session(maint=False)
    now = [0.0]
    eng1 = AsyncServeEngine(session, latency_budget=0.0, max_batch=32,
                            min_bucket=4, clock=lambda: now[0],
                            maintenance="off")
    hashes = hashing.hash_entities(forest.entity_names)
    req = ([int(bank.row_tree[0])], [int(hashes[bank.row_entity[0]])])
    eng1.submit(*req); now[0] += 1; eng1.pump(now[0])
    assert eng1.stats.batches == 1 and eng1.stats.requests == 1

    eng2 = AsyncServeEngine(session, latency_budget=0.0, max_batch=32,
                            min_bucket=4, clock=lambda: now[0],
                            maintenance="off")
    assert eng2.stats.batches == 0             # baseline excludes eng1
    eng2.submit(*req); now[0] += 1; eng2.pump(now[0])
    assert eng2.stats.batches == 1
    assert eng1.stats.batches == 2             # eng1 keeps counting on
    assert eng2.stats.bucket_histogram == {4: 1}
    # the registry itself carries the process-wide compile gauge
    assert (get_registry().gauge("serve.compile_cache_size").value()
            == session.compile_cache_size())
