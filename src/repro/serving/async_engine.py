"""Async serving engine: continuous batching over the retrieval session.

``ServeEngine`` processes synchronous batches back-to-back; nothing it
reports reflects what a caller sees under load.  ``AsyncServeEngine``
models the real request lifecycle:

1. **submit** — callers enqueue ``(tree_ids, hashes)`` query groups from
   any thread (or via ``retrieve_async`` from an event loop) and get a
   future per request.
2. **coalesce** — a ``MicroBatcher`` collects arrivals until the batch
   is full or the oldest request has waited out the latency budget.
3. **dispatch** — the batch pads to a pow2 bucket (closed shape set, so
   the jitted step never recompiles after warmup) and launches on
   device.
4. **overlap** — while the batch is in flight, the maintenance pass
   (absorb → delta → compact → sort → stage changed bytes) runs on the
   host against the *pre-dispatch* state snapshot; the serving state is
   untouched.
5. **commit** — between batches, under the ``CommitPolicy`` (every N
   batches or plan age past deadline), the staged plan splices into the
   serving state in O(changed bytes).

Retrieval outputs (hit/locations/up/down) depend only on the bank
content, not on temperature or batch grouping, so answers are
bit-identical to the synchronous engine on the same request stream —
the equivalence gate in ``benchmarks/bench_async.py`` checks exactly
that.

Determinism hooks: the constructor takes a ``clock`` (tests inject a
fake), and :meth:`pump` drives one scheduling step inline without any
threads.  ``start()``/``stop()`` run the same logic on a scheduler
thread for real workloads.

Observability: every statistic lives in the process-wide
``repro.obs`` registry (``serve.*`` counters, all mutation under the
registry lock — the old ``AsyncStats`` dataclass was updated from the
scheduler thread, the prepare worker, *and* ``stop()`` without one);
the :attr:`stats` property stays as a compat shim, reconstructing an
``AsyncStats`` view from this engine's registry deltas.  Each launch
emits a ``serve.batch`` trace span (coalesce → pad → dispatch →
prepare → device_lookup → route_back) and ticks the session's
recompile sentinel, so a commit that leaks an unstable shape into the
hot path is counted (and, armed, fatal) rather than a silent ~650 ms
tail spike.  The scheduler's wait for work is the profiler annotation
``repro/serve.wait``.

Failure model (see README "Failure model" for the full contract):

* **admission control** — the request queue is bounded
  (``max_queue_requests``); a submit past the bound raises
  :class:`~repro.serving.errors.EngineOverloaded` instead of growing
  the queue (and the tail latency) without limit.
* **tenant isolation** — with a ``TenantRegistry`` attached to the
  session, each tenant gets a queue-share quota (``tenant_quota``,
  default an equal split of ``max_queue_requests``): one tenant's
  burst raises ``EngineOverloaded(tenant=...)`` for *that tenant only*
  while the global bound still protects the engine; the batcher
  coalesces tenant-fair (round-robin across tenants, per-tenant FIFO);
  a cold/offboarded tenant's submits shed with
  :class:`~repro.serving.errors.TenantEvicted`; and every batch span
  carries its tenants so a slow tenant is attributable from the
  metrics snapshot alone.
* **deadlines** — ``submit(..., timeout=s)`` stamps an absolute
  deadline; expired requests fail fast with
  :class:`~repro.serving.errors.DeadlineExceeded` at coalesce time
  (swept from the queue before every launch) and again at dispatch
  time, never occupying a batch slot or device work.
* **dispatch faults** — an exception while serving a batch fails that
  batch's futures and the engine keeps scheduling; it never kills the
  scheduler thread (counted as ``serve.batch_failures``).
* **maintenance faults** — prepare/commit exceptions are quarantined by
  the ``RestageCoordinator`` (plan dropped, shadow invalidated) and the
  engine keeps serving the last committed state; retries follow the
  breaker's backoff schedule and an open breaker degrades to serve-only
  mode (see :class:`~repro.core.maintenance.MaintenanceBreaker`).
* **shutdown** — ``stop()`` drains the queue (every outstanding future
  resolves — with a result, or with the failure that stopped it) and
  any submit afterwards raises
  :class:`~repro.serving.errors.EngineClosed` immediately.
"""
from __future__ import annotations

import asyncio
import dataclasses
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..obs import HotPathRecompileError
from .engine import RetrievalSession
from .errors import (DeadlineExceeded, EngineClosed, EngineOverloaded,
                     TenantEvicted)
from .faultinject import fault_point
from .scheduler import (CommitPolicy, MicroBatcher, PendingRetrieval,
                        bucket_shapes)


@dataclasses.dataclass
class RetrievalSlice:
    """Per-request view of a batched retrieval: row ``i`` answers the
    request's ``i``-th ``(tree_id, hash)`` query."""
    hit: np.ndarray
    locations: np.ndarray
    up: np.ndarray
    down: np.ndarray


@dataclasses.dataclass
class AsyncStats:
    """Compat view of one engine's serving counters.

    The counters themselves live in the ``repro.obs`` registry (shared,
    lock-protected); :attr:`AsyncServeEngine.stats` materializes this
    dataclass from the registry values minus the engine's
    construction-time baseline, so sequential engines in one process
    never see each other's counts."""
    batches: int = 0
    requests: int = 0
    queries: int = 0
    padded_queries: int = 0
    prepares: int = 0
    commits: int = 0
    bucket_histogram: Dict[int, int] = dataclasses.field(default_factory=dict)


class AsyncServeEngine:
    """Continuous-batching front end over a :class:`RetrievalSession`.

    ``engine`` is a ``ServeEngine`` (its ``.retrieval`` session is used)
    or a bare ``RetrievalSession``.  ``maintenance`` picks how the
    prepare phase runs: ``"inline"`` (default) runs it on the scheduler
    thread strictly under the in-flight batch — dispatch, prepare, then
    block on results; ``"thread"`` hands it to a background worker so
    even the host pass is off the serving thread; ``"off"`` disables
    background maintenance entirely (callers drive ``maintain()``
    themselves).
    """

    def __init__(self, engine, *, latency_budget: float = 2e-3,
                 max_batch: int = 256, min_bucket: int = 16,
                 commit_every: int = 4, commit_deadline: float = 0.25,
                 clock=time.monotonic, maintenance: str = "inline",
                 max_queue_requests: int = 1024,
                 default_timeout: Optional[float] = None,
                 tenant_quota=None):
        self.session: RetrievalSession = getattr(engine, "retrieval", engine)
        if maintenance not in ("inline", "thread", "off"):
            raise ValueError(f"unknown maintenance mode {maintenance!r}")
        if max_queue_requests < 1:
            raise ValueError("max_queue_requests must be >= 1")
        self.maintenance = maintenance
        self.clock = clock
        # admission control: pending *requests* (split chunks included)
        # above this bound shed with EngineOverloaded at submit time
        self.max_queue_requests = max_queue_requests
        # per-tenant queue share: an int (same quota for every tenant),
        # a {tenant: quota} dict, or None — an equal split of the global
        # bound across the registry's tenants when one is attached
        self.tenant_quota = tenant_quota
        # deadline stamped on submits that pass no explicit timeout
        self.default_timeout = default_timeout
        self.batcher = MicroBatcher(latency_budget=latency_budget,
                                    max_batch=max_batch,
                                    min_bucket=min_bucket)
        self.policy = CommitPolicy(commit_every=commit_every,
                                   deadline=commit_deadline)

        # registry-backed statistics: one counter per AsyncStats field,
        # every mutation under the registry lock (thread-safe across the
        # scheduler thread, the prepare worker, and stop())
        m = self.session.metrics
        self._c_batches = m.counter("serve.batches", "launched batches")
        self._c_requests = m.counter("serve.requests", "served requests")
        self._c_queries = m.counter("serve.queries", "true queries served")
        self._c_padded = m.counter("serve.padded_queries",
                                   "pad slots dispatched")
        self._c_prepares = m.counter("serve.prepares",
                                     "maintenance prepare passes")
        self._c_commits = m.counter("serve.commits",
                                    "maintenance commits applied")
        self._c_bucket = m.counter("serve.batch_bucket",
                                   "batches per pow2 bucket geometry")
        self._c_rejected = m.counter(
            "serve.rejected",
            "requests shed before dispatch, by reason "
            "(overload | deadline | closed)")
        self._c_batch_failures = m.counter(
            "serve.batch_failures",
            "batches whose dispatch/serve path raised (futures failed, "
            "engine kept scheduling)")
        self._c_tenant_queries = m.counter(
            "serve.tenant_queries", "true queries served per tenant")
        self._base = self._counter_values()

        # last maintenance exception the background lifecycle swallowed
        # (the coordinator's quarantine already counted + metered it)
        self.last_maintenance_error: Optional[BaseException] = None

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # thread-mode prepare handoff: scheduler stores the pre-dispatch
        # snapshot and sets the event; the worker runs the host pass.
        self._prep_event = threading.Event()
        self._prep_state = None
        self._prep_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- stats
    def _counter_values(self) -> Dict:
        return dict(batches=self._c_batches.value(),
                    requests=self._c_requests.value(),
                    queries=self._c_queries.value(),
                    padded_queries=self._c_padded.value(),
                    prepares=self._c_prepares.value(),
                    commits=self._c_commits.value(),
                    bucket=self._c_bucket.raw())

    @property
    def stats(self) -> AsyncStats:
        """This engine's counters as the legacy ``AsyncStats`` shape —
        registry values minus the construction-time baseline."""
        cur, base = self._counter_values(), self._base
        hist = {}
        for key, v in cur["bucket"].items():
            d = int(v - base["bucket"].get(key, 0))
            if d:
                hist[int(dict(key)["bucket"])] = d
        return AsyncStats(
            batches=int(cur["batches"] - base["batches"]),
            requests=int(cur["requests"] - base["requests"]),
            queries=int(cur["queries"] - base["queries"]),
            padded_queries=int(cur["padded_queries"]
                               - base["padded_queries"]),
            prepares=int(cur["prepares"] - base["prepares"]),
            commits=int(cur["commits"] - base["commits"]),
            bucket_histogram=dict(sorted(hist.items())))

    @property
    def hot_recompiles(self) -> int:
        """Serve-step recompiles the session's sentinel attributed to
        this process's hot path — 0 on a healthy padded path."""
        return self.session.sentinel.recompiles

    # ------------------------------------------------------------ intake
    @staticmethod
    def _fail(req: PendingRetrieval, exc: BaseException) -> None:
        """Resolve a request's future with ``exc`` unless the caller
        already cancelled it (never let a future hang)."""
        try:
            req.future.set_exception(exc)
        except InvalidStateError:
            pass

    @staticmethod
    def _resolve(req: PendingRetrieval, result: "RetrievalSlice") -> None:
        try:
            req.future.set_result(result)
        except InvalidStateError:
            pass

    def _quota_for(self, tenant: str) -> Optional[int]:
        """The queue-share quota (in pending requests) for one tenant —
        ``tenant_quota`` as given, or an equal split of the global bound
        across the registry's tenants; ``None`` disables the check."""
        tq = self.tenant_quota
        if tq is None:
            reg = self.session.tenants
            if reg is None:
                return None
            return max(1, self.max_queue_requests // max(1, len(reg.names)))
        if isinstance(tq, dict):
            q = tq.get(tenant)
            return None if q is None else int(q)
        return int(tq)

    def submit(self, tree_ids: Sequence[int], hashes: Sequence[int],
               *, timeout: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one retrieval request; the future resolves to a
        :class:`RetrievalSlice` once the batch it rides in completes.
        Thread-safe.

        ``timeout`` (seconds, default :attr:`default_timeout`) stamps an
        absolute deadline: a request still queued — or popped but not yet
        dispatched — past it fails with :class:`DeadlineExceeded`.

        ``tenant`` labels the request for quota accounting and trace
        attribution; when omitted and the session carries a
        ``TenantRegistry``, it resolves from the queried tree ids (a
        batch must not span tenants).  A non-resident tenant's submit
        raises :class:`TenantEvicted`; a submit past the tenant's queue
        share raises :class:`EngineOverloaded` *with that tenant* —
        other tenants keep submitting up to their own shares.

        Raises :class:`EngineClosed` after ``stop()``, and
        :class:`EngineOverloaded` when the bounded queue is full (the
        request is shed, never enqueued).  A request larger than
        ``max_batch`` splits into chunks that ride separate batches; the
        returned future aggregates the chunk slices in query order (any
        chunk failure fails the whole request).
        """
        if len(tree_ids) != len(hashes):
            raise ValueError("tree_ids and hashes length mismatch")
        reg = self.session.tenants
        if tenant is None and reg is not None:
            tenant = reg.tenant_of_batch(tree_ids)
        if tenant is not None and reg is not None \
                and not reg.resident(tenant):
            self._c_rejected.inc(reason="evicted", tenant=tenant)
            raise TenantEvicted(tenant)
        now = self.clock()
        timeout = self.default_timeout if timeout is None else timeout
        deadline_t = None if timeout is None else now + timeout
        mb = self.batcher.max_batch
        chunks = [PendingRetrieval(
            tree_ids=list(tree_ids[i:i + mb]),
            hashes=list(hashes[i:i + mb]),
            arrive_t=now, deadline_t=deadline_t, tenant=tenant)
            for i in range(0, max(len(hashes), 1), mb)]
        with self._work:
            if self._stop:
                self._c_rejected.inc(reason="closed")
                raise EngineClosed()
            room = self.max_queue_requests - len(self.batcher)
            if len(chunks) > room:
                # all-or-nothing: a partially enqueued split request
                # could never resolve its aggregate future coherently
                if tenant is None:
                    self._c_rejected.inc(reason="overload")
                else:
                    self._c_rejected.inc(reason="overload", tenant=tenant)
                raise EngineOverloaded(pending=len(self.batcher),
                                       limit=self.max_queue_requests)
            if tenant is not None:
                quota = self._quota_for(tenant)
                held = self.batcher.pending_for(tenant)
                if quota is not None and held + len(chunks) > quota:
                    # the tenant's share is exhausted — shed *its*
                    # traffic while the rest of the queue keeps admitting
                    self._c_rejected.inc(reason="overload", tenant=tenant)
                    raise EngineOverloaded(pending=held, limit=quota,
                                           tenant=tenant)
            for c in chunks:
                self.batcher.add(c)
            self._work.notify()
        if len(chunks) == 1:
            return chunks[0].future
        return self._aggregate([c.future for c in chunks])

    @staticmethod
    def _aggregate(parts: List[Future]) -> Future:
        """One future over a split request's chunk futures: resolves to
        the concatenated :class:`RetrievalSlice` (query order preserved)
        once every chunk lands; the first chunk failure fails it."""
        parent: Future = Future()
        remaining = [len(parts)]
        lock = threading.Lock()

        def _on_done(_f) -> None:
            with lock:
                remaining[0] -= 1
                if remaining[0] > 0:
                    return
            try:
                slices = [p.result() for p in parts]
                out = RetrievalSlice(
                    hit=np.concatenate([s.hit for s in slices]),
                    locations=np.concatenate(
                        [s.locations for s in slices]),
                    up=np.concatenate([s.up for s in slices]),
                    down=np.concatenate([s.down for s in slices]))
                parent.set_result(out)
            except InvalidStateError:                # pragma: no cover
                pass
            except BaseException as exc:
                try:
                    parent.set_exception(exc)
                except InvalidStateError:            # pragma: no cover
                    pass

        for p in parts:
            p.add_done_callback(_on_done)
        return parent

    async def retrieve_async(self, tree_ids: Sequence[int],
                             hashes: Sequence[int],
                             timeout: Optional[float] = None,
                             tenant: Optional[str] = None
                             ) -> RetrievalSlice:
        """Event-loop flavor of :meth:`submit`."""
        return await asyncio.wrap_future(
            self.submit(tree_ids, hashes, timeout=timeout, tenant=tenant))

    def warmup(self) -> int:
        """Pre-compile every bucket geometry the batcher can produce so
        the measured run never hits a compile.  Returns the number of
        shapes touched."""
        shapes = bucket_shapes(self.batcher.min_bucket,
                               self.batcher.max_batch)
        for s in shapes:
            hh, tid, _ = self.session.pad_queries([0], [0], pad_to=s)
            out = self.session.retrieve_dispatch(hh, tid)
            np.asarray(out.hit)
        self.session.harvest()
        # warmup compiles are intentional: baseline the sentinel here so
        # everything after counts as a hot-path recompile
        self.session.sentinel.rebaseline()
        self.session.compile_cache_size()
        return len(shapes)

    # ----------------------------------------------------- deterministic
    def _fail_expired(self, expired: List[PendingRetrieval],
                      now: float) -> None:
        """Fail swept requests with DeadlineExceeded (outside the engine
        lock — future callbacks may re-enter submit())."""
        for req in expired:
            self._c_rejected.inc(reason="deadline")
            self._fail(req, DeadlineExceeded(req.deadline_t, now))

    def pump(self, now: Optional[float] = None) -> bool:
        """Drive one scheduling step inline: sweep expired requests,
        launch a batch if one is due, then commit a staged plan if the
        policy says so.  Returns True when a batch launched.  This is the
        thread-free path the deterministic tests (and single-threaded
        callers) use."""
        explicit = now is not None
        now = self.clock() if now is None else now
        with self._lock:
            expired = self.batcher.expire(now)
            batch = self.batcher.pop() if self.batcher.ready(now) else []
        self._fail_expired(expired, now)
        launched = False
        if batch:
            launched = self._launch(batch, now)
        self._maybe_commit(now if explicit else self.clock())
        return launched

    def flush(self, now: Optional[float] = None) -> int:
        """Launch until the queue drains, ignoring the coalescing budget
        (used on stop so no future is left hanging — every outstanding
        future resolves with a result, a DeadlineExceeded for requests
        already past deadline, or the failure that broke its batch).
        Returns batches launched."""
        n = 0
        while True:
            t = self.clock() if now is None else now
            with self._lock:
                expired = self.batcher.expire(t)
                batch = self.batcher.pop()
            self._fail_expired(expired, t)
            if not batch:
                break
            if self._launch(batch, t):
                n += 1
        return n

    # ------------------------------------------------------------ batch
    def _launch(self, batch: List[PendingRetrieval], now: float) -> bool:
        """Serve one popped batch.  Returns True when it dispatched.

        Dispatch-time deadline check: requests that expired while the
        batch coalesced fail fast here and never pad into the bucket.  A
        raise anywhere in the serve path (injected ``dispatch`` faults
        included) fails this batch's futures and returns — the engine
        keeps scheduling; it never kills the scheduler thread."""
        arrive_t = batch[0].arrive_t
        live = [r for r in batch if not r.expired(now)]
        self._fail_expired([r for r in batch if r.expired(now)], now)
        if not live:
            return False
        batch = live
        tids: List[int] = []
        hhs: List[int] = []
        for req in batch:
            tids.extend(int(t) for t in req.tree_ids)
            hhs.extend(int(h) for h in req.hashes)
        bucket = self.batcher.bucket(batch)

        sp = self.session.tracer.span("serve.batch", bucket=bucket,
                                      requests=len(batch))
        # per-tenant attribution: which tenants ride in this batch — a
        # slow tenant is identifiable from the span stream alone
        tenants = sorted({r.tenant for r in batch if r.tenant is not None})
        if tenants:
            sp.set(tenant=",".join(tenants))
        # the oldest request's queue wait is the coalescing cost this
        # batch imposed — measured from its arrival stamp, not timed here
        sp.add_stage("coalesce", max(0.0, now - arrive_t))

        # pre-dispatch snapshot: the maintenance pass absorbs against
        # arrays that are already materialized, so it never blocks on the
        # batch we just launched; this batch's bumps harvest next cycle.
        snapshot = self.session.state
        try:
            with sp.stage("pad"):
                hh, tid, b = self.session.pad_queries(tids, hhs,
                                                      pad_to=bucket)
            with sp.stage("dispatch"):
                fault_point("dispatch")
                out = self.session.retrieve_dispatch(hh, tid)

            with sp.stage("prepare"):
                self._maybe_prepare(snapshot, now)

            # materializing blocks until the batch lands — everything
            # above ran under it.
            with sp.stage("device_lookup"):
                hit = np.asarray(out.hit)
                loc = np.asarray(out.locations)
                up = np.asarray(out.up)
                down = np.asarray(out.down)
                self.session.harvest()
        except HotPathRecompileError as exc:
            # armed sentinel at dispatch: fail loudly, don't contain
            sp.set(error=type(exc).__name__).end()
            raise
        except Exception as exc:
            # contain the blast radius to this batch: fail its futures,
            # count it, keep the scheduler alive on the last good state
            sp.set(error=type(exc).__name__).end()
            self._c_batch_failures.inc()
            for req in batch:
                self._fail(req, exc)
            return False

        with sp.stage("route_back"):
            off = 0
            for req in batch:
                k = len(req)
                self._resolve(req, RetrievalSlice(
                    hit=hit[off:off + k], locations=loc[off:off + k],
                    up=up[off:off + k], down=down[off:off + k]))
                off += k
        sp.set(queries=b).end()

        with self._lock:
            self.policy.note_batch()
        self._c_batches.inc()
        self._c_requests.inc(len(batch))
        self._c_queries.inc(b)
        self._c_padded.inc(bucket - b)
        self._c_bucket.inc(bucket=bucket)
        for req in batch:
            if req.tenant is not None:
                self._c_tenant_queries.inc(len(req), tenant=req.tenant)
        # post-batch sentinel tick: any serve-step compile after warmup
        # is attributed (and fatal when armed)
        self.session.observe()
        return True

    # ------------------------------------------------------ maintenance
    def _maybe_prepare(self, snapshot, now: float) -> None:
        coord = self.session.coord
        if self.maintenance == "off" or coord is None:
            return
        if coord.deferring:
            return
        # breaker gate: backoff after failures, serve-only while open —
        # the queued delta simply waits for the next allowed attempt
        if not coord.allow(now):
            return
        if self.session.pending_mutations() == 0 and not coord.dirty:
            return
        if self.maintenance == "thread":
            if not self._prep_event.is_set():
                self._prep_state = snapshot
                self._prep_event.set()
            return
        self._prepare(snapshot, now)

    def _prepare(self, snapshot, now: float) -> None:
        # coord.prepare (not session.prepare_maintenance): a pending plan
        # is the scheduler's to commit between batches — prepare must
        # never flush one from under it.
        coord = self.session.coord
        if coord is None or coord.deferring:
            return
        try:
            coord.prepare(snapshot, now=now)
        except Exception as exc:
            # the coordinator already quarantined (plan dropped, shadow
            # invalidated, breaker fed) — serving continues on the last
            # committed state and the breaker schedules the retry
            self.last_maintenance_error = exc
            return
        self._c_prepares.inc()
        with self._lock:
            if coord.deferring:
                self.policy.note_plan(now)

    def _maybe_commit(self, now: float) -> None:
        coord = self.session.coord
        if coord is None or not coord.deferring:
            return
        with self._lock:
            due = self.policy.due(now)
        if not due:
            return
        # non-blocking: if the prepare worker holds the lifecycle lock we
        # retry on the next pump rather than stalling the serving thread.
        try:
            applied = self.session.commit_maintenance(blocking=False,
                                                      now=now)
        except HotPathRecompileError:
            # the armed sentinel is a fail-loudly tripwire (CI/debug
            # mode), not a maintenance fault — never contain it
            raise
        except Exception as exc:
            # quarantined splice failure: the session still serves the
            # pre-commit state (the plan dropped before any donation) —
            # clear the policy, the breaker gates the re-prepare
            self.last_maintenance_error = exc
            with self._lock:
                self.policy.clear()
            return
        if applied:
            self._c_commits.inc()
            with self._lock:
                self.policy.clear()

    def _prep_loop(self) -> None:
        while True:
            self._prep_event.wait()
            if self._stop:
                return
            state, self._prep_state = self._prep_state, None
            if state is not None:
                self._prepare(state, self.clock())
            self._prep_event.clear()
            if self._stop:
                return

    # ---------------------------------------------------------- threads
    def start(self) -> None:
        """Spin up the scheduler thread (and, in ``"thread"`` maintenance
        mode, the prepare worker)."""
        if self._thread is not None:
            raise RuntimeError("already started")
        self._stop = False
        if self.maintenance == "thread":
            self._prep_thread = threading.Thread(
                target=self._prep_loop, name="cft-prepare", daemon=True)
            self._prep_thread.start()
        self._thread = threading.Thread(
            target=self._schedule_loop, name="cft-scheduler", daemon=True)
        self._thread.start()

    def _schedule_loop(self) -> None:
        while True:
            with self._work:
                if self._stop:
                    return
                now = self.clock()
                expired = self.batcher.expire(now)
                if not expired and not self.batcher.ready(now):
                    deadline = self.batcher.deadline()
                    timeout = None
                    if deadline is not None:
                        timeout = max(0.0, deadline - now)
                    if self.policy.armed:
                        # wake for the commit deadline even when idle
                        t2 = max(0.0, self.policy.deadline / 4)
                        timeout = t2 if timeout is None else min(timeout, t2)
                    # trace-only: with the batches' stages it covers this
                    # thread's track, so what neither covers is a stall
                    with self.session.tracer.annotate("serve.wait"):
                        self._work.wait(timeout=timeout)
                    if self._stop:
                        return
                    now = self.clock()
                    expired += self.batcher.expire(now)
                batch = self.batcher.pop() if self.batcher.ready(now) else []
            # future callbacks may re-enter submit(): resolve outside
            # the engine lock
            self._fail_expired(expired, now)
            if batch:
                self._launch(batch, now)
            self._maybe_commit(self.clock())

    def stop(self, commit: bool = True) -> None:
        """Stop the scheduler and drain: every outstanding future
        resolves (result, DeadlineExceeded, or its batch's failure —
        never left hanging), then any staged plan optionally commits.
        Afterwards :meth:`submit` raises :class:`EngineClosed`
        immediately.  Idempotent."""
        with self._work:
            self._stop = True
            self._work.notify_all()
        self._prep_event.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._prep_thread is not None:
            self._prep_thread.join()
            self._prep_thread = None
        self.flush()
        # belt-and-braces: a request the drain could not serve (e.g. its
        # batch kept failing) must still resolve — never leak a future
        with self._lock:
            leftovers = self.batcher.pop()
            while leftovers:
                for req in leftovers:
                    self._fail(req, EngineClosed(
                        "engine stopped before the request was served"))
                leftovers = self.batcher.pop()
        if commit and self.session.coord is not None \
                and self.session.coord.deferring:
            try:
                applied = self.session.commit_maintenance()
            except Exception as exc:
                self.last_maintenance_error = exc
                applied = False
            if applied:
                self._c_commits.inc()
                with self._lock:
                    self.policy.clear()

    def close(self) -> None:
        """Alias for :meth:`stop` — the resource-style name."""
        self.stop()

    def __enter__(self) -> "AsyncServeEngine":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
