"""Open-loop retrieval requests through ``AsyncServeEngine.submit``.

The engine serves a ``RetrievalSession`` attached the way ``RAGPipeline``
attaches its own: the bank's state on the device, the Pallas arena probe
(``cuckoo_lookup_arena_auto``) and the host maintenance engine.  Each
request is timed from its scheduled arrival to the moment its future
resolves, so a stall counts against every request queued behind it; a
request that is shed, fails or is never answered counts at the close of
the wait for answers (``drain_s`` past the window).
"""
from __future__ import annotations

import time

import numpy as np

from entries.common import (counters, dataset, delta, generator,
                            probe_checks)
from harness import Check, Window, quantile


class Entry:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self) -> None:
        import jax
        from repro.core import CFTDeviceState, MaintenanceEngine, build_bank
        from repro.core import build_forest
        from repro.kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto
        from repro.serving import AsyncServeEngine, RetrievalSession

        ctx = self.ctx
        corpus, self.ref = dataset(ctx.config)
        forest = build_forest(corpus.trees)
        bank = build_bank(forest)
        session = RetrievalSession()
        session.attach(CFTDeviceState.from_bank(bank, forest),
                       lookup_fn=cuckoo_lookup_arena_auto)
        session.attach_maintenance(MaintenanceEngine(bank), forest)
        self.session = session
        # the deployment's own engine settings (the admission bound)
        self.engine = AsyncServeEngine(session,
                                       **ctx.config.get("serving", {}))
        if ctx.fault:
            from entries.faults import plant_retrieval
            plant_retrieval(session, ctx.fault)
        self.engine.warmup()
        jax.block_until_ready(session.state.fingerprints)
        self.schedule = generator(ctx.traffic).generate(
            self.ref, ctx.traffic, ctx.seconds, ctx.seed)
        self.engine.start()

    def window(self, seconds: float) -> Window:
        """Offer the schedule, then wait for every answer.  Each answer
        is copied, as it resolves, into arrays laid out by the schedule,
        and no request's future is kept: the load generator leaves no
        Python objects behind per request for the collector to scan."""
        sched, ctx = self.schedule, self.ctx
        n = sched.offsets.size
        sizes = np.fromiter((len(h) for h in sched.hashes), np.int64, n)
        start = np.concatenate([[0], np.cumsum(sizes)])
        bank = ctx.config["bank"]
        locs, depth = bank["max_locs"], bank["hierarchy_n"]
        pairs = int(start[-1])
        self.hit = np.zeros(pairs, bool)
        self.locations = np.full((pairs, locs), -1, np.int32)
        self.up = np.full((pairs, locs, depth), -1, np.int32)
        self.down = np.full((pairs, locs, depth), -1, np.int32)
        done = np.full(n, np.nan)
        sent = np.zeros(n)
        # per request: 0 not answered, 1 answered, 2 failed
        self.state = state = np.zeros(n, np.int8)

        def finish(i, fut):
            t = time.perf_counter()
            if fut.exception() is not None:
                state[i] = 2
                return
            r, a, b = fut.result(), start[i], start[i + 1]
            self.hit[a:b] = r.hit
            self.locations[a:b] = r.locations
            self.up[a:b] = r.up
            self.down[a:b] = r.down
            done[i] = t
            state[i] = 1

        from repro.serving import EngineOverloaded
        before = counters()
        submitted = np.zeros(n, bool)
        t0 = time.perf_counter() + 0.005
        for i in range(n):
            due = t0 + sched.offsets[i]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            with ctx.annotate("submit"):
                try:
                    f = self.engine.submit(sched.trees[i], sched.hashes[i])
                except EngineOverloaded:     # shed: a failure
                    continue
            submitted[i] = True
            f.add_done_callback(lambda fut, i=i: finish(i, fut))
        close = t0 + seconds + ctx.traffic["drain_s"]
        while (state[submitted] == 0).any() and time.perf_counter() < close:
            time.sleep(0.01)
        self.after = delta(before, counters())
        due = t0 + sched.offsets
        ok = state == 1
        failed = int((~submitted).sum() + (state == 2).sum())
        self.unanswered = int((submitted & (state == 0)).sum())
        # the tail is the tail of all requests: one shed, failed or never
        # answered counts as answered at the close of the wait
        lat_ms = (np.where(ok, done, close) - due) * 1e3
        self.latencies_ms = lat_ms            # in order of arrival
        late_ms = (sent - due) * 1e3
        notes = [
            f"offered {n} requests ({pairs} pairs) over "
            f"{seconds:.1f} s; generator late p50 {quantile(late_ms, .5):.3f}"
            f" p99 {quantile(late_ms, .99):.3f} max {late_ms.max():.3f} ms;"
            f" latency p95 {quantile(lat_ms, .95):.3f} ms",
            f"served queries {self.after['serve.queries']:.0f} in "
            f"{self.after['serve.batches']:.0f} batches, pad slots "
            f"{self.after['serve.padded_queries']:.0f}, shed "
            f"{self.after['serve.rejected']:.0f}, maintenance prepares "
            f"{self.after['serve.prepares']:.0f} commits "
            f"{self.after['serve.commits']:.0f}, window compiles "
            f"{self.after['xla.compiles']:.0f}"]
        self.win = Window(
            seconds=seconds, attempted=n, failed=failed,
            e2e={"retrieve_p50_ms": quantile(lat_ms, .5)},
            stats={**self.after, "late_p99_ms": quantile(late_ms, .99),
                   "retrieve_p95_ms": quantile(lat_ms, .95)},
            notes=notes)
        return self.win

    def release(self) -> None:
        self.engine.stop()
        del self.engine, self.session

    def verify(self):
        sched = self.schedule
        keep = np.repeat(self.state == 1,
                         [len(h) for h in sched.hashes])
        if not keep.any():
            return [Check("unanswered", self.unanswered, 0),
                    Check("answered", 0, -1)]
        checks, v = probe_checks(
            self.ref, np.concatenate(sched.trees)[keep],
            np.concatenate(sched.hashes)[keep], self.hit[keep],
            self.locations[keep], self.up[keep], self.down[keep],
            self.ctx.config, self.unanswered)
        self.win.stats.update(probes=v.probes + 0.0,
                              probe_hits=float(self.hit[keep].sum()))
        return checks
