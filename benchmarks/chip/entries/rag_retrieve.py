"""Closed-loop global retrieval through ``RAGPipeline.retrieve``.

One client asks a natural-language query and waits for its context
before asking the next.  The pipeline recognises the query's entities
and fans each out over every tree of the bank; each call is timed on the
host clock from query text to rendered context (``fanout_p50_ms``,
``fanout_p95_ms``).

The window asks queries for as long as it lasts, however fast the calls:
the schedule grows by a block when the window reaches its end, and the
tapped probes and answers leave the device and are held to the reference
a block at a time (``TAP_BYTES``).  Both happen between calls, outside
every call's timer; a window of fewer calls than a block, holding fewer
bytes than ``TAP_BYTES``, needs neither.
"""
from __future__ import annotations

import time

import numpy as np

from entries.common import (ProbeTally, RetrievalTap, counters, dataset,
                            delta, generator, verdict_checks)
from harness import Window, quantile

# device bytes of tapped calls that the window lets the tap hold before it
# checks them: about 185 fan-out calls over 600 trees
TAP_BYTES = 64 << 20


def ner_wrong(ref, calls, entities) -> int:
    """Device calls whose ``(tree, hash)`` batch is not every tree for
    each of the query's entities, in query order."""
    from reference.forest import fnv1a32_many
    trees_all = int(ref.tree.max()) + 1
    wrong = 0
    for (hashes, trees, *_), ents in zip(calls, entities):
        want_h = np.tile(fnv1a32_many(ents).astype(np.int64), trees_all)
        want_t = np.repeat(np.arange(trees_all), len(ents))
        got_h = np.asarray(hashes).astype(np.int64)
        got_t = np.asarray(trees).astype(np.int64)
        if got_h.shape != want_h.shape or not (
                np.array_equal(got_h, want_h)
                and np.array_equal(got_t, want_t)):
            wrong += 1
    return wrong


class Entry:
    kind = "fanout"                    # names the end-to-end metrics

    def __init__(self, ctx):
        self.ctx = ctx

    def build(self):
        from repro.serving import RAGPipeline
        return RAGPipeline(self.corpus, None, use_bank=True)

    def setup(self) -> None:
        ctx = self.ctx
        self.corpus, self.ref = dataset(ctx.config)
        self.rag = self.build()
        if ctx.fault:                  # under the tap: it records the fault
            from entries.faults import plant_rag
            plant_rag(self, ctx.fault)
        self.tap = RetrievalTap()
        self.tally = ProbeTally(self.ref, ctx.config["bank"]["hierarchy_n"])
        self.checked, self.wrong_queries, self.tap_checks = 0, 0, 0
        self.blocks = 1
        self.schedule = self.block(0)
        self.warm()

    def block(self, b: int):
        """Block ``b`` of the schedule: ``schedule_length`` queries of
        their own, drawn from the seed and ``b``."""
        traffic = self.ctx.traffic
        n = traffic["schedule_length"]
        s = generator(traffic).generate(self.ref, traffic, n, self.ctx.seed,
                                        block=b)
        if len(s.queries) != n:
            raise RuntimeError(f"schedule block {b} holds {len(s.queries)} "
                               f"queries, not {n}")
        return s

    def warm(self) -> None:
        """One query for every entity count the schedule's queries hold:
        the fan-out batch's shape depends on it alone.  Every query of
        every block holds ``entities_per_query``, so a later block brings
        no new count."""
        counts = {}
        for q, e in zip(self.schedule.queries, self.schedule.entities):
            counts.setdefault(len(e), q)
        for q in counts.values():
            self.rag.retrieve(q)

    def call(self, i: int) -> None:
        with self.ctx.annotate("retrieve"):
            self.rag.retrieve(self.schedule.queries[i])

    def check(self) -> None:
        """Move the tapped calls to the host and hold them to the
        reference: each call's batch against its query's entities, each
        probe's answer against the forest."""
        calls = self.tap.take()
        ents = self.schedule.entities[self.checked:self.checked + len(calls)]
        self.wrong_queries += ner_wrong(self.ref, calls, ents)
        self.checked += len(calls)
        if calls:
            cat = lambda k: np.concatenate([c[k] for c in calls])  # noqa
            self.tally.add(cat(1), cat(0), cat(2), cat(3), cat(4), cat(5))

    def window(self, seconds: float) -> Window:
        lat = []
        before = counters()
        self.tap.recording = True
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            if i == len(self.schedule.queries):
                self.schedule.extend(self.block(self.blocks))
                self.blocks += 1
            t = time.perf_counter()
            self.call(i)
            lat.append(time.perf_counter() - t)
            i += 1
            if self.tap.held_bytes() >= TAP_BYTES:
                with self.ctx.annotate("check"):
                    self.check()
                self.tap_checks += 1
        self.tap.recording = False
        self.asked = i
        after = delta(before, counters())
        lat_ms = np.asarray(lat) * 1e3
        kind = self.kind
        e2e = {f"{kind}_p50_ms": quantile(lat_ms, .5),
               f"{kind}_p95_ms": quantile(lat_ms, .95)}
        self.win = Window(
            seconds=time.perf_counter() - t0, attempted=i, failed=0,
            e2e=e2e, stats=dict(after),
            notes=[f"{i} {kind} calls in {seconds:.1f} s, window compiles "
                   f"{after['xla.compiles']:.0f}; "
                   + ", ".join(f"{k} {v:.3f}" for k, v in e2e.items()),
                   f"schedule blocks {self.blocks}; the tap held at most "
                   f"{self.tap.held_peak} device bytes, checked in the "
                   f"window {self.tap_checks} times"])
        return self.win

    def release(self) -> None:
        self.tap.close()
        getattr(self, "unplant", lambda: None)()
        self.check()
        del self.rag, self.tap

    def verify(self):
        v = self.tally.verdict()
        # a query whose call made no device call, or made two, leaves the
        # tapped calls and the schedule out of step
        wrong = self.wrong_queries + abs(self.checked - self.asked)
        checks = verdict_checks(v, self.ctx.config, unanswered=0,
                                ner_wrong=wrong)
        self.win.stats.update(probes=float(self.tally.probes),
                              probe_hits=float(self.tally.hits))
        return checks + self.verify_more()

    def verify_more(self):
        return []
