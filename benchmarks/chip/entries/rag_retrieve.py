"""Closed-loop global retrieval through ``RAGPipeline.retrieve``.

One client asks a natural-language query and waits for its context
before asking the next.  The pipeline recognises the query's entities
and fans each out over every tree of the bank; each call is timed on the
host clock from query text to rendered context (``fanout_p50_ms``,
``fanout_p95_ms``).
"""
from __future__ import annotations

import time

import numpy as np

from entries.common import (RetrievalTap, counters, dataset, delta,
                            generator, probe_checks, tapped_arrays)
from harness import Window, quantile


def ner_wrong(ref, calls, entities) -> int:
    """Device calls whose ``(tree, hash)`` batch is not every tree for
    each of the query's entities, in query order."""
    from reference.forest import fnv1a32_many
    trees_all = int(ref.tree.max()) + 1
    wrong = 0
    for (hashes, trees, _), ents in zip(calls, entities):
        want_h = np.tile(fnv1a32_many(ents).astype(np.int64), trees_all)
        want_t = np.repeat(np.arange(trees_all), len(ents))
        got_h = np.asarray(hashes).astype(np.int64)
        got_t = np.asarray(trees).astype(np.int64)
        if got_h.shape != want_h.shape or not (
                np.array_equal(got_h, want_h)
                and np.array_equal(got_t, want_t)):
            wrong += 1
    return wrong + abs(len(calls) - len(entities))


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
        self.schedule = generator(ctx.traffic).generate(
            self.ref, ctx.traffic, ctx.traffic["schedule_length"], ctx.seed)
        self.warm()

    def warm(self) -> None:
        """One query for every entity count the schedule's queries hold:
        the fan-out batch's shape depends on it alone."""
        counts = {}
        for q, e in zip(self.schedule.queries, self.schedule.entities):
            counts.setdefault(len(e), q)
        for q in counts.values():
            self.rag.retrieve(q)

    def call(self, i: int) -> None:
        with self.ctx.annotate("retrieve"):
            self.rag.retrieve(self.schedule.queries[i])

    def window(self, seconds: float) -> Window:
        lat = []
        before = counters()
        self.tap.recording = True
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            if i >= len(self.schedule.queries):
                raise RuntimeError("schedule too short for the window")
            t = time.perf_counter()
            self.call(i)
            lat.append(time.perf_counter() - t)
            i += 1
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
                   + ", ".join(f"{k} {v:.3f}" for k, v in e2e.items())])
        return self.win

    def release(self) -> None:
        self.tap.close()
        getattr(self, "unplant", lambda: None)()
        calls = self.tap.calls
        self.arrays = tapped_arrays(calls)
        self.calls = [(np.asarray(h), np.asarray(t), None)
                      for h, t, _ in calls]
        del self.rag, self.tap

    def verify(self):
        trees, hashes, hit, locs, up, down = self.arrays
        ner = ner_wrong(self.ref, self.calls,
                        self.schedule.entities[:self.asked])
        checks, v = probe_checks(self.ref, trees, hashes, hit, locs, up,
                                 down, self.ctx.config,
                                 unanswered=0, ner_wrong=ner)
        self.win.stats.update(probes=float(hashes.size),
                              probe_hits=float(np.asarray(hit).sum()))
        return checks + self.verify_more()

    def verify_more(self):
        return []

