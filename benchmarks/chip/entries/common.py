"""Pieces the entries share: the data set, counters, the retrieval tap
and the retrieval check."""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

from harness import Check
from reference.forest import (Forest, Verdict, compare,
                              false_positive_bound, merge, poisson_upper)

COUNTERS = ("serve.queries", "serve.padded_queries", "serve.batches",
            "serve.rejected", "serve.prepares", "serve.commits",
            "xla.compiles")


def dataset(config: dict):
    """The configuration's corpus, through the program's documented
    generator at its fixed data seed, and the reference's own forest
    built from the corpus' edge lists."""
    from repro.data import hospital_corpus
    f = config["forest"]
    if f["dataset"] != "hospital":
        raise ValueError(f"unknown dataset {f['dataset']!r}")
    corpus = hospital_corpus(num_trees=f["num_trees"], depth=f["depth"],
                             branching=f["branching"])
    return corpus, Forest.from_edges(corpus.trees)


def generator(traffic: dict):
    return importlib.import_module("traffic." + traffic["generator"])


def counters() -> Dict[str, float]:
    from repro.obs import get_registry
    reg = get_registry()
    # summed over label cells: ``serve.rejected`` counts by reason
    return {name: float(sum(reg.counter(name).raw().values()))
            for name in COUNTERS}


def delta(before: Dict[str, float], after: Dict[str, float]
          ) -> Dict[str, float]:
    return {k: after[k] - before[k] for k in before}


class RetrievalTap:
    """Records what ``RAGPipeline.retrieve`` hands to the device step and
    what it gets back, without changing either.  Each call's probes and
    answers stay on the device, where they were made, until :meth:`take`
    moves them to the host; :meth:`held_bytes` counts what is held."""

    def __init__(self):
        import repro.serving.rag as rag_mod
        self._mod = rag_mod
        self._orig = rag_mod.retrieve_device
        self.calls: List[tuple] = []
        self.recording = False
        self._held = 0
        self._counted = 0
        self.held_peak = 0

        def tapped(state, hashes, trees, **kw):
            out = self._orig(state, hashes, trees, **kw)
            if self.recording:
                self.calls.append((hashes, trees, out.hit, out.locations,
                                   out.up, out.down))
            return out
        rag_mod.retrieve_device = tapped

    def held_bytes(self) -> int:
        """Bytes of the calls held now; counted here, between calls, so
        that a timed call does no more than record."""
        for call in self.calls[self._counted:]:
            self._held += sum(int(a.nbytes) for a in call)
        self._counted = len(self.calls)
        self.held_peak = max(self.held_peak, self._held)
        return self._held

    def take(self) -> List[tuple]:
        """The held calls on the host, in call order, as ``(hashes, trees,
        hit, locations, up, down)``; the tap holds nothing after it."""
        import jax
        self.held_bytes()
        calls = jax.device_get(self.calls)
        self.calls, self._held, self._counted = [], 0, 0
        return calls

    def close(self) -> None:
        self._mod.retrieve_device = self._orig


class ProbeTally:
    """The verdict of every probe of a run, added a batch at a time, so
    that only the distinct pairs asked outlive their batch."""

    def __init__(self, forest: Forest, n: int):
        self.forest, self.n = forest, n
        self.parts: List[Verdict] = []
        self.probes = 0                 # all probes, judged or not
        self.hits = 0

    def add(self, trees, hashes, hit, locs, up, down) -> None:
        self.parts.append(compare(self.forest, trees, hashes, hit, locs, up,
                                  down, n=self.n))
        self.probes += int(np.asarray(hashes).size)
        self.hits += int(np.asarray(hit).sum())
        # fold the batches' distinct pairs together once they outnumber
        # the pairs already folded: each pair is sorted a few times at most
        if sum(v.keys.size for v in self.parts[1:]) > \
                self.parts[0].keys.size:
            self.parts = [merge(self.parts)]

    def verdict(self) -> Verdict:
        return merge(self.parts)


def probe_checks(forest: Forest, trees, hashes, hit, locs, up, down,
                 config: dict, unanswered: int, ner_wrong: int = None):
    """The retrieval checks of a batch of answers, and the verdict."""
    v = compare(forest, trees, hashes, hit, locs, up, down,
                n=config["bank"]["hierarchy_n"])
    return verdict_checks(v, config, unanswered, ner_wrong), v


def verdict_checks(v: Verdict, config: dict, unanswered: int,
                   ner_wrong: int = None) -> List[Check]:
    """The retrieval checks of a verdict, each beside its limit."""
    # the filter's stated false-positive rate, held as a count over the
    # distinct pairs asked: a sound filter exceeds it one run in a million
    fp_limit = poisson_upper(
        v.distinct * false_positive_bound(config["bank"]["slots"]))
    checks = [Check("unanswered", unanswered, 0),
              Check("wrong_answers", v.wrong, 0),
              Check("false_positives", v.false_pos, fp_limit)]
    if ner_wrong is not None:
        checks.insert(1, Check("wrong_queries", ner_wrong, 0))
    return checks
