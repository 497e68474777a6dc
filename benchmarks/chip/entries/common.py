"""Pieces the entries share: the data set, counters, the retrieval tap
and the retrieval check."""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

from harness import Check
from reference.forest import (Forest, compare, false_positive_bound,
                              poisson_upper)

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
    what it gets back, without changing either."""

    def __init__(self):
        import repro.serving.rag as rag_mod
        self._mod = rag_mod
        self._orig = rag_mod.retrieve_device
        self.calls: List[tuple] = []
        self.recording = False

        def tapped(state, hashes, trees, **kw):
            out = self._orig(state, hashes, trees, **kw)
            if self.recording:
                self.calls.append((hashes, trees, out))
            return out
        rag_mod.retrieve_device = tapped

    def close(self) -> None:
        self._mod.retrieve_device = self._orig


def probe_checks(forest: Forest, trees, hashes, hit, locs, up, down,
                 config: dict, unanswered: int, ner_wrong: int = None):
    """The retrieval checks of a batch of answers, and the verdict."""
    bank = config["bank"]
    v = compare(forest, trees, hashes, hit, locs, up, down,
                n=bank["hierarchy_n"])
    # the filter's stated false-positive rate, held as a count over the
    # distinct pairs asked: a sound filter exceeds it one run in a million
    fp_limit = poisson_upper(v.distinct * false_positive_bound(bank["slots"]))
    checks = [Check("unanswered", unanswered, 0),
              Check("wrong_answers", v.wrong, 0),
              Check("false_positives", v.false_pos, fp_limit)]
    if ner_wrong is not None:
        checks.insert(1, Check("wrong_queries", ner_wrong, 0))
    return checks, v


def tapped_arrays(calls):
    """Concatenated probes and answers of the tapped device calls."""
    if not calls:
        empty = np.zeros((0,), np.int64)
        return empty, empty, empty, np.zeros((0, 1)), np.zeros((0, 1, 1)), \
            np.zeros((0, 1, 1))
    cat = lambda xs: np.concatenate([np.asarray(x) for x in xs])  # noqa
    return (cat([c[1] for c in calls]), cat([c[0] for c in calls]),
            cat([c[2].hit for c in calls]), cat([c[2].locations for c in calls]),
            cat([c[2].up for c in calls]), cat([c[2].down for c in calls]))
