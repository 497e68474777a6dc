"""Closed-loop natural-language queries over the entity forest.

Each query names ``entities_per_query`` distinct entities of the forest
through the corpus' query templates, so entity recognition finds exactly
them.  Two mixes share this generator:

* ``pool_size`` given: a fixed pool of that many queries, made from
  ``pool_seed`` (part of the mix, the same for every run), asked with
  Zipf ``zipf_theta`` popularity.  Requests come in periods of
  ``pool_size``; each period holds every query its Zipf share of times
  (largest remainders), in an order drawn from ``--seed``.
* ``pool_size`` absent: every query fresh, its entities drawn Zipf
  ``zipf_theta`` over the vocabulary.  Popularity ranks come from
  ``rank_seed`` (part of the mix), so every seed asks the same
  popularity law of the same entities and differs only in the draws.

``answer_tokens`` (optional): the answer lengths, cycled in blocks that
hold each once, in an order drawn from ``--seed``.

A closed loop asks as many queries as its window has room for, so its
schedule comes in blocks of ``count``: block 0 draws from the stream of
``--seed`` alone, block ``b`` from a stream of its own made from the seed
and ``b``.  Every block follows the same laws (whole pool periods when
``count`` is a multiple of ``pool_size``, fresh Zipf draws otherwise), and
no block repeats another.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from traffic.common import sizes_in_blocks, zipf_cdf, zipf_draw

TEMPLATES = [
    "What is the role of {e} in the organization?",
    "Describe the history of {e} and its parent units.",
    "Which teams report to {e}?",
    "How does {e} relate to its departments?",
]


@dataclasses.dataclass
class Schedule:
    queries: List[str]
    entities: List[List[str]]            # per query, the names it holds
    max_new: Optional[np.ndarray]        # per query answer length

    def extend(self, more: "Schedule") -> None:
        """Append the queries of a later block."""
        self.queries += more.queries
        self.entities += more.entities
        if self.max_new is not None:
            self.max_new = np.concatenate([self.max_new, more.max_new])


def _query(rng, names: List[str]) -> str:
    return " ".join(TEMPLATES[int(rng.integers(len(TEMPLATES)))].format(e=e)
                    for e in names)


def _distinct_zipf(rng, cdf: np.ndarray, k: int) -> List[int]:
    got: List[int] = []
    while len(got) < k:
        r = int(zipf_draw(rng, cdf, 1)[0])
        if r not in got:
            got.append(r)
    return got


def generate(forest, params: dict, count: int, seed: int,
             block: int = 0) -> Schedule:
    rng = np.random.default_rng([seed, 2] + ([block] if block else []))
    k = params["entities_per_query"]
    theta = params["zipf_theta"]
    names = forest.names
    if "pool_size" in params:
        pool_rng = np.random.default_rng(params["pool_seed"])
        size = params["pool_size"]
        pool_ents = [[names[i] for i in pool_rng.choice(len(names), k,
                                                        replace=False)]
                     for _ in range(size)]
        pool = [_query(pool_rng, e) for e in pool_ents]
        w = 1.0 / np.arange(1, size + 1, dtype=np.float64) ** theta
        share = w / w.sum() * size
        per = np.floor(share).astype(int)
        rest = size - per.sum()
        per[np.argsort(-(share - per), kind="stable")[:rest]] += 1
        period = np.repeat(np.arange(size), per)
        picks = np.concatenate([rng.permutation(period)
                                for _ in range(-(-count // size))])[:count]
        queries = [pool[i] for i in picks]
        ents = [pool_ents[i] for i in picks]
    else:
        rank_to_entity = np.random.default_rng(
            params["rank_seed"]).permutation(len(names))
        cdf = zipf_cdf(len(names), theta)
        ents, queries = [], []
        for _ in range(count):
            e = [names[rank_to_entity[r]]
                 for r in _distinct_zipf(rng, cdf, k)]
            ents.append(e)
            queries.append(_query(rng, e))
    max_new = None
    if "answer_tokens" in params:
        max_new = sizes_in_blocks(rng, params["answer_tokens"], count)
    return Schedule(queries=queries, entities=ents, max_new=max_new)
