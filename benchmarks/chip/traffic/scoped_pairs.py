"""Open-loop tree-scoped retrieval requests.

Each request is a group of ``(tree, entity hash)`` pairs, each pair
scoped to a tree: the per-request traffic of a retrieval microservice.

Parameters (from the traffic file):

* ``rate_per_s``: arrivals per second.  A run of ``seconds`` sends
  exactly ``rate * seconds`` requests at sorted uniform times, a Poisson
  process given its count, so every seed offers the same load.
* ``pairs_per_request``: the sizes a request takes, cycled in blocks
  that hold each once, in an order drawn from the seed.
* ``zipf_theta``: pair popularity over the bank's rows, Zipf with this
  exponent over ranks that the seed assigns (YCSB's default is 0.99).
* ``absent_share``: the share of pairs that name an entity the tree does
  not hold (names made from the seed, checked absent), placed at
  positions drawn from the seed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from traffic.common import sizes_in_blocks, zipf_cdf, zipf_draw


@dataclasses.dataclass
class Schedule:
    offsets: np.ndarray            # (R,) seconds after the window opens
    trees: List[np.ndarray]        # per request (k,) int32
    hashes: List[np.ndarray]       # per request (k,) uint32


def absent_hashes(rng, forest, trees: np.ndarray) -> np.ndarray:
    from reference.forest import fnv1a32_many
    held = set(forest.node_keys().tolist())
    out = np.zeros(trees.shape, np.uint64)
    todo = np.arange(trees.size)
    while todo.size:
        tags = rng.integers(0, 2 ** 62, size=todo.size)
        names = [f"Annex Ward Z{int(t):x}" for t in tags]
        h = fnv1a32_many(names)
        keys = (trees[todo].astype(np.int64) << 32) | h.astype(np.int64)
        fresh = np.asarray([k not in held for k in keys.tolist()])
        out[todo[fresh]] = h[fresh]
        todo = todo[~fresh]
    return out


def generate(forest, params: dict, seconds: float, seed: int) -> Schedule:
    rng = np.random.default_rng([seed, 1])
    count = int(round(params["rate_per_s"] * seconds))
    offsets = np.sort(rng.random(count)) * seconds
    sizes = sizes_in_blocks(rng, params["pairs_per_request"], count)
    total = int(sizes.sum())

    rows = forest.num_nodes          # one bank row per (tree, entity) node
    rank_to_node = rng.permutation(rows)
    nodes = rank_to_node[zipf_draw(rng, zipf_cdf(rows, params["zipf_theta"]),
                                       total)]
    trees = forest.tree[nodes].astype(np.int64)
    hashes = forest.entity_hash[forest.entity[nodes]].astype(np.uint64)

    absent = rng.permutation(total)[:int(round(params["absent_share"]
                                               * total))]
    trees[absent] = rng.integers(0, int(forest.tree.max()) + 1,
                                 size=absent.size)
    hashes[absent] = absent_hashes(rng, forest, trees[absent])

    cuts = np.cumsum(sizes)[:-1]
    return Schedule(offsets=offsets,
                    trees=np.split(trees.astype(np.int32), cuts),
                    hashes=np.split(hashes.astype(np.uint32), cuts))
