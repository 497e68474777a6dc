"""Plain reference for retrieval over an entity forest.

A straightforward implementation of what a CFT-RAG retrieval answers,
written from the documented semantics and sharing no code with the
program:

* a forest is built from per-tree ``(parent, child)`` edge lists; each
  distinct name in a tree is one node, numbered in order of first
  appearance, entity ids number names in order of first appearance over
  all trees, the first parent of a node wins and an edge that would close
  a cycle is dropped;
* an entity's hash is 64-bit FNV-1a over its UTF-8 bytes folded to 32
  bits; its 12-bit fingerprint is the splitmix32 finalizer of
  ``hash ^ 0x9E3779B9``, with 0 remapped to 1;
* a query ``(tree, hash)`` for an entity held in that tree answers its
  node, the entity ids of its first ``n`` ancestors (nearest first) and
  of its first ``n`` descendants in breadth-first order; a query for a
  hash the tree does not hold answers a miss.

A cuckoo filter may also answer a query with another entry of the same
tree whose fingerprint equals the query's (a false positive).  The
comparison in :func:`compare` accepts such an answer only when it is that
entry's exact answer, and counts it apart, so the number of false
positives can be held to the filter's stated bound.
"""
from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Dict, List, Sequence, Tuple

import numpy as np

NULL = -1
FP_BITS = 12
_GOLDEN = 0x9E3779B9
_M32 = 0xFFFFFFFF


def fnv1a32(name: str) -> int:
    h = 0xCBF29CE484222325
    for b in name.encode("utf-8"):
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return (h ^ (h >> 32)) & _M32


def fnv1a32_many(names: Sequence[str]) -> np.ndarray:
    """:func:`fnv1a32` of many names at once: byte position by byte
    position over a padded ``(names, longest)`` byte matrix."""
    raw = [s.encode("utf-8") for s in names]
    if not raw:
        return np.zeros(0, np.uint64)
    width = max(len(b) for b in raw)
    mat = np.zeros((len(raw), width), np.uint64)
    lens = np.asarray([len(b) for b in raw])
    for i, b in enumerate(raw):
        mat[i, :len(b)] = np.frombuffer(b, np.uint8)
    h = np.full(len(raw), 0xCBF29CE484222325, np.uint64)
    with np.errstate(over="ignore"):
        for j in range(width):
            step = (h ^ mat[:, j]) * np.uint64(0x100000001B3)
            h = np.where(j < lens, step, h)
    return (h ^ (h >> np.uint64(32))) & np.uint64(_M32)


def _splitmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint64) & _M32
    h = ((h ^ (h >> 16)) * 0x7FEB352D) & _M32
    h = ((h ^ (h >> 15)) * 0x846CA68B) & _M32
    return h ^ (h >> 16)


def fingerprints(hashes) -> np.ndarray:
    """12-bit fingerprints of 32-bit entity hashes (0 is reserved)."""
    h = np.asarray(hashes, np.uint64)
    fp = _splitmix32(h ^ _GOLDEN) & ((1 << FP_BITS) - 1)
    return np.where(fp == 0, 1, fp).astype(np.int64)


def false_positive_bound(slots: int) -> float:
    """Fan et al.'s bound on a cuckoo filter's false-positive rate: a
    query compares its fingerprint with at most ``2 * slots`` stored
    ones, each equal with probability ``1 / (2^f - 1)``."""
    return 2 * slots / ((1 << FP_BITS) - 1)


def poisson_upper(mean: float, tail: float = 1e-6) -> int:
    """The least count ``k`` with ``P(X > k) < tail`` for ``X ~
    Poisson(mean)``: the most false positives a filter that keeps its
    bound gives, but for a one-in-a-million run."""
    if mean <= 0:
        return 0
    k, cdf = 0, 0.0
    while True:
        cdf += math.exp(k * math.log(mean) - mean - math.lgamma(k + 1))
        if 1.0 - cdf < tail:
            return k
        k += 1


@dataclasses.dataclass
class Forest:
    parent: np.ndarray           # (N,) node -> parent node or NULL
    entity: np.ndarray           # (N,) node -> entity id
    tree: np.ndarray             # (N,) node -> tree
    children: List[List[int]]
    names: List[str]             # entity id -> name
    entity_hash: np.ndarray      # (E,) uint32

    @classmethod
    def from_edges(cls, trees: Sequence[Sequence[Tuple[str, str]]]
                   ) -> "Forest":
        eid: Dict[str, int] = {}
        names: List[str] = []
        parent: List[int] = []
        entity: List[int] = []
        tree: List[int] = []
        children: List[List[int]] = []
        for t, edges in enumerate(trees):
            local: Dict[str, int] = {}

            def node(name: str) -> int:
                if name not in local:
                    local[name] = len(parent)
                    if name not in eid:
                        eid[name] = len(names)
                        names.append(name)
                    parent.append(NULL)
                    entity.append(eid[name])
                    tree.append(t)
                    children.append([])
                return local[name]

            for pname, cname in edges:
                p, c = node(pname), node(cname)
                if parent[c] != NULL or p == c:
                    continue
                up, cycle = p, False
                while up != NULL:
                    if up == c:
                        cycle = True
                        break
                    up = parent[up]
                if not cycle:
                    parent[c] = p
                    children[p].append(c)
        return cls(parent=np.asarray(parent, np.int64),
                   entity=np.asarray(entity, np.int64),
                   tree=np.asarray(tree, np.int64), children=children,
                   names=names,
                   entity_hash=fnv1a32_many(names))

    @property
    def num_nodes(self) -> int:
        return int(self.parent.shape[0])

    def node_keys(self) -> np.ndarray:
        """``tree << 32 | hash`` of every node's (tree, entity) pair."""
        return (self.tree << 32) | self.entity_hash[self.entity].astype(
            np.int64)

    def up(self, node: int, n: int) -> List[int]:
        out, p = [], int(self.parent[node])
        while p != NULL and len(out) < n:
            out.append(int(self.entity[p]))
            p = int(self.parent[p])
        return out + [NULL] * (n - len(out))

    def down(self, node: int, n: int) -> List[int]:
        out: List[int] = []
        q = deque(self.children[node])
        while q and len(out) < n:
            c = q.popleft()
            out.append(int(self.entity[c]))
            q.extend(self.children[c])
        return out + [NULL] * (n - len(out))


@dataclasses.dataclass
class Verdict:
    probes: int            # probes compared
    wrong: int             # probes whose answer no filter semantics explain
    present: int           # probes for an entity the tree holds
    keys: np.ndarray       # the distinct ``tree << 32 | hash`` pairs compared
    fp_keys: np.ndarray    # those answered with a fingerprint twin

    @property
    def distinct(self) -> int:
        return int(self.keys.size)

    @property
    def false_pos(self) -> int:
        return int(self.fp_keys.size)


def merge(verdicts: Sequence[Verdict]) -> Verdict:
    """The verdict of several batches of probes as one: the probes' counts
    add up, and a pair asked in several batches is one distinct pair."""
    empty = np.zeros(0, np.int64)
    return Verdict(probes=sum(v.probes for v in verdicts),
                   wrong=sum(v.wrong for v in verdicts),
                   present=sum(v.present for v in verdicts),
                   keys=np.unique(np.concatenate(
                       [empty] + [v.keys for v in verdicts])),
                   fp_keys=np.unique(np.concatenate(
                       [empty] + [v.fp_keys for v in verdicts])))


def compare(forest: Forest, trees, hashes, hit, locations, up, down,
            n: int = 3) -> Verdict:
    """Hold a batch of program answers to the reference.

    ``trees``/``hashes``: ``(P,)`` probes; ``hit`` ``(P,)``; ``locations``
    ``(P, L)``; ``up``/``down`` ``(P, L, n)``.  An entity has at most one
    node in a tree, so a right answer holds that node in slot 0 and NULL
    after it."""
    trees = np.asarray(trees, np.int64)
    hashes = np.asarray(hashes, np.uint64).astype(np.int64)
    hit = np.asarray(hit).astype(bool)
    locs = np.asarray(locations, np.int64)
    up = np.asarray(up, np.int64)
    down = np.asarray(down, np.int64)
    p, nl = locs.shape
    keys = (trees << 32) | hashes

    node_keys = forest.node_keys()
    order = np.argsort(node_keys, kind="stable")
    sk = node_keys[order]
    # a hash shared by two names of one tree has no single right answer:
    # such pairs cannot be judged and are left out
    dup = np.zeros(sk.shape, bool)
    dup[1:] |= sk[1:] == sk[:-1]
    dup[:-1] |= sk[1:] == sk[:-1]
    pos = np.clip(np.searchsorted(sk, keys), 0, max(sk.size - 1, 0))
    present = (sk.size > 0) & (sk[pos] == keys)
    judged = ~(present & dup[pos])
    node = np.where(present, order[pos], NULL)

    # the node whose answer the program gave (slot 0 of a hit): the
    # queried entity's node, or that of a fingerprint twin
    g = np.where(hit, locs[:, 0], NULL)
    valid_g = (g >= 0) & (g < forest.num_nodes)
    gs = np.where(valid_g, g, 0)
    exp_locs = np.full((p, nl), NULL, np.int64)
    exp_locs[:, 0] = np.where(valid_g, g, NULL)
    walks = {}
    for x in np.unique(gs[valid_g]):
        walks[int(x)] = (forest.up(int(x), n), forest.down(int(x), n))
    exp_up = np.full((p, nl, n), NULL, np.int64)
    exp_down = np.full((p, nl, n), NULL, np.int64)
    for i in np.flatnonzero(valid_g):
        u, d = walks[int(gs[i])]
        exp_up[i, 0], exp_down[i, 0] = u, d
    same = ((locs == exp_locs).all(1) & (up == exp_up).all((1, 2))
            & (down == exp_down).all((1, 2)))

    ok_hit = present & hit & same & (locs[:, 0] == node)
    ok_miss = ~present & ~hit & same
    g_hash = forest.entity_hash[forest.entity[gs]].astype(np.int64)
    twin = (hit & valid_g & same & (forest.tree[gs] == trees)
            & (g_hash != hashes)
            & (fingerprints(g_hash) == fingerprints(hashes)))
    wrong = judged & ~(ok_hit | ok_miss | twin)
    return Verdict(probes=int(judged.sum()), wrong=int(wrong.sum()),
                   present=int((present & judged).sum()),
                   keys=np.unique(keys[judged]),
                   fp_keys=np.unique(keys[judged & twin]))
