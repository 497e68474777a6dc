"""Context Generation (paper Algorithm 3).

Given a query entity's address list (from its block linked list), walk every
(tree, node) location, collect the first ``n`` upward (ancestors, nearest
first) and downward (BFS level order) hierarchical-relationship nodes, and
render them through the prompt template the paper describes ("the upward
hierarchical relationship of entity A are: B, C and D").

Host path (strings, feeds the serving prompt) and a vectorized device path
(entity-id tensors, feeds tokenized prompts inside a jitted serving step).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocklist import BlockListArena, CSRArena, NULL
from .tree import EntityForest


@dataclasses.dataclass
class EntityContext:
    entity_id: int
    locations: List[Tuple[int, int]]           # (tree, node)
    up: List[List[int]]                        # per location: ancestor eids
    down: List[List[int]]                      # per location: descendant eids

    def pairs(self) -> List[Tuple[int, int]]:
        """(h_i, h'_i) pairs per Algorithm 3's context set."""
        out = []
        for u, d in zip(self.up, self.down):
            for i in range(max(len(u), len(d))):
                out.append((u[i] if i < len(u) else NULL,
                            d[i] if i < len(d) else NULL))
        return out


def generate_context(forest: EntityForest, entity_id: int,
                     locations: Iterable[Tuple[int, int]],
                     n: int = 3) -> EntityContext:
    locs = list(locations)
    up = [forest.ancestors(node, n) for _, node in locs]
    down = [forest.descendants(node, n) for _, node in locs]
    return EntityContext(entity_id=entity_id, locations=locs, up=up, down=down)


def context_from_arena(forest: EntityForest, arena: BlockListArena,
                       entity_id: int, head: int, n: int = 3) -> EntityContext:
    """Faithful path: walk the block linked list from its head pointer."""
    return generate_context(forest, entity_id, arena.walk(head), n=n)


def context_from_csr(forest: EntityForest, csr: CSRArena,
                     entity_id: int, n: int = 3) -> EntityContext:
    """Optimized path: one contiguous span per entity."""
    return generate_context(forest, entity_id, csr.walk(entity_id), n=n)


def render_context(forest: EntityForest, ctxs: Sequence[EntityContext]) -> str:
    """Paper §3.4 prompt template."""
    lines: List[str] = []
    for c in ctxs:
        name = forest.entity_names[c.entity_id]
        for (tree, _node), u, d in zip(c.locations, c.up, c.down):
            if u:
                ups = ", ".join(forest.entity_names[e] for e in u)
                lines.append(
                    f"In tree {tree}, the upward hierarchical relationship "
                    f"of {name} are: {ups}.")
            if d:
                downs = ", ".join(forest.entity_names[e] for e in d)
                lines.append(
                    f"In tree {tree}, the downward hierarchical relationship "
                    f"of {name} are: {downs}.")
    return "\n".join(lines)


# ------------------------------------------------------------------ device

def gather_hierarchy(parent: jax.Array, entity_id: jax.Array,
                     nodes: jax.Array, n: int) -> jax.Array:
    """Vectorized n-level ancestor gather: for each node index in ``nodes``
    return (len(nodes), n) ancestor entity ids (nearest first, NULL-padded;
    a NULL node gives a NULL row).  The parent-pointer chase is ``n``
    static select+gather steps: no loop, so the walk lowers to
    straight-line gathers over the batch."""
    cur = nodes.astype(jnp.int32)
    outs = []
    for _ in range(n):
        p = jnp.where(cur == NULL, NULL, parent[jnp.maximum(cur, 0)])
        eid = jnp.where(p == NULL, NULL, entity_id[jnp.maximum(p, 0)])
        outs.append(eid)
        cur = p
    return jnp.stack(outs, axis=1)             # (B, n)


def gather_descendants(child_offsets: jax.Array, child_index: jax.Array,
                       entity_id: jax.Array, nodes: jax.Array,
                       n: int) -> jax.Array:
    """First-n BFS-down entity ids per node (level order, NULL-padded; a
    NULL node gives a NULL row).

    The frontier is a ring of ``n`` slots per node with a write cursor;
    each of the ``n`` steps pops one slot and pushes that node's children.
    Every step is static select arithmetic over the whole batch: a pop that
    has nothing to expand pushes from a NULL source, which makes every
    push lane invalid, so no ``cond`` and no loop is needed, and every
    gather reads ``(B,)`` elements of the forest's arrays (never a copy of
    them per node)."""
    b = nodes.shape[0]
    ci = child_index.shape[0]
    nodes = nodes.astype(jnp.int32)
    buf = jnp.full((b, n), NULL, jnp.int32)      # BFS frontier ring, cap n
    w = jnp.zeros((b,), jnp.int32)               # frontier write cursor
    lane = jnp.arange(n, dtype=jnp.int32)[None, :]

    def push(buf, w, src):
        s = jnp.maximum(src, 0)
        lo = child_offsets[s]
        hi = child_offsets[s + 1]
        for k in range(n):
            idx = lo + k
            valid = (src != NULL) & (idx < hi) & (w < n)
            c = jnp.where(valid, child_index[jnp.minimum(idx, ci - 1)], NULL)
            oh = (lane == jnp.minimum(w, n - 1)[:, None]) & valid[:, None]
            buf = jnp.where(oh, c[:, None], buf)
            w = jnp.where(valid, w + 1, w)
        return buf, w

    buf, w = push(buf, w, nodes)
    outs = []
    for i in range(n):
        cur = buf[:, i]
        valid = (i < w) & (cur != NULL)
        outs.append(jnp.where(valid, entity_id[jnp.maximum(cur, 0)], NULL))
        buf, w = push(buf, w, jnp.where(valid, cur, NULL))
    return jnp.stack(outs, axis=1)               # (B, n)
