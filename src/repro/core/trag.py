"""CFT-RAG retriever — the paper's method, host and device paths.

Host path (benchmark-comparable with baselines.py): sequential filter lookup
per entity, block-linked-list walk, Algorithm-3 context generation, with
temperature bump + idle-time bucket sort between query rounds.

Device path: batched lookup over all query entities at once (jnp /
Pallas-kernel semantics) + vectorized hierarchy gather — this is what runs
inside the jitted serving step (see repro/serving/rag.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing
from .bank import FilterBank, pad_csr
from .context import (EntityContext, context_from_arena, context_from_csr,
                      gather_descendants, gather_hierarchy, render_context)
from .cuckoo import CFTIndex, build_index
from .lookup import (LookupResult, bump_temperature_arena, lookup_arena,
                     sort_buckets_arena)
from .tree import EntityForest

NULL = -1


class CFTRAG:
    """Cuckoo-Filter Tree-RAG retriever (paper §3 / §4.2)."""

    def __init__(self, index: CFTIndex, use_csr: bool = False,
                 sort_every: int = 1, n_hierarchy: int = 3):
        self.index = index
        self.use_csr = use_csr          # False = faithful block linked list
        self.sort_every = sort_every    # re-sort buckets every k rounds (0=off)
        self.n = n_hierarchy
        self._round = 0

    # ----------------------------------------------------------- host path
    def locate(self, name: str):
        """Filter lookup -> address list (the paper's accelerated locate)."""
        h = hashing.entity_hash(name)
        hit, head, eid = self.index.filter.lookup_entry(int(h))
        if not hit:
            return []
        if self.use_csr:
            # use the slot's entity-id payload, NOT a name->id re-resolve:
            # on a fingerprint collision the arena path walks the stored
            # entity's addresses, and the CSR path must agree with it
            return self.index.csr.walk(eid) if eid >= 0 else []
        return self.index.arena.walk(head)

    def retrieve(self, names: Sequence[str], n: Optional[int] = None
                 ) -> List[EntityContext]:
        n = n or self.n
        f = self.index.forest
        out = []
        for nm in names:
            eid = f.name_to_id.get(nm, -1)
            locs = self.locate(nm)
            out.append(EntityContext(entity_id=eid, locations=list(locs),
                                     up=[f.ancestors(node, n) for _, node in locs],
                                     down=[f.descendants(node, n) for _, node in locs]))
        self._round += 1
        if self.sort_every and self._round % self.sort_every == 0:
            self.index.filter.sort_buckets()   # idle-time adaptive sort
        return out

    def render(self, contexts: Sequence[EntityContext]) -> str:
        return render_context(self.index.forest, contexts)

    # --------------------------------------------------------- device path
    def device_state(self) -> "CFTDeviceState":
        return CFTDeviceState.from_index(self.index)


class DeviceRetrieval(NamedTuple):
    hit: jax.Array          # (B,) bool
    locations: jax.Array    # (B, max_locs) int32 node ids (NULL-padded)
    up: jax.Array           # (B, max_locs, n) ancestor entity ids
    down: jax.Array         # (B, max_locs, n) descendant entity ids
    temperature: jax.Array  # updated (A, S) arena table — thread into state


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CFTDeviceState:
    """All retrieval tensors living on device, usable inside jit.

    Filter tables are a flat **ragged bucket arena** ``(A, S)``: tree
    ``t`` owns arena rows ``[bucket_offsets[t], bucket_offsets[t+1])``
    with its own power-of-two ``tree_nb[t]`` bucket count.  The
    single-index state from :meth:`from_index` is simply an arena with one
    tree, while :meth:`from_bank` adopts the bank's arena directly.  Slot
    payloads index rows of ``csr_offsets`` — per-entity rows in the T == 1
    case, per-(tree, entity) rows in the bank case — so the retrieval
    arithmetic downstream of the lookup is identical for both.
    """
    fingerprints: jax.Array    # (A, S) uint32 — ragged arena
    temperature: jax.Array     # (A, S) int32
    heads: jax.Array           # (A, S) int32 — CSR row id payloads
    bucket_offsets: jax.Array  # (T + 1,) int32 — per-tree segment starts
    tree_nb: jax.Array         # (T,) int32 — per-tree bucket counts
    csr_offsets: jax.Array     # (R + 1,) int32
    csr_nodes: jax.Array       # (L,) int32 — node id per location
    parent: jax.Array          # (N,) int32
    entity_id: jax.Array       # (N,) int32
    child_offsets: jax.Array   # (N + 1,) int32
    child_index: jax.Array     # (C,) int32

    @property
    def num_trees(self) -> int:
        return int(self.bucket_offsets.shape[0]) - 1

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @staticmethod
    def _forest_arrays(f: EntityForest):
        return dict(
            parent=jnp.asarray(f.parent if f.num_nodes
                               else np.zeros(1, np.int32)),
            entity_id=jnp.asarray(f.entity_id if f.num_nodes
                                  else np.zeros(1, np.int32)),
            child_offsets=jnp.asarray(f.child_offsets),
            child_index=jnp.asarray(f.child_index if f.child_index.size
                                    else np.zeros(1, np.int32)),
        )

    @classmethod
    def from_index(cls, index: CFTIndex) -> "CFTDeviceState":
        t = index.filter.tables()
        nb = index.filter.num_buckets
        # NB: the host tables must be *copied*, not wrapped — on CPU,
        # jnp.asarray zero-copies a 64-byte-aligned numpy array, and an
        # aliased buffer would let later host-side writes (inserts,
        # temperature bumps) leak into this supposedly immutable state
        return cls(
            fingerprints=jnp.array(t.fingerprints, copy=True),
            temperature=jnp.array(t.temperature, copy=True),
            # the device path uses CSR: slot payload = entity id (= row)
            heads=jnp.array(t.entity_ids, copy=True),
            bucket_offsets=jnp.asarray(np.asarray([0, nb], np.int32)),
            tree_nb=jnp.asarray(np.asarray([nb], np.int32)),
            csr_offsets=jnp.asarray(index.csr.offsets),
            csr_nodes=jnp.asarray(index.csr.addrs[:, 1]
                                  if index.csr.addrs.size else
                                  np.zeros((1,), np.int32)),
            **cls._forest_arrays(index.forest),
        )

    def with_temperature(self, temperature: jax.Array) -> "CFTDeviceState":
        """Thread an updated temperature table back into the state — the
        one sanctioned way to carry a query batch's bumps forward (callers
        previously hand-rolled ``dataclasses.replace``)."""
        return dataclasses.replace(self, temperature=temperature)

    def sort_idle(self) -> "CFTDeviceState":
        """Device-side idle-time maintenance: resort every bucket of every
        tree hot-fingerprints-first (``sort_buckets_arena`` — one flat
        per-bucket reorder over the ragged arena).  Pure-device path for
        states with no host bank mirror; when a host ``MaintenanceEngine``
        owns the tables, sort on the host and restage instead so the two
        layouts never diverge."""
        f, t, h = sort_buckets_arena(self.fingerprints, self.temperature,
                                     self.heads)
        return dataclasses.replace(self, fingerprints=f, temperature=t,
                                   heads=h)

    @classmethod
    def from_bank(cls, bank: FilterBank, forest: EntityForest
                  ) -> "CFTDeviceState":
        # pad_csr keeps the CSR shapes stable under churn so the jitted
        # retrieval step never recompiles on a restage commit
        csr_off, csr_nodes = pad_csr(bank.csr_offsets, bank.csr_nodes)
        # copy the mutable arena tables (see from_index): an aliased
        # buffer would let maintenance writes to the host bank show
        # through the serving state, breaking quarantine rollback ("keep
        # serving the last committed content")
        return cls(
            fingerprints=jnp.array(bank.fingerprints, copy=True),
            temperature=jnp.array(bank.temperature, copy=True),
            heads=jnp.array(bank.heads, copy=True),
            bucket_offsets=jnp.asarray(
                bank.bucket_offsets.astype(np.int32)),
            tree_nb=jnp.asarray(bank.tree_nb.astype(np.int32)),
            csr_offsets=jnp.asarray(csr_off),
            csr_nodes=jnp.asarray(csr_nodes),
            **cls._forest_arrays(forest),
        )


def retrieve_device(state: CFTDeviceState, query_hashes: jax.Array,
                    query_trees: Optional[jax.Array] = None,
                    max_locs: int = 4, n: int = 3,
                    lookup_fn=None, fused: bool = False) -> DeviceRetrieval:
    """Batched CFT-RAG retrieval, jit-compatible end to end.

    Queries are ``(tree_id, hash)`` pairs; ``query_trees`` defaults to all
    zeros, which on a ``T == 1`` state reproduces the single-filter
    behaviour.  The per-tree routing (arena segment start + bucket mask)
    is gathered from the state's offsets table here; ``lookup_fn(
    fingerprints, heads, row_offsets, masks, h)`` then probes the flat
    arena — defaults to the pure-jnp :func:`repro.core.lookup.
    lookup_arena`; the serving engine passes the Pallas arena kernel
    wrapper (identical signature/semantics).

    ``fused=True`` routes the whole step (probe + bump + CSR window +
    hierarchy walks) through the single-pass
    :mod:`repro.kernels.fused_retrieve` kernel instead — bit-identical
    outputs, one launch.  Mutually exclusive with ``lookup_fn`` (the fused
    kernel *is* the probe).
    """
    if fused:
        if lookup_fn is not None:
            raise ValueError("fused=True embeds the probe; lookup_fn "
                             "cannot be combined with it")
        from ..kernels.fused_retrieve import fused_retrieve_state_auto
        return fused_retrieve_state_auto(state, query_hashes, query_trees,
                                         max_locs=max_locs, n=n)
    if lookup_fn is None:
        lookup_fn = lookup_arena
    if query_trees is None:
        query_trees = jnp.zeros(query_hashes.shape, jnp.int32)
    num_trees = state.bucket_offsets.shape[0] - 1
    # out-of-range tree ids must miss, not alias to a clamped gather row
    in_range = (query_trees >= 0) & (query_trees < num_trees)
    query_trees = jnp.where(in_range, query_trees, 0).astype(jnp.int32)
    row_off = state.bucket_offsets[query_trees]
    masks = (state.tree_nb[query_trees] - 1).astype(jnp.uint32)
    res: LookupResult = lookup_fn(state.fingerprints, state.heads,
                                  row_off, masks, query_hashes)
    res = res._replace(hit=res.hit & in_range)
    temp = bump_temperature_arena(state.temperature, row_off, res)
    return gather_context(state, res, temp, max_locs=max_locs, n=n)


def gather_context(state, res: LookupResult, temperature: jax.Array,
                   max_locs: int = 4, n: int = 3) -> DeviceRetrieval:
    """CSR location gather + hierarchy windows downstream of a bank lookup.

    Shared tail of :func:`retrieve_device` and the bank-axis sharded path
    (``repro.core.distributed.sharded_retrieve_device``): ``state`` is any
    object with replicated ``csr_offsets``/``csr_nodes`` and forest arrays
    (``CFTDeviceState`` or ``ShardedBankState``), ``res.head`` indexes the
    CSR rows, and ``temperature`` (whatever layout the lookup maintains) is
    threaded through untouched.
    """
    nodes = csr_window(state.csr_offsets, state.csr_nodes,
                       res.hit, res.head, max_locs)
    return finish_context(state, res.hit, nodes, temperature, n=n)


def csr_window(csr_offsets: jax.Array, csr_nodes: jax.Array,
               hit: jax.Array, head: jax.Array,
               max_locs: int) -> jax.Array:
    """Per-query CSR location window ``(B, max_locs)``, NULL-padded.

    Misses route to the *empty sentinel row* ``R = len(csr_offsets) - 1``:
    the terminal offset is a valid row index whose window ``[terminal,
    min(R+1, R)) = [terminal, terminal)`` is empty by construction, so a
    low-hit-rate batch gathers nothing for its misses instead of pulling
    CSR row 0's full window plus hierarchy walks and masking it after the
    fact.  Bit-identical to the old clamp-to-0 form (the window mask
    already ANDed with ``hit``); no pad row is required, so it holds for
    both ``pad_csr``-staged and raw ``from_index`` states.
    """
    r = csr_offsets.shape[0] - 1
    eid = jnp.where(hit, head, r)                            # (B,) CSR rows
    lo = csr_offsets[eid]                                    # (B,)
    count = csr_offsets[jnp.minimum(eid + 1, r)] - lo
    k = jnp.arange(max_locs, dtype=jnp.int32)                # (max_locs,)
    idx = lo[:, None] + k[None, :]
    valid = (k[None, :] < count[:, None]) & hit[:, None]
    safe = jnp.clip(idx, 0, csr_nodes.shape[0] - 1)
    return jnp.where(valid, csr_nodes[safe], NULL)           # (B, max_locs)


@functools.partial(jax.jit, static_argnames="n")
def hierarchy_windows(parent: jax.Array, entity_id: jax.Array,
                      child_offsets: jax.Array, child_index: jax.Array,
                      nodes: jax.Array, n: int):
    """Ancestor and descendant windows ``(B, max_locs, n)`` of a location
    window ``nodes`` ``(B, max_locs)``; NULL locations give NULL windows.

    One program per shape, jitted here at module level so an eager caller
    compiles it once per ``(nodes shape, forest shape, n)`` and never
    again; under an enclosing ``jit`` or ``shard_map`` it is a nested
    call."""
    flat = nodes.reshape(-1)
    up = gather_hierarchy(parent, entity_id, flat, n)
    down = gather_descendants(child_offsets, child_index, entity_id, flat, n)
    shape = nodes.shape + (n,)
    return up.reshape(shape), down.reshape(shape)


def finish_context(state, hit: jax.Array, nodes: jax.Array,
                   temperature: jax.Array, n: int = 3) -> DeviceRetrieval:
    """Hierarchy windows for an already-gathered location window — the
    forest-walk tail shared by :func:`gather_context` and the sharded
    owner-fused path (which routes ``(hit, locations)`` back through the
    all-to-all and walks the replicated forest locally)."""
    up, down = hierarchy_windows(state.parent, state.entity_id,
                                 state.child_offsets, state.child_index,
                                 nodes, n=n)
    return DeviceRetrieval(hit=hit, locations=nodes, up=up, down=down,
                           temperature=temperature)


def build_retriever(trees, num_buckets: int = 1024, **kw) -> CFTRAG:
    """Convenience: edge lists -> forest -> index -> retriever."""
    from .tree import build_forest
    forest = build_forest(trees)
    index = build_index(forest, num_buckets=num_buckets)
    return CFTRAG(index, **kw)
