"""Ragged filter-bank arena — T per-tree cuckoo filters in one flat table.

The paper's headline claim ("hundreds of times faster than naive Tree-RAG
when the number of trees is large") needs the many-tree regime: one cuckoo
filter *per tree*.  Real entity forests are skewed — one hospital tree can
hold 16x the entities of its neighbours — so padding every tree to the
hottest tree's bucket count (the old dense ``(T, NB, S)`` layout) wastes
device bytes and turns any expansion into a whole-bank restage.  The bank
therefore stores a **ragged bucket arena**: each tree ``t`` owns an
independent power-of-two bucket count ``tree_nb[t]``, its buckets live as
the contiguous arena segment ``[bucket_offsets[t], bucket_offsets[t+1])``
of one flat ``(total_buckets, S)`` table, and a routed lookup probes rows
``bucket_offsets[t] + (i & (tree_nb[t] - 1))``.  Device bytes are
``sum(tree_nb)`` instead of ``T * max(tree_nb)``, and growing one hot tree
restages only that tree's segment (``repro.core.maintenance``).

Build path: instead of a per-entity Python insert loop, the bank is built in
one vectorized pass over *all* trees at once.  Hash, fingerprint and both
candidate buckets are computed for every (tree, entity) item in a single
numpy batch with per-item bucket masks, empty slots are claimed by grouped
rank assignment (``repro.core.cuckoo.bulk_place``), and only the tiny
two-choice remainder walks the scalar eviction chain.  If any kick chain
exhausts, only the failing tree doubles its bucket count and the bank
rebuilds — the vectorized pass makes that cheap.

Slot payloads are *bank CSR rows*: each (tree, entity) pair that occurs in
the forest owns one row of ``csr_offsets``/``csr_nodes`` listing the node
ids of that entity within that tree.  A routed lookup therefore yields only
locations inside the queried tree — no cross-tree leakage by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing
from .cuckoo import (DEFAULT_LOAD_THRESHOLD, DEFAULT_MAX_KICKS,
                     DEFAULT_SLOTS, NULL, bulk_place)
from .tree import EntityForest

DEFAULT_LOAD_TARGET = 0.85         # size nb_t so per-tree load stays under this
EMPTY_TREE_NB = 1                  # buckets for a tree holding zero entities


@dataclasses.dataclass
class FilterBank:
    """T per-tree cuckoo filters as one ragged arena + the CSR location
    arena.  ``fingerprints``/``temperature``/``heads``/``entity_ids``/
    ``stored_hash`` are flat ``(total_buckets, S)``; tree ``t`` owns arena
    rows ``[bucket_offsets[t], bucket_offsets[t+1])`` with its own
    power-of-two ``tree_nb[t]``."""
    num_trees: int
    tree_nb: np.ndarray            # (T,) int32 — per-tree buckets, powers of 2
    bucket_offsets: np.ndarray     # (T + 1,) int64 — arena segment starts
    slots: int
    fingerprints: np.ndarray       # (A, S) uint32 — 0 = empty
    temperature: np.ndarray        # (A, S) int32
    heads: np.ndarray              # (A, S) int32 — bank CSR row id
    entity_ids: np.ndarray         # (A, S) int32 — global entity id
    stored_hash: np.ndarray        # (A, S) uint32 — host-only (restage)
    csr_offsets: np.ndarray        # (R + 1,) int32
    csr_nodes: np.ndarray          # (L,) int32 — global node ids per row
    row_tree: np.ndarray           # (R,) int32
    row_entity: np.ndarray         # (R,) int32
    num_items: np.ndarray          # (T,) int32
    build_stats: Dict[str, int]

    # --------------------------------------------------------------- sizes
    @property
    def num_rows(self) -> int:
        return int(self.row_tree.shape[0])

    @property
    def total_buckets(self) -> int:
        """Arena rows == sum(tree_nb) — the quantity device bytes scale
        with (the dense layout paid T * max(tree_nb))."""
        return int(self.fingerprints.shape[0])

    @property
    def num_buckets(self) -> int:
        """Uniform per-tree bucket count.  Only defined while every tree
        shares one nb (a forced-uniform build, or a balanced forest before
        any tree-local expansion); a ragged bank raises."""
        nb = int(self.tree_nb[0])
        if np.any(self.tree_nb != nb):
            raise ValueError(
                f"bank is ragged (tree_nb in [{int(self.tree_nb.min())}, "
                f"{int(self.tree_nb.max())}]): no uniform num_buckets")
        return nb

    @property
    def load_factors(self) -> np.ndarray:
        return self.num_items / (self.tree_nb.astype(np.float64)
                                 * self.slots)

    def segment(self, tree: int) -> Tuple[int, int]:
        """Arena row range [lo, hi) owned by ``tree``."""
        return (int(self.bucket_offsets[tree]),
                int(self.bucket_offsets[tree + 1]))

    def arena_base_mask(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-arena-row (segment start, bucket mask) — the rehoming tables
        ``bulk_place`` uses to keep a victim's kick inside its own tree."""
        base = np.repeat(self.bucket_offsets[:-1].astype(np.int64),
                         self.tree_nb)
        mask = np.repeat((self.tree_nb - 1).astype(np.uint32), self.tree_nb)
        return base, mask

    # ---------------------------------------------------------- host path
    def _find(self, tree: int, h: np.uint32) -> Optional[Tuple[int, int]]:
        nb = int(self.tree_nb[tree])
        lo = int(self.bucket_offsets[tree])
        fp = hashing.fingerprint(np.uint32(h))
        i1 = int(hashing.bucket_i1(np.uint32(h), nb))
        i2 = int(hashing.alt_bucket(np.uint32(i1), fp, nb))
        for i in (i1, i2):
            for s in range(self.slots):
                if self.fingerprints[lo + i, s] == fp:
                    return (i, s)
        return None

    def lookup(self, tree: int, h: int, bump: bool = False
               ) -> Tuple[bool, int, int]:
        """Sequential reference lookup: (hit, csr_row, entity_id)."""
        loc = self._find(tree, np.uint32(h))
        if loc is None:
            return False, NULL, NULL
        i, s = loc
        r = int(self.bucket_offsets[tree]) + i
        if bump:
            self.temperature[r, s] += 1
        return (True, int(self.heads[r, s]), int(self.entity_ids[r, s]))

    def contains(self, tree: int, h: int) -> bool:
        return self._find(tree, np.uint32(h)) is not None

    def find_exact(self, tree_ids: np.ndarray, hs: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized exact-hash slot search (host maintenance path).

        Unlike :meth:`lookup`, matches on the stored 32-bit hash rather
        than the 12-bit fingerprint, so a colliding neighbour can never
        shadow the queried entity.  Returns flat arena-row and slot
        indices, both -1 where the (tree, hash) is not stored.
        """
        tree_ids = np.asarray(tree_ids, np.int64)
        hq = np.asarray(hs, np.uint32)
        s = self.slots
        mask = (self.tree_nb[tree_ids] - 1).astype(np.uint32)
        fp = hashing.fingerprint(hq)
        i1 = hashing.bucket_i1_masked(hq, mask).astype(np.int64)
        i2 = hashing.alt_bucket_masked(i1.astype(np.uint32), fp,
                                       mask).astype(np.int64)
        base = self.bucket_offsets[tree_ids].astype(np.int64)
        cand = np.stack([base + i1, base + i2], axis=1)        # (k, 2)
        match = (self.stored_hash[cand] == hq[:, None, None]) & \
                (self.fingerprints[cand] != hashing.EMPTY_FP)  # (k, 2, S)
        flat = match.reshape(match.shape[0], -1)
        found = flat.any(axis=1)
        first = flat.argmax(axis=1)
        which, slot = first // s, first % s
        row = np.where(found, np.take_along_axis(
            cand, which[:, None], axis=1)[:, 0], -1)
        return row.astype(np.int64), np.where(found, slot, -1).astype(
            np.int64)

    def walk_row(self, row: int) -> List[int]:
        """Node ids of one (tree, entity) CSR row."""
        lo, hi = int(self.csr_offsets[row]), int(self.csr_offsets[row + 1])
        return [int(n) for n in self.csr_nodes[lo:hi]]

    def locate(self, tree: int, name: str) -> List[int]:
        """Routed host locate: node ids of ``name`` within ``tree``."""
        hit, row, _ = self.lookup(tree, int(hashing.entity_hash(name)))
        return self.walk_row(row) if hit and row >= 0 else []

    # -------------------------------------------------------------- device
    def tables(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Device-ready flat-arena (fingerprints, temperature, heads)."""
        return (self.fingerprints.copy(), self.temperature.copy(),
                self.heads.copy())

    def dense_tables(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Dense ``(T, NB, S)`` views of the arena — the layout the
        vmapped-over-trees paths consume.  Only defined for a uniform bank
        (raises on ragged); zero-copy reshape of the contiguous arena."""
        shape = (self.num_trees, self.num_buckets, self.slots)
        return (self.fingerprints.reshape(shape),
                self.temperature.reshape(shape),
                self.heads.reshape(shape))

    def absorb_temperature(self, device_state) -> int:
        """Write device-side temperature back into the host bank.

        ``device_state`` is a ``CFTDeviceState`` (or any object with a
        ``temperature`` attribute) or a bare ``(A, S)`` arena array.
        Returns the number of new bumps absorbed (sum of positive per-slot
        deltas) — the signal the maintenance sort trigger integrates.
        """
        temp = getattr(device_state, "temperature", device_state)
        temp = np.asarray(temp, dtype=np.int32)
        if temp.shape != self.temperature.shape:
            raise ValueError(f"temperature shape {temp.shape} != bank "
                             f"{self.temperature.shape} (stale layout?)")
        bumps = int(np.maximum(temp - self.temperature, 0).sum())
        self.temperature[...] = temp
        return bumps

    # ------------------------------------------------------------ sharding
    def shard(self, num_shards: Optional[int] = None,
              tree_starts: Optional[Sequence[int]] = None) -> "ShardedBank":
        """Partition the bank into contiguous tree ranges, one self-contained
        sub-bank per shard (mesh device).

        Each sub-bank relabels its trees to ``0..Td-1``, carves out its
        contiguous arena segment block and a local CSR arena holding only
        its own (tree, entity) rows, so the full :class:`MaintenanceEngine`
        machinery (insert/delete/expand/compact) runs per shard without
        touching any other shard's tables.  Slot placement, per-tree nb and
        slot ordering are *sliced*, not rebuilt, so a freshly sharded bank
        answers bit-identically to the original.
        """
        if tree_starts is None:
            if num_shards is None:
                raise ValueError("need num_shards or tree_starts")
            tree_starts = plan_partition(self.num_items, num_shards)
        starts = np.asarray(tree_starts, np.int64)
        if starts[0] != 0 or starts[-1] != self.num_trees or \
                np.any(np.diff(starts) < 1):
            raise ValueError(f"bad tree partition {starts.tolist()} for "
                             f"T={self.num_trees}")
        off = self.csr_offsets.astype(np.int64)
        boff = self.bucket_offsets.astype(np.int64)
        # carry only rows a filter slot still references: a maintained bank
        # may hold tombstoned CSR rows, and the per-shard engines rebuild
        # liveness from slots — a dangling row would resurrect on restage
        occ_slots = self.fingerprints != hashing.EMPTY_FP
        live = np.zeros(max(self.num_rows, 1), bool)
        live[self.heads[occ_slots]] = True
        banks: List[FilterBank] = []
        for d in range(starts.size - 1):
            lo, hi = int(starts[d]), int(starts[d + 1])
            alo, ahi = int(boff[lo]), int(boff[hi])
            rows = np.flatnonzero((self.row_tree >= lo)
                                  & (self.row_tree < hi)
                                  & live[:self.num_rows])
            inv = np.full(max(self.num_rows, 1), NULL, np.int32)
            inv[rows] = np.arange(rows.size, dtype=np.int32)
            lens = off[rows + 1] - off[rows]
            loc_off = np.zeros(rows.size + 1, dtype=np.int32)
            np.cumsum(lens, out=loc_off[1:])
            total = int(lens.sum())
            idx = (np.arange(total, dtype=np.int64)
                   + np.repeat(off[rows] - loc_off[:-1], lens))
            fps = self.fingerprints[alo:ahi].copy()
            occ = fps != hashing.EMPTY_FP
            heads = np.where(occ, inv[self.heads[alo:ahi]],
                             NULL).astype(np.int32)
            banks.append(FilterBank(
                num_trees=hi - lo,
                tree_nb=self.tree_nb[lo:hi].copy(),
                bucket_offsets=boff[lo:hi + 1] - alo,
                slots=self.slots, fingerprints=fps,
                temperature=self.temperature[alo:ahi].copy(), heads=heads,
                entity_ids=self.entity_ids[alo:ahi].copy(),
                stored_hash=self.stored_hash[alo:ahi].copy(),
                csr_offsets=loc_off,
                csr_nodes=(self.csr_nodes[idx].astype(np.int32) if total
                           else np.zeros(0, np.int32)),
                row_tree=(self.row_tree[rows] - lo).astype(np.int32),
                row_entity=self.row_entity[rows].copy(),
                num_items=self.num_items[lo:hi].copy(),
                build_stats=dict(self.build_stats)))
        return ShardedBank(tree_starts=starts.astype(np.int32), banks=banks)

    def sort_buckets(self) -> None:
        """Host-side idle-time adaptive sort over the whole arena: reorder
        every bucket's slots by descending temperature, empties last — the
        same stable ordering as the device-side ``sort_buckets_arena``, so
        host tables and a freshly restaged device state agree slot-for-slot.
        """
        key = np.where(self.fingerprints == hashing.EMPTY_FP,
                       np.int64(-2 ** 62),
                       self.temperature.astype(np.int64))
        order = np.argsort(-key, axis=1, kind="stable")
        for arr in (self.fingerprints, self.temperature, self.heads,
                    self.entity_ids, self.stored_hash):
            arr[...] = np.take_along_axis(arr, order, axis=1)


# ---------------------------------------------------------------- tenants

_ARENA_TABLES = ("fingerprints", "temperature", "heads", "entity_ids",
                 "stored_hash")


def _blank_tables(rows: int, slots: int) -> Dict[str, np.ndarray]:
    """Empty arena-table segment (misses on every probe)."""
    return dict(
        fingerprints=np.full((rows, slots), hashing.EMPTY_FP, np.uint32),
        temperature=np.zeros((rows, slots), np.int32),
        heads=np.full((rows, slots), NULL, np.int32),
        entity_ids=np.full((rows, slots), NULL, np.int32),
        stored_hash=np.zeros((rows, slots), np.uint32))


def _extract_tree_range(bank: FilterBank, lo: int, hi: int
                        ) -> Dict[str, np.ndarray]:
    """Copy of the arena-table rows owned by trees ``[lo, hi)``."""
    alo, ahi = int(bank.bucket_offsets[lo]), int(bank.bucket_offsets[hi])
    return {n: getattr(bank, n)[alo:ahi].copy() for n in _ARENA_TABLES}


def _replace_tree_range(bank: FilterBank, lo: int, hi: int,
                        tree_nb: np.ndarray, num_items: np.ndarray,
                        tables: Dict[str, np.ndarray]) -> None:
    """Replace trees ``[lo, hi)``'s arena segments and layout in place.

    The same splice shape as ``MaintenanceEngine._restage_tree`` but over
    a tree *range*: tables outside the range keep their bytes, CSR rows
    are never renumbered (cold heads stay valid), ``bucket_offsets``
    recomputes from the new per-tree counts."""
    alo, ahi = int(bank.bucket_offsets[lo]), int(bank.bucket_offsets[hi])
    for name in _ARENA_TABLES:
        old = getattr(bank, name)
        setattr(bank, name, np.concatenate([old[:alo], tables[name],
                                            old[ahi:]]))
    bank.tree_nb[lo:hi] = np.asarray(tree_nb, np.int32)
    off = np.zeros(bank.num_trees + 1, np.int64)
    np.cumsum(bank.tree_nb.astype(np.int64), out=off[1:])
    bank.bucket_offsets = off
    bank.num_items[lo:hi] = np.asarray(num_items, np.int32)


@dataclasses.dataclass
class ColdTenant:
    """Host-resident copy of one evicted tenant's bank content.

    ``tables`` hold the five arena tables of the tenant's tree range in
    global tree order (for a sharded bank: shard-local head payloads,
    concatenated across owning shards).  The CSR rows the heads reference
    stay in the live bank — tombstone compaction is pinned off while any
    tenant is cold — so a reload is a pure segment splice, bit-exact."""
    name: str
    lo: int                        # global tree range [lo, hi)
    hi: int
    tree_nb: np.ndarray            # (hi - lo,) int32
    num_items: np.ndarray          # (hi - lo,) int32
    tables: Dict[str, np.ndarray]  # five (sum(tree_nb), S) arena tables

    @property
    def arena_rows(self) -> int:
        return int(self.tree_nb.sum())


class TenantRegistry:
    """Tenant → contiguous tree-range map over one bank — the thin layer
    that generalizes the ragged arena (``bucket_offsets`` CSR) to a
    multi-tenant forest.

    Ranges must be disjoint; every fault-tolerance primitive upstream
    (admission quotas, per-tenant breakers, cold eviction) keys on the
    names registered here.  The registry owns the cold store: ``evict``
    copies a tenant's arena segments to host and blanks them in the live
    bank (its queries then miss — graceful degradation under arena
    memory pressure), ``reload``/``onboard`` splice content back.  Works
    identically over a replicated :class:`FilterBank` and a
    :class:`ShardedBank` (per owning shard, local coordinates)."""

    def __init__(self, ranges):
        items = (list(ranges.items()) if isinstance(ranges, dict)
                 else [(n, (lo, hi)) for n, lo, hi in ranges])
        items.sort(key=lambda kv: kv[1][0])
        prev = 0
        for name, (lo, hi) in items:
            if not 0 <= lo < hi:
                raise ValueError(f"tenant {name!r}: bad range [{lo}, {hi})")
            if lo < prev:
                raise ValueError(f"tenant {name!r} range [{lo}, {hi}) "
                                 "overlaps its predecessor")
            prev = hi
        self._ranges = {n: (int(lo), int(hi)) for n, (lo, hi) in items}
        self._starts = np.asarray([lo for lo, _ in self._ranges.values()],
                                  np.int64)
        self._names = list(self._ranges)
        self._cold: Dict[str, ColdTenant] = {}
        self._offboarded: set = set()

    # ------------------------------------------------------------- lookup
    @property
    def names(self) -> List[str]:
        return list(self._names)

    def trees(self, name: str) -> Tuple[int, int]:
        return self._ranges[name]

    def tenant_of(self, tree: int) -> Optional[str]:
        """Owning tenant of a global tree id, or None for unowned trees."""
        i = int(np.searchsorted(self._starts, int(tree), side="right")) - 1
        if i < 0:
            return None
        name = self._names[i]
        lo, hi = self._ranges[name]
        return name if lo <= int(tree) < hi else None

    def tenant_of_batch(self, tree_ids) -> Optional[str]:
        """Single owning tenant of a query batch; raises on a batch that
        straddles tenants (isolation would be unattributable)."""
        owners = {self.tenant_of(int(t)) for t in np.asarray(
            tree_ids, np.int64).ravel()}
        if len(owners) > 1:
            raise ValueError(f"batch spans tenants {sorted(map(str, owners))}")
        return next(iter(owners)) if owners else None

    # -------------------------------------------------------------- state
    def resident(self, name: str) -> bool:
        self.trees(name)               # raises on unknown tenant
        return name not in self._cold and name not in self._offboarded

    def cold(self, name: str) -> Optional[ColdTenant]:
        return self._cold.get(name)

    @property
    def any_cold(self) -> bool:
        return bool(self._cold)

    # ------------------------------------------------------------ surgery
    def _shard_pieces(self, bank, lo: int, hi: int):
        """(sub-bank, local lo, local hi) per owning shard, tree order."""
        if isinstance(bank, ShardedBank):
            out = []
            for d, b in enumerate(bank.banks):
                slo = int(bank.tree_starts[d])
                shi = int(bank.tree_starts[d + 1])
                a, z = max(lo, slo), min(hi, shi)
                if a < z:
                    out.append((b, a - slo, z - slo))
            return out
        return [(bank, lo, hi)]

    def evict(self, bank, name: str) -> ColdTenant:
        """Copy ``name``'s tree-range content to host and blank it in the
        live bank (each tree becomes an empty ``EMPTY_TREE_NB`` segment;
        its queries miss, its CSR rows are untouched).  The caller
        restages the device state and must pin compaction off while any
        tenant is cold."""
        if not self.resident(name):
            raise ValueError(f"tenant {name!r} is not resident")
        lo, hi = self.trees(name)
        pieces = self._shard_pieces(bank, lo, hi)
        tree_nb, num_items, tabs = [], [], []
        for b, llo, lhi in pieces:
            tree_nb.append(b.tree_nb[llo:lhi].copy())
            num_items.append(b.num_items[llo:lhi].copy())
            tabs.append(_extract_tree_range(b, llo, lhi))
        cold = ColdTenant(
            name=name, lo=lo, hi=hi,
            tree_nb=np.concatenate(tree_nb),
            num_items=np.concatenate(num_items),
            tables={n: np.concatenate([t[n] for t in tabs])
                    for n in _ARENA_TABLES})
        for b, llo, lhi in pieces:
            n = lhi - llo
            _replace_tree_range(
                b, llo, lhi,
                np.full(n, EMPTY_TREE_NB, np.int32), np.zeros(n, np.int32),
                _blank_tables(n * EMPTY_TREE_NB, b.slots))
        self._cold[name] = cold
        return cold

    def reload(self, bank, name: str,
               cold: Optional[ColdTenant] = None) -> None:
        """Splice an evicted (or externally restored) tenant's content
        back into its tree range — the exact inverse of :meth:`evict`."""
        cold = cold if cold is not None else self._cold.get(name)
        if cold is None:
            raise ValueError(f"tenant {name!r} has no cold copy")
        if (cold.lo, cold.hi) != self.trees(name):
            raise ValueError(
                f"cold copy of {name!r} covers trees [{cold.lo}, "
                f"{cold.hi}) but the registry maps {self.trees(name)}")
        row_off = np.zeros(cold.hi - cold.lo + 1, np.int64)
        np.cumsum(cold.tree_nb.astype(np.int64), out=row_off[1:])
        t0 = 0
        for b, llo, lhi in self._shard_pieces(bank, cold.lo, cold.hi):
            n = lhi - llo
            a, z = int(row_off[t0]), int(row_off[t0 + n])
            _replace_tree_range(
                b, llo, lhi, cold.tree_nb[t0:t0 + n],
                cold.num_items[t0:t0 + n],
                {k: v[a:z] for k, v in cold.tables.items()})
            t0 += n
        self._cold.pop(name, None)
        self._offboarded.discard(name)

    def offboard(self, bank, name: str) -> ColdTenant:
        """Evict and drop residency permanently: the tree range stays
        allocated (tree ids never shift under other tenants) but empty;
        the returned cold copy is the caller's to snapshot or discard."""
        cold = self.evict(bank, name)
        del self._cold[name]
        self._offboarded.add(name)
        return cold

    def onboard(self, bank, name: str, cold: ColdTenant) -> None:
        """Bring a tenant live into its (currently empty) tree range from
        a cold copy — e.g. one restored via ``core.snapshot``.  Only legal
        while the tenant is offboarded (or was never made resident after
        an offboard); a resident tenant must be evicted first."""
        if self.resident(name):
            raise ValueError(f"tenant {name!r} is already resident")
        self._offboarded.add(name)      # reload() clears both flags
        self._cold.pop(name, None)
        self.reload(bank, name, cold)


# --------------------------------------------------------------- sharding

def plan_partition(weights: np.ndarray, num_shards: int) -> np.ndarray:
    """Contiguous tree ranges balanced by per-tree weight (row counts).

    Returns ``starts`` of shape ``(num_shards + 1,)``: shard ``d`` owns
    global trees ``[starts[d], starts[d+1])``.  Boundaries sit at the
    quantiles of the cumulative weight, clamped so every shard owns at
    least one tree (requires ``T >= num_shards``).
    """
    w = np.asarray(weights, np.float64).ravel()
    t, d = w.size, int(num_shards)
    if d < 1:
        raise ValueError("num_shards must be >= 1")
    if t < d:
        raise ValueError(f"cannot spread {t} trees over {d} shards")
    if w.sum() <= 0:
        w = np.ones(t)
    cum = np.cumsum(w)
    starts = np.zeros(d + 1, np.int64)
    starts[d] = t
    for k in range(1, d):
        # side="right": a boundary exactly on the quantile closes the range
        # *after* that tree (equal weights then split perfectly evenly)
        b = int(np.searchsorted(cum, cum[-1] * k / d, side="right"))
        starts[k] = min(max(b, starts[k - 1] + 1), t - (d - k))
    return starts.astype(np.int32)


@dataclasses.dataclass
class ShardedBank:
    """Tree-range partitioned :class:`FilterBank` — the host mirror of the
    device-side bank-axis sharding in ``repro.core.distributed``.

    Shard ``d`` owns global trees ``[tree_starts[d], tree_starts[d+1])`` as
    a self-contained sub-bank (local tree ids, local bucket arena, local
    CSR arena), so every maintenance operation — insert, delete, compact,
    *expand* — is tree-local inside its owning shard: one hot tree
    outgrowing its buckets restages only its own arena segment while every
    other segment (same shard or not) stays byte-identical.  Per-tree
    ``tree_nb`` may therefore diverge freely; the packed device layout pads
    each shard's arena to the largest shard's row count and routes
    candidate-bucket arithmetic through the per-tree offsets/mask tables.

    Row numbering: the *merged* numbering (shard-major, ``shard_row_base``
    offsets) is canonical for a sharded bank — it is what the packed device
    ``heads`` payloads carry and what :meth:`walk_row` resolves.
    """
    tree_starts: np.ndarray        # (D + 1,) int32
    banks: List[FilterBank]

    # --------------------------------------------------------------- sizes
    @property
    def num_shards(self) -> int:
        return len(self.banks)

    @property
    def num_trees(self) -> int:
        return int(self.tree_starts[-1])

    @property
    def slots(self) -> int:
        return self.banks[0].slots

    @property
    def arena_rows_per_shard(self) -> int:
        """Padded per-shard arena row count of the packed device layout."""
        return max(b.total_buckets for b in self.banks)

    @property
    def total_buckets(self) -> int:
        """True (unpadded) arena rows across all shards."""
        return sum(b.total_buckets for b in self.banks)

    @property
    def num_items(self) -> np.ndarray:
        return np.concatenate([b.num_items for b in self.banks])

    @property
    def num_rows(self) -> int:
        return int(sum(b.num_rows for b in self.banks))

    # ------------------------------------------------------------- routing
    def tree_shard_map(self) -> np.ndarray:
        """(T,) int32: owning shard of every global tree."""
        return np.repeat(np.arange(self.num_shards, dtype=np.int32),
                         np.diff(self.tree_starts))

    def tree_local_map(self) -> np.ndarray:
        """(T,) int32: local tree index within the owning shard."""
        t = np.arange(self.num_trees, dtype=np.int32)
        return t - self.tree_starts[self.tree_shard_map()]

    def tree_arena_offsets(self) -> np.ndarray:
        """(T,) int64: each tree's segment start *within its owning
        shard's block* — the generalization of the old per-shard NB table
        to a per-tree offsets table (the probe adds ``h & (nb_t - 1)``)."""
        return np.concatenate(
            [b.bucket_offsets[:-1].astype(np.int64) for b in self.banks])

    def tree_nb_map(self) -> np.ndarray:
        """(T,) int32: per-tree bucket count in global tree order."""
        return np.concatenate([b.tree_nb for b in self.banks]).astype(
            np.int32)

    def owner(self, tree: int) -> Tuple[int, int]:
        """Global tree -> (shard, local tree)."""
        if not 0 <= tree < self.num_trees:
            raise ValueError(f"tree {tree} out of range "
                             f"[0, {self.num_trees})")
        d = int(np.searchsorted(self.tree_starts, tree, side="right")) - 1
        return d, tree - int(self.tree_starts[d])

    def shard_row_base(self) -> np.ndarray:
        """(D + 1,) merged-row offsets: shard d's local row r is merged row
        ``base[d] + r`` — the numbering the packed device heads carry."""
        base = np.zeros(self.num_shards + 1, np.int64)
        np.cumsum([b.num_rows for b in self.banks], out=base[1:])
        return base

    # ----------------------------------------------------------- host path
    def lookup(self, tree: int, h: int, bump: bool = False
               ) -> Tuple[bool, int, int]:
        """Routed host lookup; the returned row id is *merged* numbering."""
        d, lt = self.owner(tree)
        hit, row, eid = self.banks[d].lookup(lt, h, bump=bump)
        if hit and row >= 0:
            row += int(self.shard_row_base()[d])
        return hit, row, eid

    def contains(self, tree: int, h: int) -> bool:
        d, lt = self.owner(tree)
        return self.banks[d].contains(lt, h)

    def locate(self, tree: int, name: str) -> List[int]:
        d, lt = self.owner(tree)
        return self.banks[d].locate(lt, name)

    def walk_row(self, row: int) -> List[int]:
        """Node ids of one merged-numbering (tree, entity) row."""
        base = self.shard_row_base()
        d = int(np.searchsorted(base, row, side="right")) - 1
        return self.banks[d].walk_row(int(row - base[d]))

    # -------------------------------------------------------------- device
    def packed_tables(self, arena_rows: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Device-ready packed (fingerprints, temperature, heads).

        Shape ``(D * Apad, S)`` with ``Apad = arena_rows_per_shard``:
        shard d's arena occupies rows ``[d*Apad, d*Apad + A_d)``; padding
        rows hold empty fingerprints (never match).  Head payloads are
        merged row ids (``shard_row_base`` offsets applied).

        ``arena_rows`` raises ``Apad`` above the tight minimum — the
        in-place splice commit cannot shrink a live state's padding, so
        equivalence checks against such a state repack at its block size.
        """
        d, ap, s = self.num_shards, self.arena_rows_per_shard, self.slots
        ap = max(ap, int(arena_rows or 0))
        fps = np.full((d * ap, s), hashing.EMPTY_FP, np.uint32)
        temp = np.zeros((d * ap, s), np.int32)
        heads = np.full((d * ap, s), NULL, np.int32)
        base = self.shard_row_base()
        for k, b in enumerate(self.banks):
            blk = slice(k * ap, k * ap + b.total_buckets)
            fps[blk] = b.fingerprints
            temp[blk] = b.temperature
            occ = b.fingerprints != hashing.EMPTY_FP
            heads[blk] = np.where(occ, b.heads + np.int32(base[k]), NULL)
        return fps, temp, heads

    def merged_tables(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Replicated-reference arena ``(A, S)`` tables in global tree
        order with merged-row head payloads — the tables ``lookup_arena``
        probes (routed through :meth:`merged_layout`) to produce the
        sharded path's exact results.  Well-defined for any per-tree nb."""
        base = self.shard_row_base()
        fps = np.concatenate([b.fingerprints for b in self.banks], axis=0)
        temp = np.concatenate([b.temperature for b in self.banks], axis=0)
        heads = np.concatenate(
            [np.where(b.fingerprints != hashing.EMPTY_FP,
                      b.heads + np.int32(base[k]), NULL)
             for k, b in enumerate(self.banks)], axis=0)
        return fps, temp, heads

    def merged_layout(self) -> Tuple[np.ndarray, np.ndarray]:
        """(bucket_offsets (T+1,), tree_nb (T,)) of the merged arena."""
        nb = self.tree_nb_map()
        off = np.zeros(self.num_trees + 1, np.int64)
        np.cumsum(nb, out=off[1:])
        return off, nb

    def merged_csr(self) -> Tuple[np.ndarray, np.ndarray]:
        """Concatenated CSR arena in merged-row order (device staging)."""
        offsets = [np.zeros(1, np.int32)]
        nodes = []
        shift = 0
        for b in self.banks:
            offsets.append(b.csr_offsets[1:].astype(np.int32) + shift)
            nodes.append(b.csr_nodes)
            shift += int(b.csr_offsets[-1])
        return (np.concatenate(offsets),
                np.concatenate(nodes) if nodes else np.zeros(0, np.int32))

    # --------------------------------------------- temperature feedback
    def temperature_blocks(self, packed) -> List[np.ndarray]:
        """Slice a packed ``(D*Apad, S)`` device temperature into per-shard
        owner blocks ``(A_d, S)`` — padding rows are excluded, so each
        slot's bumps are harvested exactly once, against the owning shard's
        baseline only.  The device ``Apad`` may exceed the host's tight
        minimum (the in-place splice commit never shrinks a live state's
        padding after a tree shrink); any block size that still fits every
        shard's arena slices identically."""
        temp = np.asarray(getattr(packed, "temperature", packed), np.int32)
        d, ap = self.num_shards, self.arena_rows_per_shard
        ok = (temp.ndim == 2 and temp.shape[1] == self.slots
              and temp.shape[0] % d == 0 and temp.shape[0] // d >= ap)
        if not ok:
            raise ValueError(f"packed temperature shape {temp.shape} "
                             f"incompatible with {d} shards of >= {ap} "
                             f"arena rows (stale sharded layout?)")
        ap = temp.shape[0] // d
        return [temp[k * ap:k * ap + b.total_buckets]
                for k, b in enumerate(self.banks)]

    def absorb_temperature(self, device_state) -> int:
        """Write a packed sharded device temperature back into the host
        sub-banks; returns total new bumps (sum of positive deltas against
        each owning shard's own baseline — never double-counted across
        shards or padding)."""
        return sum(b.absorb_temperature(blk) for b, blk in
                   zip(self.banks, self.temperature_blocks(device_state)))

    def sort_buckets(self) -> None:
        for b in self.banks:
            b.sort_buckets()


# ------------------------------------------------- device-side splice ops
#
# The donated-buffer update ops of the double-buffered restage: a
# maintenance cycle that touched K arena rows commits as one in-place
# scatter of K staged rows (plus, after a tree resize, one segment splice)
# instead of re-staging the whole arena.  Donation makes the scatter
# in-place on backends that support it (TPU/GPU); elsewhere XLA falls back
# to a copy — semantics are identical either way, but the *old* buffers
# are invalidated, so callers must drop the pre-commit state.

@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def splice_arena_rows(fps, temp, heads, rows, vf, vt, vh, vkeep):
    """In-place donated scatter of staged rows into the live ``(A, S)``
    arena tables: ``rows`` is sentinel-padded (sentinel = A, out of
    bounds, dropped), the value tables carry the new row contents.  O(K)
    device work, O(K) host→device bytes.

    Temperature **max-merges** on slots whose key the plan leaves in
    place (``vkeep``): a bump that landed between ``plan_restage()`` and
    commit (serving continues on the old state through the prepare
    phase) lives only on device, so overwriting with the staged value
    would silently drop it.  Where the key moved (delete, eviction, sort)
    the slot's identity changed and the staged value wins — a bump for a
    departed key does not leak onto its successor.  ``vkeep`` is the
    *plan-time* mask ``staged fp == shadow fp`` — device fingerprints
    are immutable between commits, so the shadow is the live content;
    comparing against the donated ``fps`` here instead would race the
    in-place fps scatter (no data dependency orders them)."""
    live_t = jnp.where(vkeep, temp[rows], 0)
    return (fps.at[rows].set(vf, mode="drop"),
            temp.at[rows].set(jnp.maximum(vt, live_t), mode="drop"),
            heads.at[rows].set(vh, mode="drop"))


def pad_csr(offsets: np.ndarray, nodes: np.ndarray, chunk: int = 256
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad the replicated CSR staging arrays to a pow2-chunked capacity.

    The CSR arena grows with every insert batch; staged tight, each
    growth changes the device state's array shapes and forces the jitted
    retrieval step to recompile at *every* batch geometry — hundreds of
    milliseconds on the serve path per churn window.  Padding to the next
    power of two (floored at ``chunk`` entries) keeps the shapes constant
    until the arena actually doubles, so recompiles amortize like vector
    growth.  The pad tail is inert: ``offsets`` repeats the terminal
    offset (every pad row is empty) and ``nodes`` pads with zeros that no
    live row can address.  Every staging site (fresh stage and restage
    plan alike) must pad through here so splice-committed and
    from-scratch states stay byte-identical."""
    off = np.asarray(offsets, np.int32)
    nd = np.asarray(nodes, np.int32)
    if nd.size == 0:
        nd = np.zeros(1, np.int32)
    cap = lambda n: max(chunk, int(2 ** np.ceil(np.log2(n))))  # noqa: E731
    po = np.full(cap(off.size), off[-1], np.int32)
    po[:off.size] = off
    pn = np.zeros(cap(nd.size), np.int32)
    pn[:nd.size] = nd
    return po, pn


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def splice_arena_segment(fps, temp, heads, seg_f, seg_t, seg_h,
                         lo: int, hi: int):
    """Device-side segment splice: replace arena rows ``[lo, hi)`` with
    the staged segment (possibly of a different length — ``expand_tree``
    doubles it, ``shrink_tree`` halves it), leaving every other row's
    bytes untouched.  Only the new segment crosses the host→device link;
    the surrounding rows move at device bandwidth.  ``lo``/``hi`` are
    static (a resize changes the output shape — which is also why these
    buffers are not donated), so commits recompile per geometry — tree
    resizes are rare by design."""
    cat = lambda a, s: jnp.concatenate([a[:lo], s, a[hi:]])   # noqa: E731
    return cat(fps, seg_f), cat(temp, seg_t), cat(heads, seg_h)


# ------------------------------------------------------------------- build

def _bank_rows(forest: EntityForest):
    """Enumerate (tree, entity) rows and their node lists — fully
    vectorized: one lexsort of the forest's flat node arrays replaces the
    per-entity Python grouping loop.  Rows come out entity-major, trees
    ascending within an entity, node ids ascending within a row (the same
    order the host-side ``entity_locations`` walk produces)."""
    entity_hashes = hashing.hash_entities(forest.entity_names)
    n = forest.num_nodes
    if n == 0:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(1, np.int32), np.zeros(0, np.int32), entity_hashes)
    ent = forest.entity_id.astype(np.int64)
    tre = forest.tree_id.astype(np.int64)
    nodes = np.arange(n, dtype=np.int64)
    order = np.lexsort((nodes, tre, ent))      # by entity, tree, node
    e_s, t_s, n_s = ent[order], tre[order], nodes[order]
    new_row = np.r_[True, (e_s[1:] != e_s[:-1]) | (t_s[1:] != t_s[:-1])]
    row_tree = t_s[new_row].astype(np.int32)
    row_entity = e_s[new_row].astype(np.int32)
    counts = np.bincount(np.cumsum(new_row) - 1, minlength=row_tree.size)
    offsets = np.zeros(row_tree.size + 1, dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    return row_tree, row_entity, offsets, n_s.astype(np.int32), entity_hashes


def estimate_fpr(load, slots: int,
                 fp_bits: int = hashing.FP_BITS):
    """Empirical false-positive-rate estimate of a cuckoo-filter tree at
    the given load factor(s) — the observability half of the ROADMAP's
    self-tuning-bank item (the exemplar filters in SNIPPETS.md estimate
    FPR online from load and fingerprint bits the same way).

    A missing key probes its two candidate buckets, ~``2·slots·load``
    occupied slots, each holding a fingerprint uniform over the
    ``2^fp_bits - 1`` usable values (0 is the empty sentinel —
    ``hashing.fingerprint`` remaps real fingerprints off it), so

        FPR ≈ 1 - (1 - 1/(2^fp_bits - 1))^(2·slots·load)

    Accepts a scalar or an array of per-tree loads; returns the same
    shape as a float / float64 array.
    """
    p = 1.0 / ((1 << fp_bits) - 1)
    occupied = 2.0 * slots * np.asarray(load, np.float64)
    est = 1.0 - np.power(1.0 - p, occupied)
    return float(est) if est.ndim == 0 else est


def _pick_num_buckets(max_per_tree: int, slots: int,
                      load_target: float) -> int:
    need = max(1, int(np.ceil(max_per_tree / (slots * load_target))))
    nb = 4
    while nb < need:
        nb *= 2
    return nb


def _pick_tree_buckets(per_tree: np.ndarray, slots: int,
                       load_target: float) -> np.ndarray:
    """Vectorized per-tree bucket pick: the smallest power of two (>= 4)
    keeping that tree under ``load_target``; an *empty* tree gets the
    minimum ``EMPTY_TREE_NB`` instead of inheriting a shared NB — the
    ragged layout's fix for empty-tree over-allocation."""
    need = np.maximum(1, np.ceil(per_tree / (slots * load_target)))
    nb = np.maximum(4, 2 ** np.ceil(np.log2(need))).astype(np.int64)
    return np.where(per_tree > 0, nb, EMPTY_TREE_NB).astype(np.int64)


def _scalar_insert(fps: np.ndarray, temps: np.ndarray, heads: np.ndarray,
                   eids: np.ndarray, hs: np.ndarray, base: int, nb: int,
                   slots: int, h: int, row: int, eid: int, rng,
                   max_kicks: int, temp: int = 0) -> bool:
    """Scalar cuckoo insert into flat bank tables, confined to one tree's
    arena segment [base, base + nb).  Temperature rides along the kick
    chain so displaced hot slots keep their heat (matters for live
    maintenance; a fresh build passes all-zero temps)."""
    h = np.uint32(h)
    fp = hashing.fingerprint(h)
    i1 = int(hashing.bucket_i1(h, nb))
    i2 = int(hashing.alt_bucket(np.uint32(i1), fp, nb))
    for i in (base + i1, base + i2):
        empty = np.nonzero(fps[i] == hashing.EMPTY_FP)[0]
        if empty.size:
            s = int(empty[0])
            fps[i, s], heads[i, s], eids[i, s], hs[i, s] = fp, row, eid, h
            temps[i, s] = temp
            return True
    i = base + int(rng.choice((i1, i2)))
    cur = (np.uint32(fp), np.int32(temp), np.int32(row), np.int32(eid),
           np.uint32(h))
    for _ in range(max_kicks):
        s = int(rng.integers(slots))
        victim = (fps[i, s], temps[i, s], heads[i, s], eids[i, s], hs[i, s])
        fps[i, s], temps[i, s], heads[i, s], eids[i, s], hs[i, s] = cur
        cur = victim
        local = int(hashing.alt_bucket(np.uint32(i - base), cur[0], nb))
        i = base + local
        empty = np.nonzero(fps[i] == hashing.EMPTY_FP)[0]
        if empty.size:
            s = int(empty[0])
            fps[i, s], temps[i, s], heads[i, s], eids[i, s], hs[i, s] = cur
            return True
    return False


def build_bank_from_rows(num_trees: int, row_tree: np.ndarray,
                         row_entity: np.ndarray, row_hash: np.ndarray,
                         csr_offsets: np.ndarray, csr_nodes: np.ndarray,
                         num_buckets=None,
                         slots: int = DEFAULT_SLOTS, seed: int = 0x5EED,
                         bulk: bool = True,
                         max_kicks: int = DEFAULT_MAX_KICKS,
                         load_target: float = DEFAULT_LOAD_TARGET,
                         row_temp: Optional[np.ndarray] = None
                         ) -> FilterBank:
    """Build a bank directly from explicit (tree, entity) rows.

    The shared core of :func:`build_bank` (which derives rows from a
    forest), the maintenance engine's restage paths (which re-home the live
    rows of a mutated bank, ``row_temp`` carrying their temperatures), and
    the churn-equivalence tests (from-scratch reference for an
    incrementally maintained bank).

    ``num_buckets``: ``None`` picks per-tree ragged bucket counts
    (``_pick_tree_buckets``); an int forces that uniform NB on every tree
    (the dense-equivalent layout — kick-chain failure then doubles every
    tree, preserving uniformity); an array pins per-tree counts exactly
    (failure doubles only the failing tree).
    """
    T = max(1, int(num_trees))
    row_tree = np.asarray(row_tree, np.int32)
    row_entity = np.asarray(row_entity, np.int32)
    item_hash = np.asarray(row_hash, np.uint32)
    m = row_tree.shape[0]
    item_row = np.arange(m, dtype=np.int32)
    item_temp = (np.zeros(m, np.int32) if row_temp is None
                 else np.asarray(row_temp, np.int32))

    per_tree = np.bincount(row_tree, minlength=T) if m else \
        np.zeros(T, np.int64)
    uniform = num_buckets is not None and np.ndim(num_buckets) == 0
    if num_buckets is None:
        tree_nb = _pick_tree_buckets(per_tree, slots, load_target)
    elif uniform:
        tree_nb = np.full(T, int(num_buckets), np.int64)
    else:
        tree_nb = np.asarray(num_buckets, np.int64).copy()
    assert (tree_nb & (tree_nb - 1) == 0).all() and (tree_nb > 0).all(), \
        "power-of-two buckets per tree"

    rebuilds = -1
    while True:
        rebuilds += 1
        offsets = np.zeros(T + 1, np.int64)
        np.cumsum(tree_nb, out=offsets[1:])
        a = int(offsets[-1])
        rng = np.random.default_rng(seed)
        fps = np.full((a, slots), hashing.EMPTY_FP, dtype=np.uint32)
        temps = np.zeros((a, slots), dtype=np.int32)
        heads = np.full((a, slots), NULL, dtype=np.int32)
        eids = np.full((a, slots), NULL, dtype=np.int32)
        hs = np.zeros((a, slots), dtype=np.uint32)
        stats = {"items": int(m), "bulk_placed": 0, "evicted": 0,
                 "rebuilds": rebuilds}

        if bulk and m:
            item_mask = (tree_nb[row_tree] - 1).astype(np.uint32)
            fp = hashing.fingerprint(item_hash)
            i1 = hashing.bucket_i1_masked(item_hash, item_mask)
            i2 = hashing.alt_bucket_masked(i1, fp, item_mask)
            base = offsets[row_tree]
            arena_base = np.repeat(offsets[:-1], tree_nb)
            arena_mask = np.repeat((tree_nb - 1).astype(np.uint32),
                                   tree_nb)
            r_head, r_eid, r_hash, r_temp = bulk_place(
                fps, temps, heads, eids, hs, fp,
                base + i1.astype(np.int64), base + i2.astype(np.int64),
                item_row, row_entity, item_hash, nb=0, rng=rng,
                new_temps=item_temp, row_base=arena_base,
                row_mask=arena_mask)
            stats["bulk_placed"] = int(m - r_head.size)
            stats["evicted"] = int(r_head.size)
        else:
            r_head, r_eid, r_hash = item_row, row_entity, item_hash
            r_temp = item_temp

        ok = True
        for j in range(r_head.size):
            # a remainder item's tree is recoverable from its row payload
            tree = int(row_tree[int(r_head[j])])
            if not _scalar_insert(fps, temps, heads, eids, hs,
                                  int(offsets[tree]), int(tree_nb[tree]),
                                  slots, int(r_hash[j]),
                                  int(r_head[j]), int(r_eid[j]), rng,
                                  max_kicks, temp=int(r_temp[j])):
                ok = False
                # tree-local doubling: only the failing tree grows (unless
                # the caller forced a uniform layout)
                if uniform:
                    tree_nb = tree_nb * 2
                else:
                    tree_nb[tree] *= 2
                break
        if ok:
            over = per_tree >= DEFAULT_LOAD_THRESHOLD * tree_nb * slots
            if m == 0 or not over.any():
                break
            if uniform:
                tree_nb = tree_nb * 2
            else:
                tree_nb[over] *= 2

    return FilterBank(
        num_trees=T, tree_nb=tree_nb.astype(np.int32),
        bucket_offsets=offsets, slots=slots,
        fingerprints=fps, temperature=temps,
        heads=heads, entity_ids=eids, stored_hash=hs,
        csr_offsets=np.asarray(csr_offsets, np.int32),
        csr_nodes=np.asarray(csr_nodes, np.int32),
        row_tree=row_tree, row_entity=row_entity,
        num_items=np.bincount(row_tree, minlength=T).astype(np.int32),
        build_stats=stats,
    )


def build_bank(forest: EntityForest, num_buckets=None,
               slots: int = DEFAULT_SLOTS, seed: int = 0x5EED,
               bulk: bool = True, max_kicks: int = DEFAULT_MAX_KICKS,
               load_target: float = DEFAULT_LOAD_TARGET) -> FilterBank:
    """Build the bank for ``forest``.

    ``bulk=True`` (default) is the vectorized path: batched hashing +
    grouped empty-slot placement across all T trees at once, scalar kicks
    only for the remainder.  ``bulk=False`` inserts every item through the
    scalar path — kept as the equivalence/benchmark reference.
    ``num_buckets=None`` (default) sizes every tree independently (ragged
    arena); an int forces the uniform dense-equivalent layout.
    """
    row_tree, row_entity, csr_offsets, csr_nodes, entity_hashes = \
        _bank_rows(forest)
    m = row_tree.shape[0]
    item_hash = (entity_hashes[row_entity] if m
                 else np.zeros(0, np.uint32)).astype(np.uint32)
    return build_bank_from_rows(
        max(1, forest.num_trees), row_tree, row_entity, item_hash,
        csr_offsets, csr_nodes, num_buckets=num_buckets, slots=slots,
        seed=seed, bulk=bulk, max_kicks=max_kicks, load_target=load_target)
