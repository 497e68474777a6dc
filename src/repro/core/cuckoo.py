"""Improved Cuckoo Filter — the paper's core data structure.

Host side (numpy): partial-key cuckoo insertion with random-kick eviction
(Algorithm 1), deletion (Algorithm 2), load-factor-triggered power-of-two
expansion — the offline build path, exactly as the paper keeps filter
construction outside the query hot loop.

Device side: the tables are dense arrays (fingerprints / temperature / head
pointers per bucket slot) shipped to the accelerator; batched lookup lives in
``repro.core.lookup.lookup_arena`` (pure jnp reference; one filter is an
arena of one segment) and ``repro.kernels.cuckoo_lookup`` (Pallas TPU
kernel with identical semantics).

Each bucket slot stores, per the paper (§3.1): the entity's 12-bit
fingerprint, its temperature, and the head pointer of its block linked list.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from . import hashing
from .blocklist import BlockListArena, BlockListBuilder, CSRArena, build_csr
from .tree import EntityForest

NULL = -1
DEFAULT_SLOTS = 4                  # paper: 4 fingerprints per bucket
DEFAULT_MAX_KICKS = 500
DEFAULT_LOAD_THRESHOLD = 0.95      # expand beyond this


def bulk_place(fingerprints: np.ndarray, temperature: np.ndarray,
               heads: np.ndarray, entity_ids: np.ndarray,
               stored_hash: np.ndarray, fp: np.ndarray, b1: np.ndarray,
               b2: np.ndarray, new_heads: np.ndarray, new_eids: np.ndarray,
               new_hashes: np.ndarray, nb: int, rng,
               max_rounds: int = 48,
               new_temps: Optional[np.ndarray] = None,
               row_base: Optional[np.ndarray] = None,
               row_mask: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, ...]:
    """Vectorized cuckoo placement into flat ``(num_rows, S)`` tables.

    Rows may be a single filter's buckets, a whole uniform filter bank
    flattened to ``tree * NB + bucket``, or a ragged bucket arena — the
    routine only sees row indices.  A victim's alternate bucket is computed
    within its own filter's row range: for the uniform layouts ``nb``
    (per-filter bucket count) locates the range as ``(row // nb) * nb``;
    for a ragged arena the caller passes ``row_base``/``row_mask`` — per
    arena-row segment start and bucket mask ``nb_t - 1`` — and ``nb`` is
    ignored for rehoming.

    Each round: items grouped by candidate bucket claim that bucket's free
    slots by within-group rank (one fancy-indexed write for all of them);
    round 0 survivors retry their second choice; later survivors run a
    vectorized eviction — one leader per bucket swaps with a random victim
    slot, the victim re-enters the pool at its partner bucket (temperature
    rides along) and non-leaders flip to their other bucket.  Returns
    ``(heads, eids, hashes, temps)`` of the items still homeless after
    ``max_rounds`` — the scalar-fallback remainder, ~empty below the
    expansion load threshold.  ``new_temps`` seeds the incoming items'
    temperatures (restage path: live slots keep their heat); default 0.
    """
    pool_fp = np.asarray(fp, np.uint32).copy()
    pool_head = np.asarray(new_heads, np.int32).copy()
    pool_eid = np.asarray(new_eids, np.int32).copy()
    pool_hash = np.asarray(new_hashes, np.uint32).copy()
    pool_temp = (np.zeros(pool_fp.shape[0], np.int32) if new_temps is None
                 else np.asarray(new_temps, np.int32).copy())
    bucket = np.asarray(b1, np.int64).copy()
    other = np.asarray(b2, np.int64).copy()
    slots = fingerprints.shape[1]

    for rnd in range(max_rounds):
        if pool_fp.size == 0:
            break
        # ---- empty-slot pass at each item's current candidate bucket
        occupied = fingerprints != hashing.EMPTY_FP            # (rows, S)
        # k-th free slot of each row: stable argsort floats empties first
        free_pos = np.argsort(occupied, axis=1, kind="stable")
        free_cnt = (~occupied).sum(axis=1)
        order = np.argsort(bucket, kind="stable")
        bs = bucket[order]
        starts = np.flatnonzero(np.r_[True, bs[1:] != bs[:-1]])
        run_len = np.diff(np.append(starts, bs.size))
        rank = np.arange(bs.size) - np.repeat(starts, run_len)
        fits = rank < free_cnt[bs]
        rows = bs[fits]
        ss = free_pos[rows, rank[fits]]
        sel = order[fits]
        fingerprints[rows, ss] = pool_fp[sel]
        temperature[rows, ss] = pool_temp[sel]
        heads[rows, ss] = pool_head[sel]
        entity_ids[rows, ss] = pool_eid[sel]
        stored_hash[rows, ss] = pool_hash[sel]
        keep = order[~fits]
        pool_fp, pool_head = pool_fp[keep], pool_head[keep]
        pool_eid, pool_hash = pool_eid[keep], pool_hash[keep]
        pool_temp = pool_temp[keep]
        bucket, other = bucket[keep], other[keep]
        if pool_fp.size == 0:
            break
        if rnd == 0:                   # try every item's second choice once
            bucket, other = other, bucket
            continue
        # ---- vectorized eviction (survivor buckets are provably full)
        order = np.argsort(bucket, kind="stable")
        bs = bucket[order]
        is_lead = np.r_[True, bs[1:] != bs[:-1]]
        lead = order[is_lead]
        lb = bucket[lead]
        s = rng.integers(0, slots, size=lb.size)
        v = (fingerprints[lb, s].copy(), temperature[lb, s].copy(),
             heads[lb, s].copy(), entity_ids[lb, s].copy(),
             stored_hash[lb, s].copy())
        fingerprints[lb, s] = pool_fp[lead]
        temperature[lb, s] = pool_temp[lead]
        heads[lb, s] = pool_head[lead]
        entity_ids[lb, s] = pool_eid[lead]
        stored_hash[lb, s] = pool_hash[lead]
        if row_base is None:
            base = (lb // nb) * nb
            v_other = base + hashing.alt_bucket(
                (lb - base).astype(np.uint32), v[0], nb).astype(np.int64)
        else:
            base = row_base[lb]
            v_other = base + hashing.alt_bucket_masked(
                (lb - base).astype(np.uint32), v[0],
                row_mask[lb]).astype(np.int64)
        waiters = order[~is_lead]
        pool_fp = np.concatenate([pool_fp[waiters], v[0]])
        pool_temp = np.concatenate([pool_temp[waiters], v[1]])
        pool_head = np.concatenate([pool_head[waiters], v[2]])
        pool_eid = np.concatenate([pool_eid[waiters], v[3]])
        pool_hash = np.concatenate([pool_hash[waiters], v[4]])
        bucket, other = (np.concatenate([other[waiters], v_other]),
                         np.concatenate([bucket[waiters], lb]))
    return pool_head, pool_eid, pool_hash, pool_temp


@dataclasses.dataclass
class CuckooTables:
    """Device-ready views of the filter (plain arrays, jit-friendly)."""
    fingerprints: np.ndarray       # (NB, S) uint32 — 0 = empty
    temperature: np.ndarray        # (NB, S) int32
    heads: np.ndarray              # (NB, S) int32 — blocklist head / entity id
    entity_ids: np.ndarray         # (NB, S) int32 — for CSR mode & tests


class CuckooFilter:
    """Improved cuckoo filter with temperature + per-entity address lists."""

    def __init__(self, num_buckets: int = 1024, slots: int = DEFAULT_SLOTS,
                 max_kicks: int = DEFAULT_MAX_KICKS,
                 load_threshold: float = DEFAULT_LOAD_THRESHOLD,
                 seed: int = 0x5EED):
        assert num_buckets & (num_buckets - 1) == 0, "power-of-two buckets"
        self.num_buckets = num_buckets
        self.slots = slots
        self.max_kicks = max_kicks
        self.load_threshold = load_threshold
        self._rng = np.random.default_rng(seed)
        self._alloc(num_buckets)
        self.num_items = 0
        self.num_expansions = 0
        self.probes = 0              # slot comparisons (Figure 5 metric)
        self._touched: set = set()   # buckets hit since last sort

    # ------------------------------------------------------------- plumbing
    def _alloc(self, nb: int) -> None:
        s = self.slots
        self.fingerprints = np.full((nb, s), hashing.EMPTY_FP, dtype=np.uint32)
        self.temperature = np.zeros((nb, s), dtype=np.int32)
        self.heads = np.full((nb, s), NULL, dtype=np.int32)
        self.entity_ids = np.full((nb, s), NULL, dtype=np.int32)
        # host-only: original entity hash per slot, needed for expansion rehash
        self.stored_hash = np.zeros((nb, s), dtype=np.uint32)
        self.num_buckets = nb

    @property
    def load_factor(self) -> float:
        return self.num_items / (self.num_buckets * self.slots)

    def tables(self) -> CuckooTables:
        return CuckooTables(self.fingerprints.copy(), self.temperature.copy(),
                            self.heads.copy(), self.entity_ids.copy())

    # ------------------------------------------------------------ insertion
    def insert(self, h: int, head: int, entity_id: int) -> bool:
        """Algorithm 1 (+ auto-expansion). h is the 32-bit entity hash."""
        if self.load_factor >= self.load_threshold:
            self.expand()
        if not self._insert_once(np.uint32(h), head, entity_id):
            # the kick chain placed the new item but left one victim homeless
            # (stored in self._homeless); expansion rehashes + re-homes it.
            self.expand()          # paper: expansion on insertion failure
        return True

    def _insert_once(self, h: np.uint32, head: int, entity_id: int) -> bool:
        nb = self.num_buckets
        fp = hashing.fingerprint(np.uint32(h))
        i1 = int(hashing.bucket_i1(np.uint32(h), nb))
        i2 = int(hashing.alt_bucket(np.uint32(i1), fp, nb))
        for i in (i1, i2):
            s = self._empty_slot(i)
            if s is not None:
                self._write(i, s, fp, 0, head, entity_id, h)
                self.num_items += 1
                return True
        # eviction loop
        i = int(self._rng.choice((i1, i2)))
        cur = (np.uint32(fp), np.int32(0), np.int32(head),
               np.int32(entity_id), np.uint32(h))
        for _ in range(self.max_kicks):
            s = int(self._rng.integers(self.slots))
            victim = (self.fingerprints[i, s], self.temperature[i, s],
                      self.heads[i, s], self.entity_ids[i, s],
                      self.stored_hash[i, s])
            self._write(i, s, *self._unpack(cur))
            cur = victim
            i = int(hashing.alt_bucket(np.uint32(i), cur[0], self.num_buckets))
            s2 = self._empty_slot(i)
            if s2 is not None:
                self._write(i, s2, *self._unpack(cur))
                self.num_items += 1
                return True
        # undo is unnecessary: displaced chain still stores every element,
        # `cur` is the one item left homeless — reinsert it after expansion.
        self._homeless = cur
        return False

    def insert_many(self, hashes: Sequence[int], heads: Sequence[int],
                    entity_ids: Sequence[int]) -> None:
        """Vectorized bulk build: batched hash/fingerprint/bucket compute,
        vectorized empty-slot placement via ``bulk_place``, then the scalar
        eviction path only for the small remainder.  Same membership and
        payload semantics as calling :meth:`insert` per item."""
        hashes = np.asarray(hashes, dtype=np.uint32)
        new_heads = np.asarray(heads, dtype=np.int32)
        new_eids = np.asarray(entity_ids, dtype=np.int32)
        n = int(hashes.shape[0])
        if n == 0:
            return
        # pre-expand so the final load factor stays under the threshold,
        # matching where sequential insertion would have ended up
        while ((self.num_items + n)
               / (self.num_buckets * self.slots) >= self.load_threshold):
            self.expand()
        fp = hashing.fingerprint(hashes)
        i1 = hashing.bucket_i1(hashes, self.num_buckets)
        i2 = hashing.alt_bucket(i1, fp, self.num_buckets)
        r_head, r_eid, r_hash, r_temp = bulk_place(
            self.fingerprints, self.temperature, self.heads,
            self.entity_ids, self.stored_hash, fp, i1.astype(np.int64),
            i2.astype(np.int64), new_heads, new_eids, hashes,
            nb=self.num_buckets, rng=self._rng)
        self.num_items += n - r_head.size
        for j in range(r_head.size):   # rare remainder — scalar kick chains
            self.insert(int(r_hash[j]), int(r_head[j]), int(r_eid[j]))
            if r_temp[j]:              # displaced survivors keep their heat
                self._set_temp_of(np.uint32(r_hash[j]), int(r_temp[j]))

    @staticmethod
    def _unpack(item):
        fp, t, head, eid, h = item
        return np.uint32(fp), int(t), int(head), int(eid), np.uint32(h)

    def _write(self, i: int, s: int, fp: np.uint32, temp: int, head: int,
               entity_id: int, h: np.uint32) -> None:
        self.fingerprints[i, s] = fp
        self.temperature[i, s] = temp
        self.heads[i, s] = head
        self.entity_ids[i, s] = entity_id
        self.stored_hash[i, s] = h

    def _empty_slot(self, i: int) -> Optional[int]:
        empty = np.nonzero(self.fingerprints[i] == hashing.EMPTY_FP)[0]
        return int(empty[0]) if empty.size else None

    # ------------------------------------------------------------- expansion
    def expand(self) -> None:
        """Double the bucket count and rehash every element (paper §1)."""
        old = (self.fingerprints, self.temperature, self.heads,
               self.entity_ids, self.stored_hash)
        homeless = getattr(self, "_homeless", None)
        self._homeless = None
        self._alloc(self.num_buckets * 2)
        self.num_items = 0
        self.num_expansions += 1
        fps, temps, heads, eids, hs = old
        occ = np.nonzero(fps != hashing.EMPTY_FP)
        for i, s in zip(*occ):
            ok = self._insert_once(hs[i, s], int(heads[i, s]), int(eids[i, s]))
            if ok:   # preserve temperature through migration
                self._set_temp_of(hs[i, s], int(temps[i, s]))
            else:
                self.expand()      # extremely unlikely at 0.5 load
        if homeless is not None:
            fp, t, head, eid, h = homeless
            self._insert_once(np.uint32(h), int(head), int(eid))
            self._set_temp_of(np.uint32(h), int(t))

    def _set_temp_of(self, h: np.uint32, temp: int) -> None:
        hit = self._find(h)
        if hit is not None:
            self.temperature[hit] = temp

    # ------------------------------------------------------ lookup / delete
    def _find(self, h: np.uint32) -> Optional[Tuple[int, int]]:
        nb = self.num_buckets
        fp = hashing.fingerprint(np.uint32(h))
        i1 = int(hashing.bucket_i1(np.uint32(h), nb))
        i2 = int(hashing.alt_bucket(np.uint32(i1), fp, nb))
        for i in (i1, i2):
            for s in range(self.slots):       # linear scan, paper semantics
                self.probes += 1
                if self.fingerprints[i, s] == fp:
                    self._touched.add(i)
                    return (i, s)
        return None

    def lookup(self, h: int, bump: bool = True) -> Tuple[bool, int]:
        """Sequential host lookup (reference; Algorithm 3 head). Returns
        (hit, head_ptr) and bumps temperature on hit."""
        hit = self._find(np.uint32(h))
        if hit is None:
            return False, NULL
        if bump:
            self.temperature[hit] += 1
        return True, int(self.heads[hit])

    def lookup_entry(self, h: int, bump: bool = True
                     ) -> Tuple[bool, int, int]:
        """Like :meth:`lookup` but also returns the slot's entity-id payload
        — the CSR retrieval path must use this rather than re-resolving the
        query name, so filter hits and arena hits stay consistent."""
        hit = self._find(np.uint32(h))
        if hit is None:
            return False, NULL, NULL
        if bump:
            self.temperature[hit] += 1
        return True, int(self.heads[hit]), int(self.entity_ids[hit])

    def contains(self, h: int) -> bool:
        return self._find(np.uint32(h)) is not None

    def delete(self, h: int) -> bool:
        """Algorithm 2 — remove fingerprint + its slot payload."""
        hit = self._find(np.uint32(h))
        if hit is None:
            return False
        i, s = hit
        self._write(i, s, np.uint32(hashing.EMPTY_FP), 0, NULL, NULL,
                    np.uint32(0))
        self.num_items -= 1
        return True

    # ---------------------------------------------------- temperature sort
    def sort_buckets(self, touched_only: bool = True) -> None:
        """Reorder bucket slots by descending temperature (paper §3.1
        'adaptive sorting' — done when the bucket is idle); empty slots
        sink to the end.  ``touched_only`` sorts just the buckets hit since
        the previous sort (the paper's 'if it is free' condition in
        practice: untouched buckets cannot have changed order)."""
        if touched_only and self._touched is not None:
            rows = np.fromiter(self._touched, dtype=np.int64,
                               count=len(self._touched))
            if rows.size == 0:
                return
        else:
            rows = slice(None)
        key = np.where(self.fingerprints[rows] == hashing.EMPTY_FP,
                       np.int64(-2**62),
                       self.temperature[rows].astype(np.int64))
        order = np.argsort(-key, axis=1, kind="stable")
        for arr in (self.fingerprints, self.temperature, self.heads,
                    self.entity_ids, self.stored_hash):
            arr[rows] = np.take_along_axis(arr[rows], order, axis=1)
        self._touched = set()


# ---------------------------------------------------------------- assembly

@dataclasses.dataclass
class CFTIndex:
    """Complete CFT-RAG retrieval index: filter + address arena + forest."""
    filter: CuckooFilter
    arena: BlockListArena          # faithful layout
    csr: CSRArena                  # optimized layout
    forest: EntityForest
    entity_hashes: np.ndarray      # (num_entities,) uint32, by entity id


def build_index(forest: EntityForest, num_buckets: int = 1024,
                slots: int = DEFAULT_SLOTS, block_cap: int = 4,
                seed: int = 0x5EED) -> CFTIndex:
    """Find all locations of each entity in the forest, store their addresses
    as block linked lists, and insert fingerprints+heads into the filter."""
    builder = BlockListBuilder(block_cap=block_cap)
    heads = [builder.add_entity(locs) for locs in forest.entity_locations]
    arena = builder.build()
    csr = build_csr(forest.entity_locations)
    hashes = hashing.hash_entities(forest.entity_names)
    filt = CuckooFilter(num_buckets=num_buckets, slots=slots, seed=seed)
    filt.insert_many(hashes, heads, np.arange(len(heads), dtype=np.int32))
    return CFTIndex(filter=filt, arena=arena, csr=csr, forest=forest,
                    entity_hashes=hashes)
