"""Bank-axis sharding — tree-range partitioned FilterBank over the mesh.

The paper's many-tree regime ("hundreds of times faster ... when the number
of trees is large") only scales past one device if the *tree axis* shards:
a replicated bank caps T at a single device's memory and adding devices
buys nothing.  Here the bank partitions into contiguous tree ranges over
the ``model`` mesh axis (``FilterBank.shard`` / ``plan_partition`` pick
ranges balanced by per-tree row counts) and queries travel to their data
instead of the data being everywhere:

1. each device holds its slice of the query batch; a query's owning shard
   comes from the replicated ``tree_shard`` routing table;
2. queries bucket by destination and exchange once with
   ``jax.lax.all_to_all`` inside ``shard_map`` (no full-bank broadcast) —
   the receive buffer is worst-case sized by default, or shrunk with a
   ``capacity_factor`` (two-pass: a tiny count exchange first, the factor
   as fast path when the measured counts fit, adaptive growth when not);
3. every shard probes only its own **packed ragged arena block**
   ``(Apad, S)`` — per-tree routing reads each query's arena segment start
   and bucket mask from the replicated per-tree offsets table (the
   generalization of the old per-shard NB table), so tree-local
   expansions diverge per-tree bucket counts freely and the probe is
   bit-identical everywhere;
4. results (and nothing else) route back through the inverse all-to-all —
   there is no max-reduce over replicas anywhere.

Temperature bumps land in the owning shard's arena block during the probe,
so the paper's feedback loop stays shard-local too; the host harvests with
``ShardedBank.absorb_temperature`` (per-shard baselines, never
double-counted).

The legacy single-filter helpers (``shard_filter_tables`` +
``sharded_lookup``) are thin wrappers over the same router: a bucket-striped
filter is just a degenerate bank whose "trees" are the D bucket stripes,
with each query fanned to its two candidate stripes and the pair merged
with i1 priority.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..obs import get_registry
from . import hashing
from .bank import FilterBank, ShardedBank, pad_csr
from .lookup import LookupResult, lookup_arena, sort_buckets_arena
from .tree import EntityForest
from .trag import CFTDeviceState, DeviceRetrieval, gather_context

NULL = -1


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type ``Auto``.

    ``jax.make_mesh`` hands out ``Explicit`` axes, under which every
    array carries its sharding in its type and a slice or gather along a
    sharded dimension must say where its result lives.  The router and
    the model shardings here are written for the compiler's automatic
    propagation, so each mesh that enters the program passes through
    this one conversion."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# ---------------------------------------------------------------- router

def _exchange(buf: jax.Array, axis: str) -> jax.Array:
    """One all-to-all hop: local ``(D, C, ...)`` buffer -> local
    ``(D, C, ...)`` buffer whose row s holds what source shard s sent us.
    Involutive — the same call routes results back."""
    return jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)


def _bucket_queries(dest: jax.Array, num_shards: int, capacity: int,
                    payloads: Tuple[Tuple[jax.Array, object], ...]
                    ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Pack per-query payloads into fixed ``(D, C)`` destination buckets.

    ``dest``: (Bl,) destination shard per local query.  ``capacity`` C
    defaults to Bl upstream (the degenerate case routes every local query
    to one shard, so nothing can overflow); a smaller C comes from the
    two-pass count exchange (``_pick_capacity``), which sizes it from the
    batch's actual per-pair maximum — in-kernel the scatter still drops
    out-of-capacity lanes rather than corrupting memory.  Returns each
    query's slot ``rank`` within its bucket — the return address for
    ``_route_back`` — plus one ``(D, C)`` buffer per (payload, fill) pair.
    """
    bl = dest.shape[0]
    order = jnp.argsort(dest)                       # stable
    counts = jnp.bincount(dest, length=num_shards)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    within = (jnp.arange(bl) - starts[dest[order]]).astype(jnp.int32)
    rank = jnp.zeros((bl,), jnp.int32).at[order].set(within)
    bufs = tuple(
        jnp.full((num_shards, capacity), fill, x.dtype)
        .at[dest, rank].set(x, mode="drop")
        for x, fill in payloads)
    return rank, bufs


def _route_back(x: jax.Array, dest: jax.Array, rank: jax.Array,
                axis: str, num_shards: int) -> jax.Array:
    """Send per-slot probe results home and unscatter to query order."""
    recv = _exchange(x.reshape(num_shards, -1), axis)
    return recv[dest, rank]


# ------------------------------------------------------- sharded bank state

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedBankState:
    """Device-side bank-axis sharded retrieval state.

    Filter tables are *packed ragged arenas*: shard d's bucket arena lives
    in rows ``[d*Apad, d*Apad + A_d)`` of a ``(D*Apad, S)`` tensor placed
    ``P(axis, None)`` over the mesh, so each device holds exactly one
    shard's arena (true bytes ``sum_t nb_t`` per shard, padding to the
    largest shard aside) — the old dense ``(D*Tpad, NBmax, S)``
    pad-to-max-NB blocks are gone.  Routing tables, the merged CSR
    location arena and the forest hierarchy arrays are replicated — they
    are O(T) / O(rows), not O(arena).

    ``tree_offset``/``tree_nb`` carry each tree's segment start within its
    owning shard's block and its own power-of-two bucket count: the probe
    computes ``tree_offset[t] + (i & (tree_nb[t] - 1))``, so shard- and
    tree-local expansions diverge bucket counts without any uniform-NB
    special case.  ``mesh``/``axis`` are static (pytree aux), so the state
    passes through ``jax.jit`` like any other pytree.
    """
    fingerprints: jax.Array   # (D*Apad, S) uint32, P(axis, None)
    temperature: jax.Array    # (D*Apad, S) int32
    heads: jax.Array          # (D*Apad, S) int32 — merged CSR row ids
    tree_shard: jax.Array     # (T,) int32 — owning shard, replicated
    tree_offset: jax.Array    # (T,) int32 — segment start in owner's block
    tree_nb: jax.Array        # (T,) int32 — per-tree bucket count
    csr_offsets: jax.Array    # (R + 1,) int32 — merged arena, replicated
    csr_nodes: jax.Array      # (L,) int32
    parent: jax.Array         # (N,) int32 — forest arrays, replicated
    entity_id: jax.Array      # (N,) int32
    child_offsets: jax.Array  # (N + 1,) int32
    child_index: jax.Array    # (C,) int32
    mesh: Mesh                # static
    axis: str                 # static

    _LEAVES = ("fingerprints", "temperature", "heads", "tree_shard",
               "tree_offset", "tree_nb", "csr_offsets", "csr_nodes",
               "parent", "entity_id", "child_offsets", "child_index")

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._LEAVES),
                (self.mesh, self.axis))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # --------------------------------------------------------------- sizes
    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def arena_rows_per_shard(self) -> int:
        return int(self.fingerprints.shape[0]) // self.num_shards

    @property
    def num_trees(self) -> int:
        return int(self.tree_shard.shape[0])

    @property
    def slots(self) -> int:
        return int(self.fingerprints.shape[-1])

    # ----------------------------------------------------------- threading
    def with_temperature(self, temperature: jax.Array) -> "ShardedBankState":
        """Thread an updated packed temperature forward (same contract as
        ``CFTDeviceState.with_temperature``)."""
        return dataclasses.replace(self, temperature=temperature)

    def sort_idle(self) -> "ShardedBankState":
        """Device-only idle-time bucket sort over every shard's arena at
        once (pure per-bucket slot reorder — sharding is preserved).  As
        with ``CFTDeviceState.sort_idle``: only for states with no host
        bank mirror; a host ``ShardedMaintenanceEngine`` sorts + restages
        instead so layouts never diverge."""
        f, t, h = sort_buckets_arena(self.fingerprints, self.temperature,
                                     self.heads)
        return dataclasses.replace(self, fingerprints=f, temperature=t,
                                   heads=h)


def stage_sharded_bank(sbank: ShardedBank, forest: EntityForest,
                       mesh: Mesh, axis: str = "model",
                       arena_rows: Optional[int] = None
                       ) -> ShardedBankState:
    """Place a host :class:`ShardedBank` on the mesh as a
    :class:`ShardedBankState` (packed arena blocks sharded over ``axis``,
    routing/CSR/forest replicated).  ``arena_rows`` forces a larger
    per-shard block than the tight minimum — used to compare against a
    live state whose padding an in-place commit could not shrink."""
    mesh = auto_axes(mesh)
    d = int(mesh.shape[axis])
    if d != sbank.num_shards:
        raise ValueError(f"bank has {sbank.num_shards} shards but mesh "
                         f"axis '{axis}' has {d} devices")
    fps, temp, heads = sbank.packed_tables(arena_rows=arena_rows)
    csr_off, csr_nodes = pad_csr(*sbank.merged_csr())
    blk = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    put_b = lambda a: jax.device_put(jnp.asarray(a), blk)     # noqa: E731
    put_r = lambda a: jax.device_put(jnp.asarray(a), rep)     # noqa: E731
    fa = CFTDeviceState._forest_arrays(forest)
    return ShardedBankState(
        fingerprints=put_b(fps), temperature=put_b(temp),
        heads=put_b(heads),
        tree_shard=put_r(sbank.tree_shard_map()),
        tree_offset=put_r(sbank.tree_arena_offsets().astype(np.int32)),
        tree_nb=put_r(sbank.tree_nb_map()),
        csr_offsets=put_r(csr_off),
        csr_nodes=put_r(csr_nodes if csr_nodes.size
                        else np.zeros(1, np.int32)),
        parent=put_r(fa["parent"]), entity_id=put_r(fa["entity_id"]),
        child_offsets=put_r(fa["child_offsets"]),
        child_index=put_r(fa["child_index"]),
        mesh=mesh, axis=axis)


def shard_bank(bank: FilterBank, forest: EntityForest, mesh: Mesh,
               axis: str = "model",
               tree_starts: Optional[np.ndarray] = None
               ) -> Tuple[ShardedBank, ShardedBankState]:
    """Partition + stage in one step; returns (host sbank, device state)."""
    sbank = bank.shard(num_shards=int(mesh.shape[axis]),
                       tree_starts=tree_starts)
    return sbank, stage_sharded_bank(sbank, forest, mesh, axis)


def plan_tenant_partition(weights: np.ndarray, registry,
                          num_shards: int) -> np.ndarray:
    """Shard ``tree_starts`` balanced by per-tree weight but snapped to
    the registry's tenant boundaries, so no tenant straddles two shards.

    A straddling tenant would make its eviction/reload a cross-shard
    transaction and its fault attribution ambiguous; with aligned
    boundaries every tenant lifecycle op stays a per-shard segment
    splice.  Needs at least ``num_shards`` boundary-delimited segments
    (tenant ranges plus any unowned gaps)."""
    from .bank import plan_partition
    w = np.asarray(weights, np.float64).ravel()
    cuts = {0, w.size}
    for name in registry.names:
        lo, hi = registry.trees(name)
        cuts.update((int(lo), int(hi)))
    bounds = np.asarray(sorted(cuts), np.int64)
    if bounds[0] < 0 or bounds[-1] > w.size:
        raise ValueError("tenant ranges exceed the tree count")
    seg_w = np.add.reduceat(np.maximum(w, 1e-9), bounds[:-1])
    seg_starts = plan_partition(seg_w, num_shards)
    return bounds[seg_starts.astype(np.int64)].astype(np.int32)


# ----------------------------------------------- incremental arena update
#
# The donated-buffer commit ops of the double-buffered restage
# (``repro.core.maintenance.commit_restage``): a maintenance cycle writes
# its delta straight into the live packed arena — only the owning shard's
# rows are touched, every non-owner block comes out byte-identical, and
# the whole update moves O(changed rows) host→device bytes instead of a
# shard repack.  Donation keeps the scatter in-place where the backend
# supports it; the pre-commit arrays are invalid either way.

@functools.partial(jax.jit, static_argnames=("mesh", "axis"),
                   donate_argnums=(0, 1, 2))
def sharded_apply_delta(fps: jax.Array, temp: jax.Array, heads: jax.Array,
                        rows: jax.Array, vf: jax.Array, vt: jax.Array,
                        vh: jax.Array, vkeep: jax.Array, shift: jax.Array,
                        mesh: Mesh, axis: str):
    """Per-shard in-place row scatter + merged-head-numbering shift.

    ``rows``/``v*`` are stacked per-shard payloads ``(D, Kpad[, S])`` in
    *local block* coordinates (sentinel rows land out of bounds and are
    dropped — a shard with no changes gets an all-sentinel lane);
    ``shift`` is the per-shard merged CSR row-id delta (an insert into
    shard d renumbers every later shard's merged rows — applied here as
    an elementwise add over occupied slots, zero host→device bytes).

    Like :func:`repro.core.bank.splice_arena_rows`, temperature
    max-merges on slots whose key the plan leaves in place — ``vkeep``
    is the plan-time ``staged fp == shadow fp`` mask (see there for why
    the donated fps must not be read for the guard) — so bumps that
    landed on device between plan and commit survive.
    """
    def local(f, t, h, r, lf, lt, lh, lk, s):
        h = jnp.where(h != NULL, h + s[0], h)
        r0 = r[0]
        live_t = jnp.where(lk[0], t[r0], 0)
        return (f.at[r0].set(lf[0], mode="drop"),
                t.at[r0].set(jnp.maximum(lt[0], live_t), mode="drop"),
                h.at[r0].set(lh[0], mode="drop"))

    blk = P(axis, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(blk, blk, blk, blk, P(axis, None, None),
                                 P(axis, None, None), P(axis, None, None),
                                 P(axis, None, None), P(axis)),
                       out_specs=(blk, blk, blk), check_vma=False)
    return fn(fps, temp, heads, rows, vf, vt, vh, vkeep, shift)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"),
                   donate_argnums=(0, 1, 2))
def sharded_splice_segment(fps: jax.Array, temp: jax.Array,
                           heads: jax.Array, seg_f: jax.Array,
                           seg_t: jax.Array, seg_h: jax.Array,
                           owner: jax.Array, start: jax.Array,
                           mesh: Mesh, axis: str):
    """Owner-local segment splice via ``dynamic_update_slice`` inside
    ``shard_map``: the staged segment (the resized tree plus the shifted
    later trees of the same shard, padded with empty rows when a shrink
    leaves a stale tail) lands at ``start`` of the owning shard's packed
    block; every other shard returns its block untouched.  ``owner`` and
    ``start`` are traced scalars, so repeated splices at different
    positions reuse one compilation per segment length."""
    def local(f, t, h, sf, st, sh, ow, st0):
        me = jax.lax.axis_index(axis)

        def splice(_):
            dus = lambda a, s: jax.lax.dynamic_update_slice(  # noqa: E731
                a, s, (st0, jnp.int32(0)))
            return dus(f, sf), dus(t, st), dus(h, sh)

        return jax.lax.cond(me == ow, splice, lambda _: (f, t, h), None)

    blk = P(axis, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(blk, blk, blk, P(), P(), P(), P(), P()),
                       out_specs=(blk, blk, blk), check_vma=False)
    return fn(fps, temp, heads, seg_f, seg_t, seg_h, owner, start)


# ------------------------------------------------------- bank-axis lookup

def _bank_local_fn(axis: str, num_shards: int, num_trees: int, slots: int,
                   bump: bool, lookup_fn, capacity: int):
    """Build the shard-local body: route -> probe own arena -> route back.

    ``lookup_fn(fps, heads, row_offsets, masks, h)`` is the arena-probe
    contract (pure-jnp :func:`repro.core.lookup.lookup_arena` by default,
    or the Pallas ``cuckoo_lookup_arena_auto``): queries arrive on their
    owning shard already carrying their segment start and bucket mask, so
    heterogeneous per-tree bucket counts need no special casing.
    """
    probe = lookup_arena if lookup_fn is None else lookup_fn

    def local(fps_b, temp_b, heads_b, tree_shard, tree_off, tree_nb,
              tid, h):
        # ---- destination + local coordinates (replicated routing tables)
        tq = jnp.clip(tid, 0, num_trees - 1)
        valid = (tid >= 0) & (tid < num_trees)
        dest = jnp.where(valid, tree_shard[tq], 0).astype(jnp.int32)
        aoff = jnp.where(valid, tree_off[tq], 0).astype(jnp.int32)
        msk = jnp.where(valid, (tree_nb[tq] - 1).astype(jnp.uint32),
                        jnp.uint32(0))
        rank, (bh, bo, bm, bv) = _bucket_queries(
            dest, num_shards, capacity,
            ((h.astype(jnp.uint32), jnp.uint32(0)),
             (aoff, jnp.int32(0)), (msk, jnp.uint32(0)), (valid, False)))
        # ---- one exchange: every query lands on its owning shard
        qh = _exchange(bh, axis).reshape(-1)
        qo = _exchange(bo, axis).reshape(-1)
        qm = _exchange(bm, axis).reshape(-1)
        qv = _exchange(bv, axis).reshape(-1)
        # ---- shard-local probe of the owned (Apad, S) arena block
        res = probe(fps_b, heads_b, qo, qm, qh)
        hit = res.hit & qv
        head = jnp.where(hit, res.head, jnp.int32(NULL))
        if bump:   # owner-local: each tree's temperature has exactly 1 home
            temp_b = temp_b.at[qo + res.bucket, res.slot].add(
                hit.astype(temp_b.dtype))
        # ---- inverse exchange: results home to their source shard
        back = functools.partial(_route_back, dest=dest, rank=rank,
                                 axis=axis, num_shards=num_shards)
        return LookupResult(hit=back(hit), head=back(head),
                            bucket=back(res.bucket),
                            slot=back(res.slot)), temp_b

    return local


def _lookup_core(state: ShardedBankState, tree_ids: jax.Array,
                 h: jax.Array, bump: bool, lookup_fn,
                 capacity: Optional[int]
                 ) -> Tuple[LookupResult, jax.Array]:
    mesh, axis = state.mesh, state.axis
    d = state.num_shards
    b = h.shape[0]
    pad = (-b) % d
    bl = (b + pad) // d
    cap = bl if capacity is None else min(capacity, bl)
    tid = jnp.pad(tree_ids.astype(jnp.int32), (0, pad),
                  constant_values=NULL)            # pad queries always miss
    hp = jnp.pad(h.astype(jnp.uint32), (0, pad))
    local = _bank_local_fn(axis, d, state.num_trees, state.slots, bump,
                           lookup_fn, cap)
    spec_b = P(axis, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_b, spec_b, spec_b, P(), P(), P(), P(axis), P(axis)),
        out_specs=(LookupResult(hit=P(axis), head=P(axis), bucket=P(axis),
                                slot=P(axis)), spec_b),
        # pallas_call has no replication rule; rep-check only costs us the
        # kernel probe path, so switch it off just there
        check_vma=lookup_fn is None)
    res, temp = fn(state.fingerprints, state.temperature, state.heads,
                   state.tree_shard, state.tree_offset, state.tree_nb,
                   tid, hp)
    return LookupResult(hit=res.hit[:b], head=res.head[:b],
                        bucket=res.bucket[:b], slot=res.slot[:b]), temp


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "num_shards",
                                             "num_trees"))
def _routing_counts_jit(tree_shard: jax.Array, tid: jax.Array, mesh: Mesh,
                        axis: str, num_shards: int, num_trees: int):
    """First pass of the two-pass capacity protocol: each shard counts
    its outgoing queries per destination and one tiny ``all_to_all``
    exchanges the per-pair counts — O(D²) ints instead of the payload."""
    pad = (-tid.shape[0]) % num_shards
    tid = jnp.pad(tid.astype(jnp.int32), (0, pad), constant_values=NULL)

    def local(ts, tl):
        tq = jnp.clip(tl, 0, num_trees - 1)
        valid = (tl >= 0) & (tl < num_trees)
        # invalid/pad queries route to shard 0 and occupy buffer slots,
        # exactly as in the payload exchange — count them too
        dest = jnp.where(valid, ts[tq], 0).astype(jnp.int32)
        counts = jnp.zeros((num_shards,), jnp.int32).at[dest].add(1)
        recv = _exchange(counts.reshape(num_shards, 1), axis)
        return recv.reshape(1, num_shards)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                       out_specs=P(axis, None), check_vma=False)
    return fn(tree_shard, tid)


def routing_counts(state: ShardedBankState, tree_ids) -> np.ndarray:
    """(D, D) routed-query counts of this batch — entry ``[dst, src]`` is
    how many of source shard ``src``'s local queries (pad slots included)
    land on shard ``dst``.  Padding and counting both run device-side;
    the only host transfer is the O(D²) count readback that sizes the
    payload buffer."""
    tid = jnp.asarray(tree_ids).reshape(-1)
    counts = np.asarray(_routing_counts_jit(
        state.tree_shard, tid, state.mesh, state.axis, state.num_shards,
        state.num_trees))
    reg = get_registry()
    if reg.enabled:
        reg.counter("dist.count_exchanges",
                    "all-to-all routing-count passes").inc()
        reg.counter("dist.routed_queries",
                    "queries routed through the all-to-all "
                    "(pad slots included)").inc(int(counts.sum()))
        reg.gauge("dist.routing_max",
                  "worst per-(dst,src) routed count of the last batch"
                  ).set(int(counts.max()))
    return counts


def _pick_capacity(state: ShardedBankState, tree_ids,
                   capacity_factor: Optional[float]) -> Optional[int]:
    """Two-pass adaptive receive capacity for the routed all-to-all.

    ``None`` keeps the worst-case buffer (C = Bl: every local query to
    one shard — no count pass, can never overflow).  With a factor ``f``,
    the count exchange measures the batch's actual per-pair maximum:
    when it fits ``ceil(f·Bl)`` the factor-derived capacity is used (the
    fast path — a batch-independent static shape, so steady traffic
    never recompiles); when it would overflow, the buffer grows to the
    measured maximum instead (rounded up to a power of two to bound
    recompiles), replacing the old eager host-side pre-check that raised.
    """
    adapt = get_registry().counter(
        "dist.capacity", "all-to-all receive-capacity picks by path")
    if capacity_factor is None:
        adapt.inc(path="worst_case")
        return None
    d = state.num_shards
    b = int(jnp.asarray(tree_ids).size)    # shape metadata, no transfer
    bl = -(-b // d)
    fast = min(bl, max(1, int(np.ceil(bl * float(capacity_factor)))))
    worst = int(routing_counts(state, tree_ids).max())
    if worst <= fast:
        adapt.inc(path="fast")
        return fast
    adapt.inc(path="adapted")
    return min(bl, 1 << int(np.ceil(np.log2(max(1, worst)))))


@functools.partial(jax.jit, static_argnames=("lookup_fn", "capacity"))
def _sharded_lookup_jit(state: ShardedBankState, tree_ids: jax.Array,
                        h: jax.Array, lookup_fn=None,
                        capacity: Optional[int] = None) -> LookupResult:
    res, _ = _lookup_core(state, tree_ids, h, bump=False,
                          lookup_fn=lookup_fn, capacity=capacity)
    return res


def sharded_lookup_bank(state: ShardedBankState, tree_ids: jax.Array,
                        h: jax.Array, lookup_fn=None,
                        capacity_factor: Optional[float] = None
                        ) -> LookupResult:
    """All-to-all routed bank lookup; bit-identical to
    ``lookup_arena`` over the merged replicated arena, with each query
    routed to its tree's segment as ``retrieve_device`` routes it.

    ``lookup_fn(fps, heads, row_offsets, masks, h)`` swaps in a different
    shard-local arena probe (e.g. the row-tiled Pallas kernel
    ``repro.kernels.cuckoo_lookup.cuckoo_lookup_arena_auto``) — usable
    regardless of heterogeneous per-tree bucket counts, since routing
    arrives per query.  ``capacity_factor`` shrinks the all-to-all
    receive buffer below the worst case via the two-pass count exchange
    (see :func:`_pick_capacity`: the factor is the fast path when the
    batch's measured per-pair counts fit, and the buffer adapts to the
    actual maximum when they don't — no overflow, no eager host
    pre-check).  Pure: temperature is not bumped (use
    :func:`sharded_retrieve_device` for serving).
    """
    capacity = _pick_capacity(state, tree_ids, capacity_factor)
    return _sharded_lookup_jit(state, tree_ids, h, lookup_fn=lookup_fn,
                               capacity=capacity)


@functools.partial(jax.jit,
                   static_argnames=("max_locs", "n", "lookup_fn",
                                    "capacity"))
def _sharded_retrieve_jit(state: ShardedBankState,
                          query_hashes: jax.Array,
                          query_trees: jax.Array,
                          max_locs: int = 4, n: int = 3,
                          lookup_fn=None,
                          capacity: Optional[int] = None
                          ) -> DeviceRetrieval:
    res, temp = _lookup_core(state, query_trees, query_hashes, bump=True,
                             lookup_fn=lookup_fn, capacity=capacity)
    return gather_context(state, res, temp, max_locs=max_locs, n=n)


def sharded_retrieve_device(state: ShardedBankState,
                            query_hashes: jax.Array,
                            query_trees: Optional[jax.Array] = None,
                            max_locs: int = 4, n: int = 3,
                            lookup_fn=None,
                            capacity_factor: Optional[float] = None
                            ) -> DeviceRetrieval:
    """Bank-axis sharded analogue of ``repro.core.retrieve_device``.

    The lookup routes through the all-to-all; temperature bumps land in
    the owning shard's packed arena during the probe (so the returned
    ``temperature`` keeps the sharded layout — thread it forward with
    ``state.with_temperature``); the CSR location gather and hierarchy
    windows run on the replicated arrays exactly as the replicated path.
    """
    if query_trees is None:
        query_trees = jnp.zeros(query_hashes.shape, jnp.int32)
    capacity = _pick_capacity(state, query_trees, capacity_factor)
    return _sharded_retrieve_jit(state, query_hashes, query_trees,
                                 max_locs=max_locs, n=n,
                                 lookup_fn=lookup_fn, capacity=capacity)


# ------------------------------------------- legacy single-filter wrappers

def _filter_local_fn(axis: str, num_shards: int, nb_global: int,
                     nb_local: int, slots: int):
    """Shard-local body for the bucket-striped single filter: each query
    fans out to its two candidate stripes through the shared router, each
    stripe scans one bucket row, and the pair merges with i1 priority."""

    def local(fps_s, heads_s, h_l):
        bl = h_l.shape[0]
        fp, i1, i2 = hashing.candidate_buckets(h_l.astype(jnp.uint32),
                                               nb_global, jnp)
        # 2 routed probes per query: [all i1 probes ; all i2 probes]
        cand = jnp.concatenate([i1, i2]).astype(jnp.int32)
        dest = cand // nb_local                    # stripe == owning shard
        lb = cand % nb_local
        fp2 = jnp.tile(fp, 2)
        rank, (bb, bf) = _bucket_queries(
            dest, num_shards, 2 * bl,
            ((lb, jnp.int32(0)), (fp2, jnp.uint32(0))))
        qb = _exchange(bb, axis).reshape(-1)
        qf = _exchange(bf, axis).reshape(-1)
        rows = fps_s[qb]                           # (D*C, S)
        m = rows == qf[:, None]
        hit = jnp.any(m, axis=1)
        slot = jnp.argmax(m, axis=1).astype(jnp.int32)
        head = jnp.take_along_axis(heads_s[qb], slot[:, None],
                                   axis=1)[:, 0]
        back = functools.partial(_route_back, dest=dest, rank=rank,
                                 axis=axis, num_shards=num_shards)
        hit, head, slot = back(hit), back(head), back(slot)
        h1, h2 = hit[:bl], hit[bl:]
        # i1 priority — identical tie-breaking to match_rows' 2S concat
        return LookupResult(
            hit=h1 | h2,
            head=jnp.where(h1, head[:bl],
                           jnp.where(h2, head[bl:], jnp.int32(NULL))),
            bucket=jnp.where(h1 | ~h2, i1, i2).astype(jnp.int32),
            slot=jnp.where(h1, slot[:bl],
                           jnp.where(h2, slot[bl:], jnp.int32(0))))

    return local


def sharded_lookup(mesh: Mesh, axis: str, fingerprints: jax.Array,
                   heads: jax.Array, h: jax.Array) -> LookupResult:
    """Single-filter lookup with tables bucket-sharded over ``axis``.

    Thin wrapper over the bank-axis router: the D bucket stripes act as a
    degenerate D-tree bank (one "tree" per shard), each query routes to its
    two candidate stripes, and no replica combine exists.  Bit-identical to
    ``lookup_arena`` over the unsharded filter as an arena of one segment
    (zero offsets, masks ``nb - 1``).
    """
    nb_global, slots = fingerprints.shape
    mesh = auto_axes(mesh)
    d = int(mesh.shape[axis])
    if nb_global % d:
        raise ValueError(f"bucket count {nb_global} not divisible by "
                         f"mesh axis size {d}")
    b = h.shape[0]
    pad = (-b) % d
    hp = jnp.pad(h.astype(jnp.uint32), (0, pad))
    local = _filter_local_fn(axis, d, nb_global, nb_global // d, slots)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis)),
        out_specs=LookupResult(hit=P(axis), head=P(axis), bucket=P(axis),
                               slot=P(axis)))
    res = fn(fingerprints, heads, hp)
    return LookupResult(hit=res.hit[:b], head=res.head[:b],
                        bucket=res.bucket[:b], slot=res.slot[:b])


def shard_filter_tables(mesh: Mesh, axis: str, *tables: jax.Array
                        ) -> Tuple[jax.Array, ...]:
    """Place filter tables bucket-sharded on the mesh."""
    sharding = NamedSharding(auto_axes(mesh), P(axis, None))
    return tuple(jax.device_put(t, sharding) for t in tables)
