"""Public jit'd wrappers for the fused retrieval kernel.

Handles: query padding to the TILE multiple and the kernel's lane layout,
f32 staging of the arena and the packed CSR/forest context tables, arena-
row padding for tiled grids, the launch plan (interpret mode off the chip;
on a TPU, MXU gathers with the device's VMEM tile budget), and repackaging
into ``core.trag.DeviceRetrieval``.  Observability
(``serve.fused_batches``, ``kernel.tile_rows``) is emitted from the
non-traced auto entries so the counters tick per call, not per trace.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ...core.trag import DeviceRetrieval
from ...obs import get_registry
from .. import vmem
from ..cuckoo_lookup.kernel import TILE
from ..cuckoo_lookup.ops import (lane_queries, on_tpu, padded_rows,
                                 pick_row_tile, stage_tables)
from .kernel import GATHER_CHUNK, fused_retrieve_pallas

#: One-hot matmul gathers are exact in f32 only below this value bound;
#: wrappers assert every table dimension (node/CSR/arena counts) under it.
F32_EXACT_MAX = 1 << 24


def chunk_columns(tab: jax.Array) -> jax.Array:
    """``(C, R)`` table -> the kernel's ``(K, C, W)`` gather chunks: column
    ``r`` at ``[r // W, :, r % W]``, zero-padded to at least one whole
    chunk."""
    c, r = tab.shape
    w = min(GATHER_CHUNK, padded_rows(max(r, 1), 0))
    rp = -(-max(r, 1) // w) * w
    return jnp.pad(tab, ((0, 0), (0, rp - r))).reshape(
        c, rp // w, w).transpose(1, 0, 2)


def stage_context_tables(csr_offsets, csr_nodes, parent, entity_id,
                         child_offsets, child_index
                         ) -> Tuple[jax.Array, ...]:
    """Pack the CSR/forest tables into the kernel's transposed f32 gather
    layout (one column per row of the source table), each then cut into
    gather chunks by :func:`chunk_columns`:

    csr_lc      (2, R)    [row start; row count]
    csr_nodes   (1, L)
    parent_eid  (2, N)    [parent node; entity id]
    child_lc    (2, N)    [children start; child count]
    child_index (1, C)
    """
    lo = csr_offsets[:-1]
    csr_lc = jnp.stack([lo, csr_offsets[1:] - lo]).astype(jnp.float32)
    nodes2 = csr_nodes.astype(jnp.float32)[None, :]
    parent_eid = jnp.stack([parent, entity_id]).astype(jnp.float32)
    child_lc = jnp.stack(
        [child_offsets[:-1], child_offsets[1:] - child_offsets[:-1]]
    ).astype(jnp.float32)
    cidx2 = child_index.astype(jnp.float32)[None, :]
    return tuple(chunk_columns(t) for t in
                 (csr_lc, nodes2, parent_eid, child_lc, cidx2))


def _check_f32_exact(*dims: int) -> None:
    for d in dims:
        if d >= F32_EXACT_MAX:
            raise ValueError(
                f"table dimension {d} >= 2^24 breaks f32-exact one-hot "
                "gathers; shard the bank (core.distributed) first")


def _block_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a 32-bit ``(rows, cols)`` block ((8, 128)-tiled; a
    chunked context table costs what its unchunked ``(C, R)`` form does)."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def context_resident_bytes(arena_rows: int, slots: int, num_csr_rows: int,
                           num_csr_nodes: int, num_nodes: int,
                           num_children: int, mxu: bool) -> int:
    """VMEM pinned for the whole launch: the temperature in and out blocks
    and the packed context tables, each double-buffered by the pipeline,
    plus under ``mxu`` the ``(A, TILE)`` bump one-hot and one
    ``(GATHER_CHUNK, TILE)`` context-gather one-hot."""
    blocks = (2 * _block_bytes(slots, arena_rows)
              + _block_bytes(2, num_csr_rows)
              + _block_bytes(1, max(num_csr_nodes, 1))
              + 2 * _block_bytes(2, num_nodes)
              + _block_bytes(1, max(num_children, 1)))
    resident = 2 * blocks
    if mxu:
        resident += TILE * 4 * (arena_rows + GATHER_CHUNK)
    return resident


@functools.lru_cache(maxsize=256)
def launch_plan(arena_rows: int, slots: int, num_csr_rows: int,
                num_csr_nodes: int, num_nodes: int, num_children: int
                ) -> Tuple[bool, bool, int, int]:
    """Per-geometry launch plan ``(interpret, mxu, row_tile, vmem_limit)``.

    Off the chip: interpret mode with direct gathers, one block.  On a
    TPU: MXU gathers, the row tile that fits the device's VMEM budget
    after the resident blocks, and the scoped limit that budget assumes.
    Raises when the resident blocks alone overflow the budget — the caller
    asked for the fused path, and serving another in its place would hide
    that.  Cached so the hot serving path pays this once per geometry."""
    if not on_tpu():
        return True, False, 0, 0
    resident = context_resident_bytes(arena_rows, slots, num_csr_rows,
                                      num_csr_nodes, num_nodes,
                                      num_children, mxu=True)
    budget = vmem.device_budget(slots=slots, tile=TILE)
    if resident + TILE * budget.per_row_bytes > budget.budget_bytes:
        raise ValueError(
            f"fused retrieval needs {resident} resident VMEM bytes, over "
            f"the {budget.budget_bytes}-byte tile budget of this device; "
            "serve this bank unfused")
    return (False, True, pick_row_tile(arena_rows, False, resident),
            budget.limit_bytes)


def _lanes_to_rows(x, b):
    """Kernel ``(max_locs, W, Bp)`` output -> ``(b, max_locs, W)``."""
    return x[:, :, :b].transpose(2, 0, 1)


def _repack(outs, b, a) -> DeviceRetrieval:
    hit, _head, _bucket, _slot, _prio, loc, up, down, temp = outs
    return DeviceRetrieval(
        hit=hit[0, :b].astype(jnp.bool_),
        locations=_lanes_to_rows(loc, b)[:, :, 0],
        up=_lanes_to_rows(up, b), down=_lanes_to_rows(down, b),
        temperature=temp[:, :a].T)


def _stage_arena(fingerprints, temperature, heads, row_tile):
    rows = padded_rows(fingerprints.shape[0], row_tile)
    tab = stage_tables(fingerprints, heads, rows)
    temp = jnp.pad(temperature,
                   ((0, rows - temperature.shape[0]), (0, 0))).T
    return tab, temp


@functools.partial(jax.jit, static_argnames=("max_locs", "n", "interpret",
                                             "row_tile", "mxu",
                                             "vmem_limit"))
def fused_retrieve_arena(fingerprints, temperature, heads, row_offsets,
                         masks, valid, h, csr_offsets, csr_nodes, parent,
                         entity_id, child_offsets, child_index,
                         max_locs: int = 4, n: int = 3,
                         interpret: bool = True, row_tile: int = 0,
                         mxu: bool = False,
                         vmem_limit: int = 0) -> DeviceRetrieval:
    """Pre-routed fused retrieval: per-query (segment start, bucket mask)
    pairs as in ``core.lookup.lookup_arena``, plus a ``valid`` admission
    mask (the unfused path's ``in_range``).  Returns a full
    ``DeviceRetrieval`` from one kernel launch."""
    a, _ = fingerprints.shape
    _check_f32_exact(a, csr_offsets.shape[0], csr_nodes.shape[0],
                     parent.shape[0], child_index.shape[0])
    b = h.shape[0]
    hp, op, mp, vp = lane_queries(
        b, h.astype(jnp.uint32), row_offsets.astype(jnp.int32),
        masks.astype(jnp.uint32), valid.astype(jnp.int32))
    tab, temp = _stage_arena(fingerprints, temperature, heads, row_tile)
    ctx = stage_context_tables(csr_offsets, csr_nodes, parent, entity_id,
                               child_offsets, child_index)
    outs = fused_retrieve_pallas(
        hp, op, mp, vp, tab, temp, *ctx, max_locs=max_locs, n=n,
        interpret=interpret, row_tile=row_tile, mxu=mxu,
        vmem_limit=vmem_limit)
    return _repack(outs, b, a)


@functools.partial(jax.jit, static_argnames=("max_locs", "n", "interpret",
                                             "row_tile", "mxu",
                                             "vmem_limit"))
def fused_retrieve_ragged(fingerprints, temperature, heads, bucket_offsets,
                          tree_nb, tree_ids, h, csr_offsets, csr_nodes,
                          parent, entity_id, child_offsets, child_index,
                          max_locs: int = 4, n: int = 3,
                          interpret: bool = True, row_tile: int = 0,
                          mxu: bool = False,
                          vmem_limit: int = 0) -> DeviceRetrieval:
    """Tree-routed fused retrieval — the ``retrieve_device(fused=True)``
    entry.  Each query's (segment start, bucket mask) pair is gathered
    here from the O(T) per-tree tables; out-of-range tree ids miss
    (clamped for the gather, masked via ``valid``), exactly as the
    unfused path's ``in_range`` handling."""
    in_range = (tree_ids >= 0) & (tree_ids < tree_nb.shape[0])
    tp = jnp.where(in_range, tree_ids, 0).astype(jnp.int32)
    return fused_retrieve_arena(
        fingerprints, temperature, heads, bucket_offsets[tp],
        (tree_nb[tp] - 1).astype(jnp.uint32), in_range, h, csr_offsets,
        csr_nodes, parent, entity_id, child_offsets, child_index,
        max_locs=max_locs, n=n, interpret=interpret, row_tile=row_tile,
        mxu=mxu, vmem_limit=vmem_limit)


@functools.partial(jax.jit, static_argnames=("max_locs", "interpret",
                                             "row_tile", "mxu",
                                             "vmem_limit"))
def fused_probe_locs(fingerprints, temperature, heads, row_offsets, masks,
                     valid, h, csr_offsets, csr_nodes, max_locs: int = 4,
                     interpret: bool = True, row_tile: int = 0,
                     mxu: bool = False, vmem_limit: int = 0):
    """Owner-shard fusion: probe + temperature bump + CSR location window
    in one launch, no hierarchy tail (the forest walk runs on the source
    shard after the route-back all-to-all).  Returns ``(hit (B,) bool,
    locations (B, max_locs) int32, temperature (A, S))``."""
    a, _ = fingerprints.shape
    _check_f32_exact(a, csr_offsets.shape[0], csr_nodes.shape[0])
    b = h.shape[0]
    hp, op, mp, vp = lane_queries(
        b, h.astype(jnp.uint32), row_offsets.astype(jnp.int32),
        masks.astype(jnp.uint32), valid.astype(jnp.int32))
    tab, temp = _stage_arena(fingerprints, temperature, heads, row_tile)
    dummy = jnp.zeros((1,), jnp.int32)
    ctx = stage_context_tables(csr_offsets, csr_nodes, dummy, dummy,
                               jnp.zeros((2,), jnp.int32), dummy)
    hit, _head, _bucket, _slot, _prio, loc, tout = fused_retrieve_pallas(
        hp, op, mp, vp, tab, temp, *ctx, max_locs=max_locs, n=1,
        interpret=interpret, row_tile=row_tile, mxu=mxu, locs_only=True,
        vmem_limit=vmem_limit)
    return (hit[0, :b].astype(jnp.bool_), _lanes_to_rows(loc, b)[:, :, 0],
            tout[:, :a].T)


def _emit_obs(row_tile: int) -> None:
    reg = get_registry()
    reg.counter("serve.fused_batches",
                "batches served by the fused retrieval kernel").inc()
    reg.gauge("kernel.tile_rows",
              "arena rows per fused-kernel grid step (0 = single block)"
              ).set(row_tile)


def fused_retrieve_state_auto(state, query_hashes, query_trees=None,
                              max_locs: int = 4, n: int = 3
                              ) -> DeviceRetrieval:
    """Backend-aware fused entry over a ``CFTDeviceState``: kernel with
    MXU one-hot gathers on TPU, interpret + direct gathers elsewhere.
    Raises (see :func:`launch_plan`) when the fused resident working set
    cannot fit the device's VMEM budget."""
    if query_trees is None:
        query_trees = jnp.zeros(query_hashes.shape, jnp.int32)
    a, s = state.fingerprints.shape
    interpret, mxu, rt, limit = launch_plan(
        a, s, state.csr_offsets.shape[0] - 1, state.csr_nodes.shape[0],
        state.parent.shape[0], state.child_index.shape[0])
    _emit_obs(rt)
    return fused_retrieve_ragged(
        state.fingerprints, state.temperature, state.heads,
        state.bucket_offsets, state.tree_nb, query_trees, query_hashes,
        state.csr_offsets, state.csr_nodes, state.parent, state.entity_id,
        state.child_offsets, state.child_index, max_locs=max_locs, n=n,
        interpret=interpret, row_tile=rt, mxu=mxu, vmem_limit=limit)


def fused_retrieve_arena_auto(fingerprints, temperature, heads,
                              row_offsets, masks, valid, h, csr_offsets,
                              csr_nodes, parent, entity_id, child_offsets,
                              child_index, max_locs: int = 4, n: int = 3
                              ) -> DeviceRetrieval:
    """Backend-aware pre-routed fused entry (tests / direct callers)."""
    a, s = fingerprints.shape
    interpret, mxu, rt, limit = launch_plan(
        a, s, csr_offsets.shape[0] - 1, csr_nodes.shape[0],
        parent.shape[0], child_index.shape[0])
    _emit_obs(rt)
    return fused_retrieve_arena(
        fingerprints, temperature, heads, row_offsets, masks, valid, h,
        csr_offsets, csr_nodes, parent, entity_id, child_offsets,
        child_index, max_locs=max_locs, n=n, interpret=interpret,
        row_tile=rt, mxu=mxu, vmem_limit=limit)
