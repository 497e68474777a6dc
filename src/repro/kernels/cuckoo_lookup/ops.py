"""Public jit'd wrappers for the cuckoo-lookup Pallas kernel.

Handles: query padding to the TILE multiple and the kernel's lane layout,
int -> f32 table staging, the row tile and VMEM limit of a launch on the
chip, interpret-mode selection off the backend, and repackaging into
core.lookup.LookupResult.  Every entry probes through the one arena kernel:
a single filter is an arena of one segment, a dense ``(T, NB, S)`` bank an
arena of T equal segments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.lookup import LookupResult
from .. import vmem
from .kernel import TILE, cuckoo_lookup_arena_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def stage_tables(fingerprints: jax.Array, heads: jax.Array,
                 rows: int) -> jax.Array:
    """``(A, S)`` int tables -> the kernel's ``(2S, rows)`` f32 layout:
    fingerprint slots over head slots, zero-padded to ``rows`` arena rows
    (an empty fingerprint never matches)."""
    a = fingerprints.shape[0]
    tab = jnp.concatenate([fingerprints, heads], axis=1).astype(jnp.float32)
    return jnp.pad(tab, ((0, rows - a), (0, 0))).T


def padded_rows(arena_rows: int, row_tile: int) -> int:
    """Arena rows after padding to a whole number of grid tiles."""
    unit = row_tile if row_tile > 0 else TILE
    return -(-arena_rows // unit) * unit


def lane_queries(b: int, *arrs):
    """Pad ``(B,)`` query arrays to a TILE multiple, as ``(1, Bp)`` rows."""
    pad = (-b) % TILE
    return [jnp.pad(a, (0, pad))[None, :] for a in arrs]


def pick_row_tile(arena_rows: int, interpret: bool,
                  resident_bytes: int = 0) -> int:
    """Row tile of a launch: one block in interpret mode (no VMEM to fit),
    else the attached TPU's tile budget."""
    if interpret:
        return 0
    cap = vmem.max_rows_for_vmem(vmem.device_budget(slots=4, tile=TILE),
                                 TILE, resident_bytes)
    return vmem.row_tile_for(arena_rows, cap)


def launch_vmem_limit(interpret: bool) -> int:
    """Scoped VMEM limit of a launch (0 = compiler default)."""
    return 0 if interpret else vmem.device_budget(slots=4,
                                                  tile=TILE).limit_bytes


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile",
                                             "vmem_limit"))
def cuckoo_lookup_arena(fingerprints: jax.Array, heads: jax.Array,
                        row_offsets: jax.Array, masks: jax.Array,
                        h: jax.Array, interpret: bool = True,
                        row_tile: int = -1,
                        vmem_limit: int = 0) -> LookupResult:
    """Ragged-arena lookup with pre-routed queries — same signature and
    semantics as ``core.lookup.lookup_arena``.  Tables: flat ``(A, S)``;
    ``row_offsets``/``masks``: per-query segment start and ``nb_t - 1``.

    ``row_tile``: -1 auto-selects (:func:`pick_row_tile`); 0 forces the
    single-block path; > 0 (a TILE multiple) forces that many arena rows
    per grid step.  ``vmem_limit`` 0 launches under the scoped limit the
    tile budget assumes (:func:`launch_vmem_limit`).  The arena is padded
    here with empty-fingerprint rows (which can never match), so callers
    never pre-pad.
    """
    a, _ = fingerprints.shape
    if row_tile < 0:
        row_tile = pick_row_tile(a, interpret)
    vmem_limit = vmem_limit or launch_vmem_limit(interpret)
    b = h.shape[0]
    hp, op, mp = lane_queries(b, h.astype(jnp.uint32),
                              row_offsets.astype(jnp.int32),
                              masks.astype(jnp.uint32))
    tab = stage_tables(fingerprints, heads, padded_rows(a, row_tile))
    hit, head, bucket, slot = cuckoo_lookup_arena_pallas(
        hp, op, mp, tab, interpret=interpret, row_tile=row_tile,
        vmem_limit=vmem_limit)
    return LookupResult(hit=hit[0, :b].astype(jnp.bool_), head=head[0, :b],
                        bucket=bucket[0, :b], slot=slot[0, :b])


def cuckoo_lookup_arena_auto(fingerprints, heads, row_offsets, masks, h
                             ) -> LookupResult:
    """Kernel on TPU, interpret elsewhere — serving's ragged-arena entry
    (the ``lookup_fn`` shape ``retrieve_device`` and the sharded probe
    consume)."""
    return cuckoo_lookup_arena(fingerprints, heads, row_offsets, masks, h,
                               interpret=not on_tpu())


def _uniform_masks(h: jax.Array, num_buckets: int) -> jax.Array:
    return jnp.full(h.shape, num_buckets - 1, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cuckoo_lookup(fingerprints: jax.Array, heads: jax.Array, h: jax.Array,
                  interpret: bool = True) -> LookupResult:
    """Same signature/semantics as core.lookup.lookup_batch: one filter
    ``(NB, S)`` is an arena of one segment."""
    nb = fingerprints.shape[0]
    return cuckoo_lookup_arena(fingerprints, heads,
                               jnp.zeros(h.shape, jnp.int32),
                               _uniform_masks(h, nb), h,
                               interpret=interpret)


def cuckoo_lookup_auto(fingerprints, heads, h) -> LookupResult:
    """Kernel on TPU, interpret elsewhere."""
    return cuckoo_lookup(fingerprints, heads, h, interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("interpret", "tree_tile"))
def cuckoo_lookup_bank(fingerprints: jax.Array, heads: jax.Array,
                       tree_ids: jax.Array, h: jax.Array,
                       interpret: bool = True,
                       tree_tile: int = -1) -> LookupResult:
    """Bank lookup with per-query tree routing — same signature/semantics
    as core.lookup.lookup_batch_bank.  Tables: (T, NB, S), probed as an
    arena of T segments of NB rows.

    ``tree_tile``: -1 auto-selects; 0 forces the single-block path; > 0
    streams about that many trees per grid step (rounded up to a TILE
    multiple of rows).  Out-of-range tree ids route outside the arena and
    miss.
    """
    t, nb, s = fingerprints.shape
    row_tile = (-1 if tree_tile < 0 else
                0 if tree_tile == 0 else padded_rows(tree_tile * nb, 0))
    return cuckoo_lookup_arena(fingerprints.reshape(t * nb, s),
                               heads.reshape(t * nb, s),
                               tree_ids.astype(jnp.int32) * nb,
                               _uniform_masks(h, nb), h,
                               interpret=interpret, row_tile=row_tile)


def cuckoo_lookup_bank_auto(fingerprints, heads, tree_ids, h
                            ) -> LookupResult:
    """Kernel on TPU, interpret elsewhere — serving's bank-routing entry."""
    return cuckoo_lookup_bank(fingerprints, heads, tree_ids, h,
                              interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile",
                                             "vmem_limit"))
def cuckoo_lookup_ragged(fingerprints: jax.Array, heads: jax.Array,
                         bucket_offsets: jax.Array, tree_nb: jax.Array,
                         tree_ids: jax.Array, h: jax.Array,
                         interpret: bool = True, row_tile: int = -1,
                         vmem_limit: int = 0) -> LookupResult:
    """Tree-routed ragged lookup — same signature/semantics as
    ``core.lookup.lookup_batch_ragged``.  Each query's (segment start,
    bucket mask) pair is gathered here from the O(T) per-tree tables and
    the pre-routed :func:`cuckoo_lookup_arena` probes; out-of-range tree
    ids are clamped (matching the jnp reference's clipped gather).
    """
    t = jnp.clip(tree_ids.astype(jnp.int32), 0, tree_nb.shape[0] - 1)
    return cuckoo_lookup_arena(fingerprints, heads, bucket_offsets[t],
                               (tree_nb[t] - 1).astype(jnp.uint32), h,
                               interpret=interpret, row_tile=row_tile,
                               vmem_limit=vmem_limit)


def cuckoo_lookup_ragged_auto(fingerprints, heads, bucket_offsets, tree_nb,
                              tree_ids, h) -> LookupResult:
    """Kernel on TPU, interpret elsewhere — tree-routed ragged entry."""
    return cuckoo_lookup_ragged(fingerprints, heads, bucket_offsets,
                                tree_nb, tree_ids, h,
                                interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("interpret",))
def cuckoo_lookup_trees(fingerprints: jax.Array, heads: jax.Array,
                        h: jax.Array, interpret: bool = True
                        ) -> LookupResult:
    """Per-tree entry: tables (T, NB, S), h (T, B) — one dense query batch
    per tree, result fields shaped (T, B)."""
    t, b = h.shape
    tid = jnp.repeat(jnp.arange(t, dtype=jnp.int32), b)
    res = cuckoo_lookup_bank(fingerprints, heads, tid, h.reshape(-1),
                             interpret=interpret)
    return LookupResult(*(x.reshape(t, b) for x in res))
