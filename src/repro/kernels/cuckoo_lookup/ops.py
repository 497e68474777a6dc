"""Public jit'd wrappers for the cuckoo-lookup Pallas kernel.

Handles: query padding to the TILE multiple and the kernel's lane layout,
int -> f32 table staging, the row tile and VMEM limit of a launch on the
chip, interpret-mode selection off the backend, and repackaging into
core.lookup.LookupResult.  Queries arrive pre-routed (segment start and
bucket mask per query): routing a tree id to its segment is the caller's
(``retrieve_device`` or the sharded shard-local body), and a single filter
is an arena of one segment.
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


def pick_row_tile(arena_rows: int, interpret: bool) -> int:
    """Row tile of a launch: one block in interpret mode (no VMEM to fit),
    else the attached TPU's tile budget."""
    if interpret:
        return 0
    cap = vmem.max_rows_for_vmem(vmem.device_budget(slots=4, tile=TILE),
                                 TILE)
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
