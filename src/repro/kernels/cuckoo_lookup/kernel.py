"""Pallas TPU kernel: batched cuckoo-filter probe over the ragged bucket
arena (the paper's hot loop).

TPU layout: queries ride the 128 vector lanes.  Every per-query value is a
``(1, TILE)`` row, and the arena is staged transposed as one f32 table
``(2S, A)`` — fingerprint slots stacked over head slots — so a tile of
``row_tile`` arena rows is a ``(2S, row_tile)`` block.  Bucket rows are
gathered with one-hot matmuls on the MXU (exact in f32 for 12-bit
fingerprints and < 2^24 payloads), replacing the CPU implementation's
pointer dereference per probe:

  1. integer hash pipeline (VPU):  fp, i1, i2 = candidates(h, mask)
  2. rows1 = tab @ one_hot(off + i1)       (2S, rt) @ (rt, TILE) on the MXU
     rows2 = tab @ one_hot(off + i2)
  3. first match over the [i1 slots | i2 slots] order via sublane iota-min;
     outputs hit/head/bucket/slot — the semantics of
     ``repro.core.lookup.lookup_arena``.

Every probe entry (single filter, dense bank, ragged arena, fused
retrieval) routes through :func:`_arena_probe`: a single filter is an
arena of one segment, a dense bank an arena of equal segments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import hashing

TILE = 128          # queries per grid step (one vector lane row)
_HIGHEST = jax.lax.Precision.HIGHEST


def compiler_params(vmem_limit: int):
    """Mosaic parameters for a launch: ``vmem_limit`` > 0 raises the
    kernel's scoped VMEM limit to what the tile budget was derived for
    (0 keeps the compiler default)."""
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit or None)


def _fp_f32(fp):
    """Query fingerprints as the f32 the tables are staged in.  Mosaic has
    no uint32 -> f32 cast; fingerprints are FP_BITS wide, so the hop
    through int32 is exact."""
    return fp.astype(jnp.int32).astype(jnp.float32)


def _arena_probe(h, qoff, qmask, ti, tab_ref, hit_ref, head_ref,
                 bucket_ref, slot_ref, prio_ref, *, slots: int,
                 row_tile: int):
    """Probe body shared by the arena and fused kernels.

    ``h``/``qoff``/``qmask``: (1, TILE) hash, segment start and bucket
    mask ``nb_t - 1``.  Grid axis 1 walks tiles of ``row_tile`` arena
    rows.  A query's two candidate rows may fall in different tiles
    (segments are not tile-aligned), so each tile contributes its local
    first match and ``prio_ref`` — the running position in the [i1 slots
    | i2 slots] order — keeps the global first match.  Step 0 writes the
    miss defaults (head -1, bucket i2, slot S-1); since every candidate
    row lives in exactly one tile, the min-priority merge reproduces the
    single-block match order exactly."""
    fp, i1u, i2u = hashing.candidate_buckets_masked(h, qmask, jnp)
    i1 = i1u.astype(jnp.int32)
    i2 = i2u.astype(jnp.int32)

    @pl.when(ti == 0)
    def _init():
        hit_ref[...] = jnp.zeros((1, TILE), jnp.int32)
        head_ref[...] = jnp.full((1, TILE), -1, jnp.int32)
        bucket_ref[...] = i2
        slot_ref[...] = jnp.full((1, TILE), slots - 1, jnp.int32)
        prio_ref[...] = jnp.full((1, TILE), 2 * slots, jnp.int32)

    base = ti * row_tile
    l1 = qoff + i1 - base
    l2 = qoff + i2 - base
    in1 = (l1 >= 0) & (l1 < row_tile)
    in2 = (l2 >= 0) & (l2 < row_tile)

    tab = tab_ref[...]                                  # (2S, row_tile) f32
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (row_tile, TILE), 0)
    # out-of-tile candidates produce all-zero one-hots -> zero rows -> no
    # match (query fingerprints are never the empty sentinel 0)
    oh1 = ((row_iota == l1) & in1).astype(jnp.float32)
    oh2 = ((row_iota == l2) & in2).astype(jnp.float32)
    rows1 = jax.lax.dot(tab, oh1, precision=_HIGHEST)   # (2S, TILE)
    rows2 = jax.lax.dot(tab, oh2, precision=_HIGHEST)

    fpq = _fp_f32(fp)
    pos = jax.lax.broadcasted_iota(jnp.int32, (slots, TILE), 0)
    none = 2 * slots
    first = jnp.minimum(
        jnp.min(jnp.where(rows1[:slots] == fpq, pos, none), axis=0,
                keepdims=True),
        jnp.min(jnp.where(rows2[:slots] == fpq, pos + slots, none), axis=0,
                keepdims=True))                         # (1, TILE)
    better = first < prio_ref[...]
    # exact gather of the winning slot's head (one nonzero term)
    head = (jnp.sum(jnp.where(pos == first, rows1[slots:], 0.0), axis=0,
                    keepdims=True) +
            jnp.sum(jnp.where(pos + slots == first, rows2[slots:], 0.0),
                    axis=0, keepdims=True))

    hit_ref[...] = jnp.where(better, 1, hit_ref[...])
    head_ref[...] = jnp.where(better, head.astype(jnp.int32), head_ref[...])
    bucket_ref[...] = jnp.where(better, jnp.where(first < slots, i1, i2),
                                bucket_ref[...])
    slot_ref[...] = jnp.where(better,
                              jnp.where(first < slots, first, first - slots),
                              slot_ref[...])
    prio_ref[...] = jnp.where(better, first, prio_ref[...])


def _arena_kernel(h_ref, off_ref, mask_ref, tab_ref, hit_ref, head_ref,
                  bucket_ref, slot_ref, prio_ref, *, slots: int,
                  row_tile: int):
    _arena_probe(h_ref[...], off_ref[...], mask_ref[...], pl.program_id(1),
                 tab_ref, hit_ref, head_ref, bucket_ref, slot_ref, prio_ref,
                 slots=slots, row_tile=row_tile)


def cuckoo_lookup_arena_pallas(h: jax.Array, row_offsets: jax.Array,
                               masks: jax.Array, table_f32: jax.Array,
                               interpret: bool = True, row_tile: int = 0,
                               vmem_limit: int = 0):
    """Pre-routed arena probe.

    h (uint32) / row_offsets (int32) / masks (uint32): ``(1, B)`` with
    ``B % TILE == 0``; ``table_f32``: ``(2S, A)`` from ``ops.stage_tables``
    with ``A`` a multiple of TILE.  ``row_tile == 0`` keeps the whole
    arena as one VMEM block; ``row_tile > 0`` (a TILE multiple dividing
    A) streams arena tiles over a second grid dimension.  Returns (hit,
    head, bucket, slot), each ``(1, B)`` int32.
    """
    two_s, rows_total = table_f32.shape
    b = h.shape[1]
    rt = rows_total if row_tile <= 0 else row_tile
    assert rows_total % rt == 0 and rt % TILE == 0, \
        "pad the arena to a multiple of row_tile (and TILE) before calling"
    grid = (b // TILE, rows_total // rt)       # arena axis innermost
    qspec = pl.BlockSpec((1, TILE), lambda qi, ti: (0, qi))
    tabspec = pl.BlockSpec((two_s, rt), lambda qi, ti: (0, ti))
    outs = pl.pallas_call(
        functools.partial(_arena_kernel, slots=two_s // 2, row_tile=rt),
        grid=grid,
        in_specs=[qspec, qspec, qspec, tabspec],
        out_specs=[qspec] * 5,
        out_shape=[jax.ShapeDtypeStruct((1, b), jnp.int32)
                   for _ in range(5)],
        compiler_params=compiler_params(vmem_limit),
        interpret=interpret,
        name="cuckoo_probe",
    )(h, row_offsets, masks, table_f32)
    return outs[:4]                            # drop the priority scratch
