"""Batched cuckoo-filter lookup — pure-jnp reference semantics.

This is the vectorized (TPU-adapted) form of the paper's lookup (§3.4): all
query-entity hashes are probed at once.  The Pallas kernel in
``repro.kernels.cuckoo_lookup`` implements exactly these semantics and is
validated against this function.

Slot priority matches the paper's linear bucket scan: bucket i1 slots 0..S-1,
then bucket i2 slots 0..S-1 — so after a temperature sort, hot entities
resolve at slot 0.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import hashing


class LookupResult(NamedTuple):
    hit: jax.Array        # (B,) bool
    head: jax.Array       # (B,) int32 — blocklist head / entity id (NULL=-1)
    bucket: jax.Array     # (B,) int32 — bucket of the matching slot
    slot: jax.Array       # (B,) int32 — slot within that bucket


def match_rows(fp: jax.Array, i1: jax.Array, i2: jax.Array,
               rows1: jax.Array, rows2: jax.Array,
               heads1: jax.Array, heads2: jax.Array,
               s: int) -> LookupResult:
    """Shared slot-priority match over two gathered bucket rows — the one
    place lookup semantics live; the arena probe gathers its candidate
    rows and defers to this."""
    match = jnp.concatenate([rows1 == fp[:, None],
                             rows2 == fp[:, None]], axis=1)   # (B, 2S)
    hit = jnp.any(match, axis=1)
    first = jnp.argmax(match, axis=1)                # first matching position
    bucket = jnp.where(first < s, i1, i2).astype(jnp.int32)
    slot = jnp.where(first < s, first, first - s).astype(jnp.int32)
    heads_cat = jnp.concatenate([heads1, heads2], axis=1)
    head = jnp.where(hit,
                     jnp.take_along_axis(heads_cat, first[:, None], axis=1)[:, 0],
                     jnp.int32(-1))
    return LookupResult(hit=hit, head=head.astype(jnp.int32),
                        bucket=bucket, slot=slot)


def lookup_arena(fingerprints: jax.Array, heads: jax.Array,
                 row_offsets: jax.Array, masks: jax.Array,
                 h: jax.Array) -> LookupResult:
    """Probe a flat ragged bucket arena with pre-routed per-query segments.

    fingerprints/heads: (A, S) arena tables; ``row_offsets``/``masks``:
    (B,) per-query segment start and bucket mask ``nb_t - 1``.  This is
    the layer the sharded all-to-all hands exchanged queries to (the
    receiving shard knows each query's segment, not its global tree id);
    ``retrieve_device`` derives the per-query routing from the per-tree
    offsets table.  A single filter ``(NB, S)`` is an arena of one
    segment: zero offsets and masks ``NB - 1``.  ``bucket`` is the
    tree-local bucket index, so results are bit-identical to probing that
    tree's standalone filter.
    """
    s = fingerprints.shape[-1]
    fp, i1, i2 = hashing.candidate_buckets_masked(
        h.astype(jnp.uint32), masks.astype(jnp.uint32), jnp)
    base = row_offsets.astype(jnp.int32)
    r1 = base + i1.astype(jnp.int32)
    r2 = base + i2.astype(jnp.int32)
    return match_rows(fp, i1, i2, fingerprints[r1], fingerprints[r2],
                      heads[r1], heads[r2], s)


def bump_temperature_arena(temperature: jax.Array, row_offsets: jax.Array,
                           res: LookupResult) -> jax.Array:
    """Algorithm 3: temperature += 1 for every hit slot (scatter-add).
    temperature (A, S); ``row_offsets`` (B,) per-query segment starts —
    the hit slot lives at arena row ``row_offsets + bucket``."""
    return temperature.at[row_offsets.astype(jnp.int32) + res.bucket,
                          res.slot].add(res.hit.astype(temperature.dtype))


def _sort_slots(fingerprints: jax.Array, temperature: jax.Array,
                *tables: jax.Array):
    """Stable per-bucket slot reorder by descending temperature, empties
    last; any number of payload tables ride along under the same order."""
    key = jnp.where(fingerprints == jnp.uint32(hashing.EMPTY_FP),
                    jnp.int64(-(2 ** 62)) if temperature.dtype == jnp.int64
                    else jnp.int32(-(2 ** 30)),
                    temperature.astype(jnp.int32))
    order = jnp.argsort(-key, axis=1, stable=True)
    take = lambda a: jnp.take_along_axis(a, order, axis=1)
    return (take(fingerprints), take(temperature)) + tuple(
        take(t) for t in tables)


def sort_buckets_arena(fingerprints: jax.Array, temperature: jax.Array,
                       *tables: jax.Array):
    """Ragged-arena idle-time sort: one flat per-bucket slot reorder over
    the whole ``(A, S)`` arena (a bucket sort never crosses rows, so the
    tree segmentation needs no special handling).  Hot fingerprints float to
    slot 0 of their bucket within every tree's filter at once.  Payload
    tables (heads, entity ids, ...) are variadic so both the 3-table
    device state and the 5-table host bank restage through the same
    routine."""
    return _sort_slots(fingerprints, temperature, *tables)
