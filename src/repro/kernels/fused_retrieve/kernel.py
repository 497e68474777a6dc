"""Pallas TPU kernel: fused CFT-RAG retrieval — one pass from query hash to
context rows.

Dataflow per query tile (TILE=128 lanes), all stages on-chip:

    hash -> arena probe (shared ``_arena_probe`` accumulators, arena rows
    streamed in ``row_tile`` blocks over the inner grid axis, double-
    buffered by the Pallas pipeline) -> temperature bump -> CSR location
    window (sentinel-row miss routing) -> ancestor / descendant hierarchy
    windows (static ``n``-step unrolled walks)

No ``(B,)``-shaped intermediate (hit/head/bucket/slot) ever round-trips
HBM: the probe accumulators live in the output blocks, and the context
tail consumes them in-register on the *last* arena tile, when the
cross-tile priority merge has settled.  The CSR/forest tables and the
temperature table ride as whole VMEM blocks with constant index maps
(resident for the launch, consecutively revisited — the budget in
``ops.context_resident_bytes`` accounts for them).

Layout, as in the probe kernel: queries ride the lanes, so per-query
values are ``(1, TILE)`` rows and every table is staged transposed —
``(columns, rows)`` — so a gather returns ``(columns, TILE)`` and a column
is a sublane row.  The context tables are further cut into fixed-width
chunks along their rows (``ops.chunk_columns``) that a gather loops over.
The temperature table is ``(S, A)``; the location and hierarchy outputs
are ``(max_locs, 1 | n, B)``.

Two static gather strategies (``mxu``):
  * ``mxu=True``  — one-hot matmul gathers on the MXU (TPU; exact in f32
    for values < 2^24, which the wrapper asserts from the table shapes).
  * ``mxu=False`` — direct clipped vector gathers (interpret mode, where
    one-hot matmuls would lower to giant dense XLA ops).
Both produce bit-identical results; tests pin them against each other and
against the unfused oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..cuckoo_lookup.kernel import TILE, _arena_probe, compiler_params

NULL = -1
_HIGHEST = jax.lax.Precision.HIGHEST
#: Context-table columns one MXU gather step covers; its one-hot operand
#: is ``(GATHER_CHUNK, TILE)`` f32 (512 KiB).
GATHER_CHUNK = 1024


def _gather_rows(tab_ref, idx, gate, mxu):
    """Gather columns ``idx`` (1, TILE) int32 of a context table staged as
    ``(K, C, W)`` chunks (column ``r`` at ``[r // W, :, r % W]``, see
    ``ops.chunk_columns``) -> (C, TILE) f32; lanes with ``gate`` False, or
    an index past the table, yield zeros (callers re-mask with their own
    sentinel).  mxu: one-hot matmul per chunk, accumulated over a loop so
    the one-hot operand stays ``(W, TILE)`` whatever the table length (one
    chunk holds a lane's column, the others add exact zeros); else clipped
    indexing."""
    nch, c, w = tab_ref.shape
    if not mxu:
        safe = jnp.clip(idx[0], 0, nch * w - 1)
        cols = tab_ref[...][safe // w, :, safe % w].T           # (C, TILE)
        return jnp.where(gate, cols, jnp.float32(0))
    it = jax.lax.broadcasted_iota(jnp.int32, (w, TILE), 0)

    def step(k, acc):
        oh = ((it + k * w == idx) & gate).astype(jnp.float32)
        return acc + jax.lax.dot(tab_ref[k], oh, precision=_HIGHEST)

    return jax.lax.fori_loop(0, nch, step,
                             jnp.zeros((c, TILE), jnp.float32))


def _as_int(row):
    return row.astype(jnp.int32)


def _up_walk(nodes, pe_ref, n, mxu):
    """Ancestor window: ``n`` (1, TILE) rows — mirrors
    ``core.context.gather_hierarchy`` on the packed [parent; entity_id]
    table."""
    cur = nodes
    outs = []
    for _ in range(n):
        g = cur != NULL
        prow = _gather_rows(pe_ref, jnp.maximum(cur, 0), g, mxu)
        p = jnp.where(g, _as_int(prow[0:1]), NULL)
        g2 = p != NULL
        erow = _gather_rows(pe_ref, jnp.maximum(p, 0), g2, mxu)
        outs.append(jnp.where(g2, _as_int(erow[1:2]), NULL))
        cur = p
    return outs


def _down_walk(nodes, child_lc_ref, child_index_ref, pe_ref, n, mxu):
    """Descendant window: ``n`` (1, TILE) rows — mirrors
    ``core.context.gather_descendants`` on packed tables: child_lc
    [child_lo; child_count], child_index, entity ids from the
    parent/entity table's second row."""
    null_row = jnp.full((1, TILE), NULL, jnp.int32)
    buf = [null_row] * n                       # BFS frontier ring, cap n
    w = jnp.zeros((1, TILE), jnp.int32)        # frontier write cursor

    def push(buf, w, src):
        g = src != NULL
        lc = _gather_rows(child_lc_ref, jnp.maximum(src, 0), g, mxu)
        lo = _as_int(lc[0:1])
        hi = lo + _as_int(lc[1:2])
        for k in range(n):
            idx = lo + k
            valid = g & (idx < hi) & (w < n)
            crow = _gather_rows(child_index_ref, idx, valid, mxu)
            c = jnp.where(valid, _as_int(crow), NULL)
            buf = [jnp.where(valid & (w == j), c, buf[j]) for j in range(n)]
            w = jnp.where(valid, w + 1, w)
        return buf, w

    buf, w = push(buf, w, nodes)
    out = [null_row] * n
    for i in range(n):
        cur = buf[i]
        valid = (i < w) & (cur != NULL)
        erow = _gather_rows(pe_ref, jnp.maximum(cur, 0), valid, mxu)
        out[i] = jnp.where(valid, _as_int(erow[1:2]), out[i])
        buf, w = push(buf, w, jnp.where(valid, cur, NULL))
    return out


def _context_tail(qoff, valid, csr_lc_ref, csr_nodes_ref, parent_eid_ref,
                  child_lc_ref, child_index_ref, hit_ref, head_ref,
                  bucket_ref, slot_ref, loc_ref, up_ref, down_ref,
                  temp_in_ref, temp_ref, qi, *, slots, max_locs, n, mxu,
                  locs_only):
    """Consume the settled probe accumulators: bump temperature, gather the
    CSR window, walk the hierarchy — all from VMEM-resident tables.  One
    loop step per location slot keeps the walks' code emitted once."""
    vhit = (hit_ref[...] > 0) & valid               # = unfused hit&in_range
    hit_ref[...] = vhit.astype(jnp.int32)           # the emitted hit
    bucket = bucket_ref[...]
    slot = slot_ref[...]

    @pl.when(qi == 0)
    def _init_temp():
        temp_ref[...] = temp_in_ref[...]

    arena_rows = temp_ref.shape[1]
    rows = qoff + bucket                            # always < arena_rows
    if mxu:
        it = jax.lax.broadcasted_iota(jnp.int32, (arena_rows, TILE), 0)
        rows_oh = ((it == rows) & vhit).astype(jnp.float32)
        st = jax.lax.broadcasted_iota(jnp.int32, (slots, TILE), 0)
        slot_oh = (st == slot).astype(jnp.float32)
        contrib = jax.lax.dot_general(                     # (S, A) counts
            slot_oh, rows_oh, (((1,), (1,)), ((), ())), precision=_HIGHEST)
        temp_ref[...] += contrib.astype(temp_ref.dtype)
    else:
        t = temp_ref[...]
        temp_ref[...] = t.at[slot[0], jnp.clip(rows[0], 0, arena_rows - 1)
                             ].add(vhit[0].astype(t.dtype))

    # CSR location window; misses gather zero rows (start 0, count 0)
    lc = _gather_rows(csr_lc_ref, jnp.where(vhit, head_ref[...], 0), vhit,
                      mxu)
    lo = _as_int(lc[0:1])
    count = _as_int(lc[1:2])

    def location(k, carry):
        validk = (k < count) & vhit
        nrow = _gather_rows(csr_nodes_ref, lo + k, validk, mxu)
        node_k = jnp.where(validk, _as_int(nrow), NULL)
        loc_ref[k] = node_k
        if not locs_only:
            src = jnp.maximum(node_k, 0)
            miss = node_k == NULL
            ups = _up_walk(src, parent_eid_ref, n, mxu)
            downs = _down_walk(src, child_lc_ref, child_index_ref,
                               parent_eid_ref, n, mxu)
            for j in range(n):
                up_ref[k, j:j + 1, :] = jnp.where(miss, NULL, ups[j])
                down_ref[k, j:j + 1, :] = jnp.where(miss, NULL, downs[j])
        return carry

    jax.lax.fori_loop(0, max_locs, location, 0)


def _split_out_refs(refs, locs_only):
    """(hit, head, bucket, slot, prio, loc[, up, down], temp) — the
    locs_only variant (sharded owner probe) omits the hierarchy blocks."""
    if locs_only:
        hit, head, bucket, slot, prio, loc, temp = refs
        return hit, head, bucket, slot, prio, loc, None, None, temp
    return refs


def _fused_kernel(h_ref, off_ref, mask_ref, valid_ref, tab_ref, temp_in_ref,
                  csr_lc_ref, csr_nodes_ref, parent_eid_ref, child_lc_ref,
                  child_index_ref, *out_refs, slots, row_tile, num_tiles,
                  max_locs, n, mxu, locs_only):
    """Pre-routed fused kernel: probe every arena tile, run the context
    tail once the last tile's priority merge has settled."""
    (hit_ref, head_ref, bucket_ref, slot_ref, prio_ref, loc_ref, up_ref,
     down_ref, temp_ref) = _split_out_refs(out_refs, locs_only)
    qi = pl.program_id(0)
    ti = pl.program_id(1)
    qoff = off_ref[...]
    _arena_probe(h_ref[...], qoff, mask_ref[...], ti, tab_ref, hit_ref,
                 head_ref, bucket_ref, slot_ref, prio_ref, slots=slots,
                 row_tile=row_tile)

    @pl.when(ti == num_tiles - 1)
    def _tail():
        _context_tail(qoff, valid_ref[...] > 0, csr_lc_ref, csr_nodes_ref,
                      parent_eid_ref, child_lc_ref, child_index_ref,
                      hit_ref, head_ref, bucket_ref, slot_ref, loc_ref,
                      up_ref, down_ref, temp_in_ref, temp_ref, qi,
                      slots=slots, max_locs=max_locs, n=n, mxu=mxu,
                      locs_only=locs_only)


def fused_retrieve_pallas(h, row_offsets, masks, valid, table_f32,
                          temperature, csr_lc, csr_nodes, parent_eid,
                          child_lc, child_index, max_locs: int = 4,
                          n: int = 3, interpret: bool = True,
                          row_tile: int = 0, mxu: bool = False,
                          locs_only: bool = False, vmem_limit: int = 0):
    """Pre-routed fused retrieval.  h/row_offsets/masks/valid: ``(1, B)``
    with B % TILE == 0; ``table_f32`` ``(2S, A)`` (A a multiple of TILE and
    of row_tile when tiling); temperature ``(S, A)``; context tables packed
    by ``ops.stage_context_tables``.  Returns (hit, head, bucket, slot,
    prio, locations ``(max_locs, 1, B)``[, up, down ``(max_locs, n, B)``],
    temperature) — the wrapper drops the probe internals."""
    two_s, rows_total = table_f32.shape
    slots = two_s // 2
    b = h.shape[1]
    rt = rows_total if row_tile <= 0 else row_tile
    assert rows_total % rt == 0 and rt % TILE == 0, \
        "pad the arena to a multiple of row_tile (and TILE) before calling"
    nt = rows_total // rt
    grid = (b // TILE, nt)                     # arena axis innermost
    qspec = pl.BlockSpec((1, TILE), lambda qi, ti: (0, qi))
    tabspec = pl.BlockSpec((two_s, rt), lambda qi, ti: (0, ti))

    def wide(w):
        return pl.BlockSpec((max_locs, w, TILE), lambda qi, ti: (0, 0, qi))

    def const(arr):
        return pl.BlockSpec(arr.shape,
                            lambda qi, ti: (0,) * arr.ndim)

    out_shape = [jax.ShapeDtypeStruct((1, b), jnp.int32) for _ in range(5)]
    out_specs = [qspec] * 5
    for w in [1] if locs_only else [1, n, n]:
        out_shape.append(jax.ShapeDtypeStruct((max_locs, w, b), jnp.int32))
        out_specs.append(wide(w))
    out_shape.append(jax.ShapeDtypeStruct(temperature.shape,
                                          temperature.dtype))
    out_specs.append(const(temperature))
    return pl.pallas_call(
        functools.partial(_fused_kernel, slots=slots, row_tile=rt,
                          num_tiles=nt, max_locs=max_locs, n=n, mxu=mxu,
                          locs_only=locs_only),
        grid=grid,
        in_specs=[qspec, qspec, qspec, qspec, tabspec, const(temperature),
                  const(csr_lc), const(csr_nodes), const(parent_eid),
                  const(child_lc), const(child_index)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit),
        interpret=interpret,
        name="fused_retrieve",
    )(h, row_offsets, masks, valid, table_f32, temperature, csr_lc,
      csr_nodes, parent_eid, child_lc, child_index)
