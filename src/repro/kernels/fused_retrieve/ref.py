"""Pure-jnp oracle for the fused retrieval kernel.

Semantically this is ``lookup_arena`` + temperature bump + the CSR location
window + hierarchy walks — exactly what ``retrieve_device`` followed by
``gather_context`` computes — restated in the *fused* dataflow the Pallas
kernel implements: the sentinel-row miss routing and the core path's
select-based walks (static ``n`` steps, no ``lax.while``/``lax.cond``,
``core.context.gather_hierarchy``/``gather_descendants``), so every
intermediate stays a register-shaped value.  Tests pin this function
bit-identical to the unfused core path; the kernel is validated against
both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.lookup import bump_temperature_arena, lookup_arena
from ...core.trag import NULL, DeviceRetrieval, hierarchy_windows


def fused_retrieve_ref(fingerprints: jax.Array, temperature: jax.Array,
                       heads: jax.Array, row_offsets: jax.Array,
                       masks: jax.Array, valid: jax.Array, h: jax.Array,
                       csr_offsets: jax.Array, csr_nodes: jax.Array,
                       parent: jax.Array, entity_id: jax.Array,
                       child_offsets: jax.Array, child_index: jax.Array,
                       max_locs: int = 4, n: int = 3) -> DeviceRetrieval:
    """One fused pass: probe -> bump -> CSR window -> hierarchy windows.

    ``valid`` is the per-query admission mask (in-range tree, real lane):
    invalid lanes miss, bump nothing, and emit NULL windows — matching the
    ``in_range`` masking in ``retrieve_device``.
    """
    res = lookup_arena(fingerprints, heads, row_offsets, masks, h)
    res = res._replace(hit=res.hit & valid)
    temp = bump_temperature_arena(temperature, row_offsets, res)

    # Miss routing: misses read the empty sentinel window [terminal,
    # terminal) at CSR row R instead of row 0's real window (satellite fix,
    # mirrored from core.trag.csr_window).
    r = csr_offsets.shape[0] - 1
    eid = jnp.where(res.hit, res.head, r)
    lo = csr_offsets[eid]
    count = csr_offsets[jnp.minimum(eid + 1, r)] - lo
    k = jnp.arange(max_locs, dtype=jnp.int32)
    idx = lo[:, None] + k[None, :]
    window = (k[None, :] < count[:, None]) & res.hit[:, None]
    safe = jnp.clip(idx, 0, csr_nodes.shape[0] - 1)
    nodes = jnp.where(window, csr_nodes[safe], NULL)       # (B, max_locs)

    up, down = hierarchy_windows(parent, entity_id, child_offsets,
                                 child_index, nodes, n=n)
    return DeviceRetrieval(hit=res.hit, locations=nodes, up=up, down=down,
                           temperature=temp)
