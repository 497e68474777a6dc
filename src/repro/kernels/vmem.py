"""Shared VMEM budget for the retrieval kernels.

The arena kernels (``cuckoo_lookup`` and ``fused_retrieve``) stream arena
tiles through VMEM and must cap the rows per tile so that the tile working
set fits on chip.  The capacity comes from the attached TPU (Pallas' TPU
hardware table, ``pltpu.get_tpu_info``, keyed by ``device_kind``; a kind
the table does not know is an error).  Only a TPU has VMEM: off the chip
the kernels run in interpret mode and nothing here is consulted.

Per arena row streamed through a probe tile (closed form, f32 staging):

    (2S, rows) table tile, double-buffered   2 * 2 * slots * 4 bytes
    two (rows, TILE) one-hot operands        2 * TILE * 4 bytes
    -------------------------------------------------------------
    per_row = 4 * (4 * slots + 2 * TILE)

Budget = half of the core's VMEM for the streamed tiles; the other half is
headroom for the residents and Mosaic's own scratch.  The kernels are
launched with a scoped VMEM limit of the whole capacity, so the compiler
holds them to the physical memory the budget was derived from.

No measurement refines the closed form: ``memory_analysis()`` of a
program that calls a kernel counts the program's HBM temporaries on the
TPU (and interpret-mode buffers on the CPU), never the kernel's VMEM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax

from ..obs import get_registry

#: Fraction of VMEM the streamed tiles may occupy.
BUDGET_FRACTION = 0.5


class VmemBudget(NamedTuple):
    budget_bytes: int     # bytes available to the streamed tile working set
    per_row_bytes: int    # bytes of VMEM one arena row costs inside a tile
    limit_bytes: int      # scoped VMEM limit the kernels are launched with


def vmem_capacity_bytes() -> int:
    """Per-core VMEM of the attached TPU.  Raises on any other backend
    (nothing off the chip has VMEM) and on a TPU kind the hardware table
    does not list."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no VMEM on a {dev.platform} device: the "
                           "retrieval kernels run interpreted there")
    from jax.experimental.pallas import tpu as pltpu
    return int(pltpu.get_tpu_info().vmem_capacity_bytes)


def closed_form_row_bytes(slots: int, tile: int) -> int:
    """Staged f32 table tile (double-buffered) + the two one-hot operands."""
    return 4 * (4 * slots + 2 * tile)


def budget_for(capacity_bytes: int, slots: int = 4,
               tile: int = 128) -> VmemBudget:
    """The tile budget on a core with ``capacity_bytes`` of VMEM."""
    return VmemBudget(budget_bytes=int(capacity_bytes * BUDGET_FRACTION),
                      per_row_bytes=closed_form_row_bytes(slots, tile),
                      limit_bytes=int(capacity_bytes))


@functools.lru_cache(maxsize=None)
def device_budget(slots: int = 4, tile: int = 128) -> VmemBudget:
    """The tile budget of the attached TPU (cached per process)."""
    b = budget_for(vmem_capacity_bytes(), slots, tile)
    get_registry().gauge(
        "kernel.vmem_budget_bytes",
        "VMEM bytes budgeted for streamed arena tiles").set(b.budget_bytes)
    return b


def max_rows_for_vmem(budget: VmemBudget, tile: int = 128,
                      resident_bytes: int = 0) -> int:
    """Largest arena row count whose tile working set fits the budget after
    subtracting ``resident_bytes`` (tables pinned for the whole launch,
    e.g. the fused kernel's CSR/forest/temperature blocks)."""
    avail = max(budget.budget_bytes - resident_bytes, 0)
    rows = avail // budget.per_row_bytes
    return max(tile, rows // tile * tile)


def row_tile_for(arena_rows: int, max_rows: int) -> int:
    """0 (the whole arena as one block) when it fits ``max_rows``; else
    ``max_rows`` arena rows per grid step."""
    return 0 if arena_rows <= max_rows else max_rows
