from .kernel import TILE, cuckoo_lookup_arena_pallas
from .ops import cuckoo_lookup_arena, cuckoo_lookup_arena_auto, stage_tables

__all__ = ["TILE", "cuckoo_lookup_arena_pallas", "cuckoo_lookup_arena",
           "cuckoo_lookup_arena_auto", "stage_tables"]
