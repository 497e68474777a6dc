from .kernel import TILE, cuckoo_lookup_arena_pallas
from .ops import (cuckoo_lookup, cuckoo_lookup_arena,
                  cuckoo_lookup_arena_auto, cuckoo_lookup_auto,
                  cuckoo_lookup_bank, cuckoo_lookup_bank_auto,
                  cuckoo_lookup_ragged, cuckoo_lookup_ragged_auto,
                  cuckoo_lookup_trees, stage_tables)
from .ref import (cuckoo_lookup_arena_ref, cuckoo_lookup_bank_ref,
                  cuckoo_lookup_ragged_ref, cuckoo_lookup_ref)

__all__ = ["TILE", "cuckoo_lookup_arena_pallas",
           "cuckoo_lookup", "cuckoo_lookup_auto", "cuckoo_lookup_bank",
           "cuckoo_lookup_bank_auto", "cuckoo_lookup_arena",
           "cuckoo_lookup_arena_auto", "cuckoo_lookup_ragged",
           "cuckoo_lookup_ragged_auto", "cuckoo_lookup_trees",
           "stage_tables", "cuckoo_lookup_ref", "cuckoo_lookup_bank_ref",
           "cuckoo_lookup_arena_ref", "cuckoo_lookup_ragged_ref"]
