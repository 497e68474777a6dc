from .kernel import TILE, fused_retrieve_pallas
from .ops import (fused_probe_locs, fused_retrieve_arena,
                  fused_retrieve_arena_auto, fused_retrieve_ragged,
                  fused_retrieve_state_auto, launch_plan,
                  stage_context_tables)
from .ref import fused_retrieve_ref

__all__ = ["TILE", "fused_retrieve_pallas",
           "fused_retrieve_arena", "fused_retrieve_arena_auto",
           "fused_retrieve_ragged", "fused_retrieve_state_auto",
           "fused_probe_locs", "launch_plan",
           "stage_context_tables", "fused_retrieve_ref"]
