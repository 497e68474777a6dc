"""CFT-RAG core: improved cuckoo filter + entity-tree retrieval."""
from .bank import (ColdTenant, FilterBank, ShardedBank, TenantRegistry,
                   build_bank, build_bank_from_rows, estimate_fpr,
                   plan_partition, splice_arena_rows, splice_arena_segment)
from .baselines import BloomTRAG, BloomTRAG2, NaiveTRAG
from .blocklist import BlockListArena, BlockListBuilder, CSRArena, build_csr
from .context import (EntityContext, context_from_arena, context_from_csr,
                      generate_context, render_context)
from .cuckoo import (CFTIndex, CuckooFilter, CuckooTables, build_index,
                     bulk_place)
from .lookup import (LookupResult, bump_temperature_arena, lookup_arena,
                     sort_buckets_arena)
from .maintenance import (BankDelta, MaintenanceBreaker, MaintenanceEngine,
                          MaintenanceReport, PendingRestage,
                          PendingShardedRestage, ShardedMaintenanceEngine,
                          commit_restage, warm_restage)
from .snapshot import (RestoredSnapshot, SnapshotWriter,
                       apply_maint_bookkeeping, cleanup_snapshots,
                       latest_snapshot, list_snapshots, list_tenants,
                       load_tenant, merge_sharded_bank, restore_snapshot,
                       restore_state, save_snapshot, save_tenant)
from .trag import (CFTRAG, CFTDeviceState, DeviceRetrieval, build_retriever,
                   csr_window, finish_context, gather_context,
                   retrieve_device)
from .distributed import (ShardedBankState, plan_tenant_partition,
                          routing_counts, shard_bank, sharded_apply_delta,
                          sharded_lookup, sharded_lookup_bank,
                          sharded_retrieve_device, sharded_splice_segment,
                          shard_filter_tables, stage_sharded_bank)
from .tree import EntityForest, build_forest

__all__ = [
    "ColdTenant", "FilterBank", "ShardedBank", "TenantRegistry",
    "build_bank", "build_bank_from_rows",
    "estimate_fpr", "plan_partition", "splice_arena_rows",
    "splice_arena_segment",
    "BankDelta", "MaintenanceBreaker", "MaintenanceEngine",
    "MaintenanceReport",
    "PendingRestage", "PendingShardedRestage", "ShardedMaintenanceEngine",
    "commit_restage", "warm_restage",
    "RestoredSnapshot", "SnapshotWriter", "apply_maint_bookkeeping",
    "cleanup_snapshots", "latest_snapshot", "list_snapshots",
    "list_tenants", "load_tenant", "merge_sharded_bank",
    "restore_snapshot", "restore_state", "save_snapshot", "save_tenant",
    "ShardedBankState", "plan_tenant_partition", "routing_counts",
    "shard_bank",
    "sharded_apply_delta", "sharded_lookup", "sharded_lookup_bank",
    "sharded_retrieve_device", "sharded_splice_segment",
    "shard_filter_tables", "stage_sharded_bank", "gather_context",
    "csr_window", "finish_context",
    "BloomTRAG", "BloomTRAG2", "NaiveTRAG",
    "BlockListArena", "BlockListBuilder", "CSRArena", "build_csr",
    "EntityContext", "context_from_arena", "context_from_csr",
    "generate_context", "render_context",
    "CFTIndex", "CuckooFilter", "CuckooTables", "build_index", "bulk_place",
    "LookupResult", "bump_temperature_arena", "lookup_arena",
    "sort_buckets_arena",
    "CFTRAG", "CFTDeviceState", "DeviceRetrieval", "build_retriever",
    "retrieve_device",
    "EntityForest", "build_forest",
]
