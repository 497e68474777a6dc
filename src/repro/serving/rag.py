"""End-to-end CFT-RAG serving pipeline (paper Figure 1).

query -> entity recognition (NER stub) -> cuckoo-filter lookup -> block-list
walk -> hierarchical context (Algorithm 3) -> prompt assembly
[system | context | query] -> generator prefill+decode.

Two retrieval paths:
* host path — CFTRAG (temperature bump + idle-time bucket sort between
  rounds), used by benchmarks and the default pipeline;
* device path — ``retrieve_device`` with the Pallas lookup kernel, fusing
  retrieval into the jitted serving step (TPU deployment shape).
"""
from __future__ import annotations

import asyncio
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..core import (CFTRAG, CFTDeviceState, MaintenanceEngine,
                    ShardedBankState, ShardedMaintenanceEngine, build_bank,
                    build_forest, build_index, retrieve_device,
                    sharded_retrieve_device, stage_sharded_bank)
from ..core import hashing
from ..data.datasets import SyntheticCorpus
from ..data.ner import (add_to_gazetteer, build_gazetteer,
                        recognize_entities)
from ..data.tokenizer import HashTokenizer
from ..kernels.cuckoo_lookup.ops import cuckoo_lookup_arena_auto
from .async_engine import AsyncServeEngine
from .engine import Request, RetrievalSession, ServeEngine

SYSTEM_PROMPT = ("You are an assistant answering questions about an "
                 "organization using its entity hierarchy.")


@dataclasses.dataclass
class RAGAnswer:
    query: str
    entities: List[str]
    context: str
    prompt: str
    output_ids: Optional[List[int]] = None
    text: Optional[str] = None


class RAGPipeline:
    def __init__(self, corpus: SyntheticCorpus, engine: Optional[ServeEngine],
                 tokenizer: Optional[HashTokenizer] = None,
                 num_buckets: int = 1024, n_hierarchy: int = 3,
                 use_device_lookup: bool = False, use_bank: bool = False,
                 mesh=None, mesh_axis: str = "model",
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 1, snapshot_keep: int = 3,
                 tenants=None):
        self.corpus = corpus
        self.forest = build_forest(corpus.trees)
        self.index = build_index(self.forest, num_buckets=num_buckets)
        self.retriever = CFTRAG(self.index, n_hierarchy=n_hierarchy)
        self.gazetteer = build_gazetteer(self.forest.entity_names)
        self.engine = engine
        self.tokenizer = tokenizer or HashTokenizer(
            engine.cfg.vocab if engine else 64000)
        self.use_device_lookup = use_device_lookup or use_bank
        self.use_bank = use_bank
        self._mesh, self._mesh_axis = mesh, mesh_axis
        self.bank = build_bank(self.forest) if use_bank else None
        # the session owns the device state and the two-phase restage
        # lifecycle; the pipeline's `_dev_state`/`_coord` are views on it
        self.session = RetrievalSession()
        self._gen_lock = threading.Lock()
        # crash recovery: a compatible snapshot under snapshot_dir
        # replaces the fresh bank/state build — bit-identical to what was
        # serving when the snapshot was taken (corrupt or layout-
        # incompatible snapshots fall back to a fresh build)
        self.snapshot_dir = snapshot_dir
        self.restored_step: Optional[int] = None
        if snapshot_dir:
            # startup sweep: a crash (or injected fault) mid-snapshot
            # leaves a tmp.* dir behind — sweep it here so restarts never
            # accumulate leaked disk (keep_last <= 0 means "keep all
            # snapshots", so the sweep then only removes tmp dirs)
            from ..core.snapshot import cleanup_snapshots, list_snapshots
            keep = snapshot_keep if snapshot_keep > 0 \
                else max(1, len(list_snapshots(snapshot_dir)))
            cleanup_snapshots(snapshot_dir, keep_last=keep)
        snap = self._load_snapshot() if use_bank and snapshot_dir else None
        if use_bank and mesh is not None:
            from ..core.snapshot import apply_maint_bookkeeping, \
                restore_state
            if snap is not None:
                self.bank = snap.bank
                self.maintenance = ShardedMaintenanceEngine(self.bank)
                apply_maint_bookkeeping(self.maintenance, snap)
                self._dev_state = restore_state(snap, mesh=mesh,
                                                axis=mesh_axis)
                self.restored_step = snap.step
            else:
                # bank-axis sharded deployment: tree ranges partitioned
                # over the mesh axis, shard-local maintenance,
                # all-to-all routing
                self.bank = self.bank.shard(int(mesh.shape[mesh_axis]))
                self.maintenance = ShardedMaintenanceEngine(self.bank)
                self._dev_state = stage_sharded_bank(self.bank, self.forest,
                                                     mesh, mesh_axis)
        elif use_bank:
            from ..core.snapshot import apply_maint_bookkeeping, \
                restore_state
            if snap is not None:
                self.bank = snap.bank
                self.maintenance = MaintenanceEngine(self.bank)
                apply_maint_bookkeeping(self.maintenance, snap)
                self._dev_state = restore_state(snap)
                self.restored_step = snap.step
            else:
                self.maintenance = MaintenanceEngine(self.bank)
                # NB: the pipeline owns its device state, so it runs its
                # own idle-time hook (maintain() below) rather than
                # attaching the engine's — two restage owners over one
                # bank would let host and device slot layouts diverge.
                self._dev_state = CFTDeviceState.from_bank(self.bank,
                                                           self.forest)
        elif use_device_lookup:
            self.maintenance = None
            self._dev_state = CFTDeviceState.from_index(self.index)
        else:
            self.maintenance = None
            self._dev_state = None
        if self._dev_state is not None:
            # builds the padded jitted step (used by the async engine);
            # the inline `retrieve` below keeps its own exact-shape calls
            self.session.attach(self._dev_state,
                                lookup_fn=cuckoo_lookup_arena_auto)
        if self.maintenance is not None:
            self.session.attach_maintenance(self.maintenance, self.forest)
        if tenants is not None:
            # tenant -> tree-range registry: quotas, per-tenant fault
            # domains, and the evict/reload lifecycle key off it
            from ..core.bank import TenantRegistry
            reg = tenants if isinstance(tenants, TenantRegistry) \
                else TenantRegistry(tenants)
            self.session.attach_tenants(reg)
        self.tenants = self.session.tenants
        if self.maintenance is not None and snapshot_dir is not None \
                and snapshot_every > 0:
            from ..core.snapshot import SnapshotWriter
            from .faultinject import fault_point
            self.session.configure_snapshots(SnapshotWriter(
                snapshot_dir, every=snapshot_every, keep_last=snapshot_keep,
                fault_hook=fault_point))

    def _load_snapshot(self):
        """Latest snapshot under ``snapshot_dir`` if it matches this
        pipeline's deployment layout (flat vs sharded, shard count ==
        mesh axis size); ``None`` — fresh build — otherwise, including
        on a corrupt snapshot (crash recovery must never crash)."""
        from ..core import ShardedBank
        from ..core.snapshot import latest_snapshot, restore_snapshot
        try:
            if latest_snapshot(self.snapshot_dir) is None:
                return None
            snap = restore_snapshot(self.snapshot_dir)
        except Exception:
            return None
        sharded = isinstance(snap.bank, ShardedBank)
        if sharded != (self._mesh is not None):
            return None
        if sharded and snap.bank.num_shards != int(
                self._mesh.shape[self._mesh_axis]):
            return None
        if not snap.state_leaves or not snap.row_alive:
            return None
        return snap

    # device state + restage lifecycle live on the session; keep the
    # historical attribute names as views so callers (and tests) that
    # poke `rag._dev_state` / `rag._coord` see the single source of truth
    @property
    def _dev_state(self):
        return self.session.state

    @_dev_state.setter
    def _dev_state(self, state) -> None:
        self.session.state = state

    @property
    def _coord(self):
        return self.session.coord

    # ---------------------------------------------------------- retrieval
    def retrieve(self, query: str,
                 tree_scope: Optional[int] = None) -> RAGAnswer:
        """Recognize entities and retrieve their hierarchical context.

        ``tree_scope`` routes the whole query batch to one tree of the
        filter bank (multi-tenant shape); ``None`` retrieves globally —
        on a bank state that fans each entity out to every tree.
        """
        # the device path stays inline: JAX records the Python stack of
        # every operation the eager step traces, and a helper frame would
        # lengthen each
        with self.session.tracer.span("rag.retrieve") as sp:
            with sp.stage("recognise"):
                ents = recognize_entities(query, self.gazetteer)
                if self.use_device_lookup:
                    trees_np, hashes_np, b = self._device_query_batch(
                        ents, tree_scope)
            if self.use_device_lookup:
                # the device stage holds the per-call trace, lowering and
                # compile work of the eager step; fetch waits for its
                # results
                with sp.stage("device"):
                    hashes = jnp.asarray(hashes_np)
                    trees = jnp.asarray(trees_np)
                    if isinstance(self._dev_state, ShardedBankState):
                        # the Pallas arena probe routes per query (segment
                        # start + bucket mask), so it works unchanged after
                        # tree-local expansions diverge per-tree bucket
                        # counts
                        out = sharded_retrieve_device(
                            self._dev_state, hashes, trees,
                            lookup_fn=cuckoo_lookup_arena_auto)
                    else:
                        out = retrieve_device(
                            self._dev_state, hashes, trees,
                            lookup_fn=cuckoo_lookup_arena_auto)
                    self._dev_state = self._dev_state.with_temperature(
                        out.temperature)
                with sp.stage("harvest"):
                    # harvest defers while a restage is staged-but-
                    # uncommitted (the bank may already carry the next
                    # geometry)
                    self.session.harvest()
                with sp.stage("fetch"):
                    up, down = np.asarray(out.up), np.asarray(out.down)
                with sp.stage("render"):
                    up, down = self._merge_bank_updown(up, down, b,
                                                       tree_scope)
                    ctxs = self._render_device(ents, up, down)
            else:
                with sp.stage("lookup"):
                    ctxs = self.retriever.render(
                        self.retriever.retrieve(ents))
            prompt = f"{SYSTEM_PROMPT}\n{ctxs}\nQuestion: {query}\nAnswer:"
        return RAGAnswer(query=query, entities=ents, context=ctxs,
                         prompt=prompt)

    def _device_query_batch(self, ents: Sequence[str],
                            tree_scope: Optional[int] = None):
        """Map recognized entities to the ``(tree_ids, hashes)`` batch the
        device step consumes.  ``tree_scope`` routes everything to one
        tree; bank mode with no scope fans each entity out to every tree
        (per-entity results merge back in :meth:`_merge_bank_updown`)."""
        hashes = np.asarray(hashing.hash_entities(ents) if ents
                            else np.zeros((1,), np.uint32))
        b = hashes.shape[0]
        if tree_scope is not None:
            trees = np.full((b,), tree_scope, np.int32)
        elif self.use_bank:
            # global query over a bank: (tree_id, hash) pairs for every
            # tree; per-entity results merge across trees afterwards
            t = self.bank.num_trees
            trees = np.repeat(np.arange(t, dtype=np.int32), b)
            hashes = np.tile(hashes, t)
        else:
            trees = np.zeros((b,), np.int32)
        return trees, hashes, b

    def _merge_bank_updown(self, up: np.ndarray, down: np.ndarray, b: int,
                           tree_scope: Optional[int]):
        """Fold the per-tree fan-out back to per-entity rows: the
        ``(t*b, locs, n)`` device result regroups as ``(b, t*locs, n)``."""
        if tree_scope is None and self.use_bank:
            t, locs, n = self.bank.num_trees, up.shape[1], up.shape[2]
            up = (up.reshape(t, b, locs, n).transpose(1, 0, 2, 3)
                    .reshape(b, t * locs, n))
            down = (down.reshape(t, b, locs, n).transpose(1, 0, 2, 3)
                      .reshape(b, t * locs, n))
        return up, down

    # -------------------------------------------------------- maintenance
    def insert_entity(self, tree: int, name: str,
                      nodes: Sequence[int]) -> None:
        """Queue a live (tree, entity) insert; applied at the next
        :meth:`maintain` idle window (bank mode only).  ``nodes`` are
        existing forest node ids the entity should resolve to.  The NER
        gazetteer learns the name immediately so queries can mention it
        as soon as the delta lands."""
        if self.maintenance is None:
            raise RuntimeError("dynamic updates need use_bank=True")
        eid = self.forest.name_to_id.get(name, -1)
        self.maintenance.queue_insert(tree, name, nodes, entity_id=eid)
        add_to_gazetteer(self.gazetteer, name)

    def delete_entity(self, tree: int, name: str) -> None:
        if self.maintenance is None:
            raise RuntimeError("dynamic updates need use_bank=True")
        self.maintenance.queue_delete(tree, name)

    def prepare_maintenance(self):
        """Phase one of the zero-pause restage: host-side maintenance pass
        + staging of only the changed bytes (overlappable with in-flight
        retrieval on the still-serving old state).  Commits any previous
        uncommitted plan first; returns the MaintenanceReport (None in
        non-bank mode)."""
        return self.session.prepare_maintenance()

    def commit_maintenance(self) -> bool:
        """Phase two: O(changed-bytes) device splice + atomic swap of the
        retrieval state.  Returns True when a staged plan was applied."""
        return self.session.commit_maintenance()

    def maintain(self):
        """Idle-time maintenance: apply queued inserts/deletes, compact,
        shrink, resort hot buckets, and splice-commit the device state if
        the bank mutated (``prepare_maintenance`` + ``commit_maintenance``
        in one call).  Returns the MaintenanceReport (None in non-bank
        mode)."""
        report = self.prepare_maintenance()
        self.commit_maintenance()
        return report

    def _render_device(self, ents: Sequence[str], up_arr: np.ndarray,
                       down_arr: np.ndarray) -> str:
        lines = []
        names = self.forest.entity_names
        for i, e in enumerate(ents):
            ups = [names[int(u)] for u in up_arr[i].ravel() if int(u) >= 0]
            downs = [names[int(d)] for d in down_arr[i].ravel()
                     if int(d) >= 0]
            if ups:
                lines.append(f"The upward hierarchical relationship of {e} "
                             f"are: {', '.join(dict.fromkeys(ups))}.")
            if downs:
                lines.append(f"The downward hierarchical relationship of {e} "
                             f"are: {', '.join(dict.fromkeys(downs))}.")
        return "\n".join(lines)

    # ----------------------------------------------------------- generate
    def answer(self, query: str, max_new_tokens: int = 16) -> RAGAnswer:
        # the answer's retrieve, generate and maintenance spans open
        # under this one
        with self.session.tracer.span("rag.answer"):
            ans = self.retrieve(query)
            if self.engine is None:
                return ans
            ids = self.tokenizer.encode(ans.prompt, bos=True)
            req = Request(prompt_ids=ids, max_new_tokens=max_new_tokens)
            self.engine.serve([req])
            ans.output_ids = req.out_ids
            ans.text = self.tokenizer.decode(req.out_ids)
            self.maintain()        # generation was the idle window
        return ans

    # -------------------------------------------------------------- async
    def async_serving(self, **knobs) -> AsyncServeEngine:
        """Build a continuous-batching front end over this pipeline's
        retrieval session (``latency_budget``, ``max_batch``,
        ``commit_every``, ... forward to :class:`AsyncServeEngine`).
        The returned engine coalesces concurrent :meth:`answer_async`
        retrievals into shared device batches and runs the two-phase
        maintenance lifecycle in the background — do not call
        :meth:`maintain` concurrently with a started engine."""
        if self._dev_state is None:
            raise RuntimeError(
                "async serving needs use_device_lookup or use_bank")
        return AsyncServeEngine(self.session, **knobs)

    async def answer_async(self, query: str, aengine: AsyncServeEngine,
                           max_new_tokens: int = 16,
                           tree_scope: Optional[int] = None) -> RAGAnswer:
        """Async flavor of :meth:`answer`: retrieval rides the shared
        continuous batches of ``aengine`` (built by
        :meth:`async_serving`), generation runs on an executor thread
        serialized by a lock (the decode step donates its buffers, so
        two generations must not interleave).  Maintenance is *not*
        driven here — the async engine's background lifecycle owns it."""
        ents = recognize_entities(query, self.gazetteer)
        trees, hashes, b = self._device_query_batch(ents, tree_scope)
        sl = await aengine.retrieve_async(
            [int(t) for t in trees], [int(h) for h in hashes])
        up, down = self._merge_bank_updown(np.asarray(sl.up),
                                           np.asarray(sl.down),
                                           b, tree_scope)
        ctxs = self._render_device(ents, up, down)
        prompt = f"{SYSTEM_PROMPT}\n{ctxs}\nQuestion: {query}\nAnswer:"
        ans = RAGAnswer(query=query, entities=ents, context=ctxs,
                        prompt=prompt)
        if self.engine is None:
            return ans
        ids = self.tokenizer.encode(ans.prompt, bos=True)
        req = Request(prompt_ids=ids, max_new_tokens=max_new_tokens)

        def _generate() -> None:
            with self._gen_lock:
                self.engine.serve([req])

        await asyncio.get_running_loop().run_in_executor(None, _generate)
        ans.output_ids = req.out_ids
        ans.text = self.tokenizer.decode(req.out_ids)
        return ans

    # --------------------------------------------------- retrieval metrics
    def retrieval_accuracy(self, queries: Sequence[str],
                           gold_entities: Sequence[Sequence[str]]) -> float:
        """Fraction of gold entities whose retrieved locations match a naive
        BFS exactly (the DESIGN.md §7 accuracy proxy)."""
        from ..core import NaiveTRAG
        naive = NaiveTRAG(self.forest)
        total, correct = 0, 0
        for q, gold in zip(queries, gold_entities):
            for e in gold:
                total += 1
                if sorted(self.retriever.locate(e)) == sorted(naive.locate(e)):
                    correct += 1
        return correct / max(total, 1)
