"""Serving engine: jitted prefill + decode loop with a continuous-lite
batch scheduler.

An answer is two device programs: the prefill, which ends in the first
greedy token, and the decode loop, which runs every further step of the
answer on the device (``lax.fori_loop`` over ``lm.decode_step`` and the
argmax) and writes each token into a device buffer.  The loop donates
the cache/state buffers (no double-buffered KV) and its trip count is a
traced scalar, so it compiles once per batch geometry whatever the
answer length.  The scheduler packs pending requests into fixed-size
batches (padding short prompts) — the "continuous-lite" policy: new
requests join at the next batch boundary rather than mid-flight, which
keeps both programs shape-stable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core import (CFTDeviceState, DeviceRetrieval, MaintenanceEngine,
                    MaintenanceReport, ShardedBankState, retrieve_device,
                    sharded_retrieve_device)
from ..core.maintenance import RestageCoordinator
from ..data.tokenizer import HashTokenizer
from ..models import lm
from ..models.attention import stacked_width
from ..obs import RecompileSentinel, Tracer, get_registry, state_shapes


@dataclasses.dataclass
class Request:
    prompt_ids: List[int]
    max_new_tokens: int = 16
    out_ids: Optional[List[int]] = None


class RetrievalSession:
    """The enqueue-able retrieval unit behind every serving front end.

    Owns a bank-axis device state, the jitted lookup step, the padding
    policy, temperature threading, and the two-phase maintenance
    lifecycle.  ``ServeEngine`` composes one (synchronous batches),
    ``AsyncServeEngine`` schedules one (continuous batching), and
    ``RAGPipeline``'s device path delegates to one — so the state-swap /
    harvest / restage invariants live in exactly one place.

    The hot path splits into dispatch and harvest so a scheduler can
    overlap host work with the in-flight device batch:

    * :meth:`pad_queries` — shape-stable padding (fixed multiple for the
      sync engine, pow2 buckets for the async one);
    * :meth:`retrieve_dispatch` — run the jitted step and thread the
      temperature state *without* forcing a device sync;
    * :meth:`harvest` — best-effort absorb of device temperature into
      the host bank (skipped while a restage plan is pending).
    """

    def __init__(self):
        self.state = None                      # CFTDeviceState | Sharded
        self.maint: Optional[MaintenanceEngine] = None
        self.coord: Optional[RestageCoordinator] = None
        self.snapshots = None                  # Optional[SnapshotWriter]
        self.tenants = None                    # Optional[TenantRegistry]
        self.batch_pad = 64
        self.fused = False
        self._step = None
        self._watched_step = None
        self._attach_args = (None, 4, 3)
        # observability: process-wide registry, per-session tracer and
        # recompile sentinel (the PR 6 shape-instability tripwire)
        self.metrics = get_registry()
        self.tracer = Tracer(self.metrics)
        self.sentinel = RecompileSentinel(self.metrics)

    # ------------------------------------------------------------ attach
    def attach(self, state, lookup_fn=None, max_locs: int = 4, n: int = 3,
               batch_pad: int = 64, fused: bool = False) -> None:
        """Point the session at a device state: one jitted step over the
        bank-axis layout, shape-stable via the padding policy.

        ``state`` is either a replicated :class:`CFTDeviceState` or a
        bank-axis :class:`ShardedBankState` — the sharded step routes each
        query batch to the owning shards with an all-to-all instead of
        probing a replicated bank; everything downstream (padding policy,
        temperature threading, maintenance harvest) is identical.

        ``fused=True`` serves through the single-pass
        :mod:`repro.kernels.fused_retrieve` kernel (probe + bump + CSR
        window + hierarchy walks in one launch; owner-shard fusion on the
        sharded layout).  Mutually exclusive with ``lookup_fn`` — the
        fused kernel *is* the probe.  Flip at runtime with
        :meth:`set_fused`.
        """
        if fused and lookup_fn is not None:
            raise ValueError("fused=True embeds the probe; lookup_fn "
                             "cannot be combined with it")
        self.state = state
        self.batch_pad = batch_pad
        self.fused = bool(fused)
        self._attach_args = (lookup_fn, max_locs, n)
        self._build_step()

    def _build_step(self) -> None:
        lookup_fn, max_locs, n = self._attach_args
        if isinstance(self.state, ShardedBankState):
            # already jitted; mesh/axis ride in the state's static aux
            self._step = functools.partial(
                sharded_retrieve_device, max_locs=max_locs, n=n,
                lookup_fn=lookup_fn, fused=self.fused)
            from ..core.distributed import _sharded_retrieve_jit
            self._watched_step = _sharded_retrieve_jit
        elif self.fused:
            # the fused entry picks its launch plan outside any trace, so
            # the jit boundary is the kernel ops wrapper
            from ..kernels.fused_retrieve import (fused_retrieve_state_auto,
                                                  ops as _fops)
            self._step = functools.partial(fused_retrieve_state_auto,
                                           max_locs=max_locs, n=n)
            self._watched_step = _fops.fused_retrieve_ragged
        else:
            self._step = jax.jit(functools.partial(
                retrieve_device, max_locs=max_locs, n=n,
                lookup_fn=lookup_fn))
            self._watched_step = self._step
        self.sentinel.watch("serve.step", self._watched_step)

    def set_fused(self, on: bool) -> None:
        """Flip the attached step between the fused single-pass kernel
        and the unfused oracle path at runtime.  The new step compiles
        its geometries once — an expected, intentional event — so the
        recompile sentinel forgives exactly one cache growth
        (:meth:`RecompileSentinel.allow_next`), keeping armed tripwires
        quiet for the flip itself but live for anything after it."""
        if self.state is None:
            raise RuntimeError("attach a retrieval state first")
        if bool(on) == self.fused:
            return
        lookup_fn, _, _ = self._attach_args
        if on and lookup_fn is not None:
            raise ValueError("fused=True embeds the probe; lookup_fn "
                             "cannot be combined with it")
        self.fused = bool(on)
        self._build_step()
        self.sentinel.allow_next()

    def attach_maintenance(self, maint, forest, breaker=None,
                           registry=None) -> None:
        """Attach a host-side maintenance engine over the bank backing
        the attached state — which must have just been staged from that
        bank (the engine's restage shadow initializes to its content).
        ``breaker`` overrides the coordinator's fault-domain circuit
        breaker (tests pass one with a tight threshold/cooldown);
        ``registry`` (a :class:`~repro.core.bank.TenantRegistry`) makes
        the fault domain per-tenant — see :meth:`attach_tenants`.  The
        fault-injection hook is wired here so ``repro.core`` never
        imports the serving layer."""
        from .faultinject import fault_point
        self.maint = maint
        self.coord = RestageCoordinator(maint, forest, breaker=breaker,
                                        fault_hook=fault_point,
                                        registry=registry)
        if registry is not None:
            self.tenants = registry

    def attach_tenants(self, registry) -> None:
        """Attach (or swap in) a :class:`~repro.core.bank.TenantRegistry`
        over the already-attached bank: tenant quotas, per-tenant
        maintenance fault domains, and the evict/reload/onboard lifecycle
        all key off it."""
        self.tenants = registry
        if self.coord is not None:
            self.coord.registry = registry

    def configure_snapshots(self, writer) -> None:
        """Attach a :class:`repro.core.snapshot.SnapshotWriter`: every
        applied maintenance commit ticks it, so snapshots land exactly
        when bank and device state are in sync."""
        self.snapshots = writer

    # ---------------------------------------------------------- hot path
    def pad_queries(self, tree_ids: Sequence[int], hashes: Sequence[int],
                    pad_to: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array, int]:
        """Pad a query batch to a shape-stable geometry; returns
        ``(hashes, tree_ids, true_length)``.  Default policy rounds up to
        a multiple of ``batch_pad``; a caller-picked ``pad_to`` (the
        async engine's pow2 buckets) overrides it.  Pad slots query tree
        0 with hash 0; a pad hash can in principle alias a stored
        fingerprint, which only over-bumps that slot's temperature — a
        heuristic, not a correctness input."""
        b = len(hashes)
        bp = pad_to if pad_to is not None else \
            max(self.batch_pad, -(-b // self.batch_pad) * self.batch_pad)
        if bp < b:
            raise ValueError(f"pad_to {bp} < batch {b}")
        tid = np.zeros((bp,), np.int32)
        tid[:b] = np.asarray(tree_ids, np.int32)
        hh = np.zeros((bp,), np.uint32)
        hh[:b] = np.asarray(hashes, np.uint32)
        return jnp.asarray(hh), jnp.asarray(tid), b

    def retrieve_dispatch(self, hh: jax.Array, tid: jax.Array):
        """Dispatch one already-padded retrieval step and thread the
        bumped temperature into the live state.  Returns the raw padded
        result *without* blocking — the arrays are in flight, so host
        maintenance can run under the batch before the caller touches
        them."""
        if self.state is None:
            raise RuntimeError("attach a retrieval state first")
        out = self._step(self.state, hh, tid)
        self.state = self.state.with_temperature(out.temperature)
        return out

    def harvest(self) -> int:
        """Close the paper's feedback loop: absorb this batch's bumps
        into the host bank (drives the idle-sort trigger policy).  While
        a restage is staged-but-uncommitted — or a background prepare
        holds the lifecycle lock — the harvest is skipped; bumps stay on
        device and the first post-commit batch harvests them."""
        if self.coord is None:
            return 0
        return self.coord.absorb(self.state)

    def retrieve(self, tree_ids: Sequence[int],
                 hashes: Sequence[int]) -> DeviceRetrieval:
        """Serve one ``(tree_id, hash)`` query batch synchronously: pad,
        dispatch, harvest, slice back to the true batch."""
        with self.tracer.span("serve.retrieve",
                              queries=len(hashes)) as sp:
            with sp.stage("pad"):
                hh, tid, b = self.pad_queries(tree_ids, hashes)
            with sp.stage("dispatch"):
                out = self.retrieve_dispatch(hh, tid)
            with sp.stage("harvest"):
                self.harvest()
        return DeviceRetrieval(hit=out.hit[:b], locations=out.locations[:b],
                               up=out.up[:b], down=out.down[:b],
                               temperature=out.temperature)

    def compile_cache_size(self) -> int:
        """Number of compiled geometries the jitted step holds (-1 when
        the backend does not expose it) — the async tests pin this to the
        bucket count to prove the hot path never recompiles.  Refreshes
        the ``serve.compile_cache_size`` gauge as a side effect."""
        size = getattr(self._watched_step, "_cache_size", None)
        n = int(size()) if callable(size) else -1
        self.metrics.gauge("serve.compile_cache_size",
                           "compiled geometries held by the serve step"
                           ).set(n)
        return n

    def observe(self) -> dict:
        """Post-batch observability tick: refresh the compile-cache
        gauge and let the sentinel attribute any new hot-path
        compilations (raising when armed).  Cheap — two cache-size
        reads — so schedulers call it every batch."""
        self.compile_cache_size()
        return self.sentinel.check()

    # -------------------------------------------------------- maintenance
    def prepare_maintenance(self, state=None, now=None,
                            force: bool = False
                            ) -> Optional[MaintenanceReport]:
        """Phase one of the zero-pause restage: run the host-side
        maintenance pass (absorb → delta → compact → shrink → sort) and
        stage the restage plan's payload — only the changed bytes.

        Everything here is host work plus async device_put dispatch, so
        it overlaps with an in-flight serve batch: issue the next batch,
        call this, then :meth:`commit_maintenance` once the batch is
        consumed.  The old state keeps serving untouched until commit.
        An uncommitted previous plan is committed first (plans do not
        stack).  ``state`` overrides the absorb target — a scheduler
        passes the pre-dispatch snapshot so the pass never blocks on the
        in-flight batch's temperature."""
        if self.maint is None:
            return None
        self.commit_maintenance()
        return self.coord.prepare(self.state if state is None else state,
                                  now=now, force=force)

    def commit_maintenance(self, blocking: bool = True,
                           now: Optional[float] = None) -> bool:
        """Phase two: the O(changed-bytes) device splice + atomic state
        swap.  Returns True when a staged plan was applied.  The splice
        donates the old state's arena buffers — the swapped-out state must
        not be probed again (on backends without donation this is merely
        a copy).  A splice failure quarantines the plan and re-raises;
        ``self.state`` is untouched (the fault fires before donation), so
        the session keeps serving the last committed content."""
        if self.coord is None:
            return False
        pending = self.coord.pending
        kind = getattr(pending, "kind", None)
        before = state_shapes(self.state) if pending is not None else None
        self.state, applied = self.coord.commit(self.state,
                                                blocking=blocking, now=now)
        if applied and before is not None:
            # shape-stability tripwire: a delta/none commit must never
            # change a committed array shape (PR 6's recompile bug)
            self.sentinel.note_commit(kind, before,
                                      state_shapes(self.state))
        if applied and self.snapshots is not None:
            # bank == device right here; the writer decides cadence and
            # swallows write failures (serving outlives a bad disk)
            self.snapshots.note_commit(self.state, self.maint)
        return applied

    def maintain(self) -> Optional[MaintenanceReport]:
        """Idle-time maintenance hook (between serving batches) — the
        single-call wrapper over :meth:`prepare_maintenance` +
        :meth:`commit_maintenance`.

        With a maintenance engine attached: one ``maintain`` pass on the
        host bank, then splice-commit the changed bytes into the device
        state (host stays the source of truth so slot layouts never
        diverge; a compaction falls back to the full restage).  Without
        one: a pure device-side idle sort (``sort_buckets_arena``) — hot
        fingerprints bubble to slot 0 using temperature alone."""
        if self.maint is not None:
            report = self.prepare_maintenance()
            self.commit_maintenance()
            return report
        if self.state is not None:
            self.state = self.state.sort_idle()
        return None

    def pending_mutations(self) -> int:
        """Queued-but-unapplied insert/delete count across the attached
        engine('s shards) — the async scheduler's prepare trigger."""
        if self.maint is None:
            return 0
        engines = getattr(self.maint, "engines", None)
        if engines is None:
            engines = [self.maint]
        return sum(len(e.delta) for e in engines)

    # ----------------------------------------------- tenant lifecycle
    def _tenant_registry(self):
        if self.tenants is None:
            raise RuntimeError("attach a TenantRegistry first "
                               "(attach_tenants)")
        if self.maint is None:
            raise RuntimeError("tenant lifecycle needs an attached "
                               "maintenance engine")
        return self.tenants

    def _host_bank(self):
        """The host bank the registry operates on — the sharded bank for
        a sharded engine, the flat one otherwise."""
        sb = getattr(self.maint, "sbank", None)
        return sb if sb is not None else self.maint.bank

    def _tenant_restage(self, lo: int, hi: int, pinned: bool) -> None:
        """Finish a registry surgery: set the tenant's pin state, then
        force a prepare/commit cycle so the surgically edited bank
        restages onto device (``force`` because the bank's arena geometry
        already disagrees with the device's — a plain absorb would
        raise)."""
        self.maint.pin_tree_range(lo, hi, pinned)
        self.prepare_maintenance(force=True)
        self.commit_maintenance()

    def evict_tenant(self, name: str):
        """Evict ``name`` to host under arena memory pressure: flush the
        pending maintenance cycle (bank == device), copy the tenant's
        arena rows into a :class:`~repro.core.bank.ColdTenant`, blank its
        tree range in place, pin it (cold rows reference live CSR ids —
        compaction/rebuild must not renumber them), and splice the
        blanked segments onto device.  Queries against its trees miss
        safely; the admission path sheds them with
        :class:`~repro.serving.errors.TenantEvicted` instead.  The
        ``evict`` fault site fires before the surgery — an injected
        fault leaves bank and device exactly as served."""
        from .faultinject import fault_point
        reg = self._tenant_registry()
        self.maintain()                    # bank == device for the copy
        fault_point("evict")
        cold = reg.evict(self._host_bank(), name)
        self._tenant_restage(cold.lo, cold.hi, pinned=True)
        self.metrics.counter(
            "tenant.evictions",
            "cold-tenant evictions to host").inc(tenant=name)
        return cold

    def reload_tenant(self, name: str, cold=None) -> None:
        """Splice an evicted tenant back in — the exact inverse of
        :meth:`evict_tenant`, bit-exact because eviction never mutates
        the cold copy or its CSR rows (the pin guarantees the ids still
        resolve).  ``cold`` overrides the registry's retained copy (the
        snapshot-restore path)."""
        from .faultinject import fault_point
        reg = self._tenant_registry()
        self.maintain()
        fault_point("reload")
        reg.reload(self._host_bank(), name, cold)
        lo, hi = reg.trees(name)
        self._tenant_restage(lo, hi, pinned=False)
        self.metrics.counter(
            "tenant.reloads",
            "cold-tenant reloads from host").inc(tenant=name)

    def offboard_tenant(self, name: str):
        """Live offboarding: evict ``name`` and drop it from the
        registry's residency — its trees stay as pinned empty segments
        (the range is reusable via :meth:`onboard_tenant`).  Returns the
        :class:`ColdTenant` so the caller can persist it
        (``save_tenant``)."""
        from .faultinject import fault_point
        reg = self._tenant_registry()
        self.maintain()
        fault_point("evict")
        cold = reg.offboard(self._host_bank(), name)
        self._tenant_restage(cold.lo, cold.hi, pinned=True)
        self.metrics.counter(
            "tenant.offboards", "tenants offboarded live").inc(tenant=name)
        return cold

    def onboard_tenant(self, name: str, cold) -> None:
        """Live onboarding into an offboarded range: splice ``cold``'s
        trees (typically from :func:`~repro.core.snapshot.load_tenant`)
        into the blank range and restage — no restart, no full
        rebuild."""
        from .faultinject import fault_point
        reg = self._tenant_registry()
        self.maintain()
        fault_point("onboard")
        reg.onboard(self._host_bank(), name, cold)
        lo, hi = reg.trees(name)
        self._tenant_restage(lo, hi, pinned=False)
        self.metrics.counter(
            "tenant.onboards", "tenants onboarded live").inc(tenant=name)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, cache_size: int = 512,
                 batch_size: int = 4):
        self.cfg = cfg
        self.params = params
        self.cache_size = cache_size
        self.batch_size = batch_size

        self._prefill = jax.jit(
            functools.partial(_prefill_token, cfg, cache_size))
        self._decode = jax.jit(
            functools.partial(_decode_loop, cfg, cache_size),
            donate_argnums=(2,))
        self.retrieval = RetrievalSession()
        self.tracer = Tracer()
        self._c_dispatches = self.tracer.registry.counter(
            "serve.decode_dispatches",
            "decode programs launched: one an answer of two or more tokens")
        gauge = get_registry().gauge(
            "serve.state_bytes", "one sequence's decode state at the cache")
        for kind, n in decode_state_bytes(cfg, cache_size).items():
            gauge.set(n, kind=kind)

    # engine-internal views of the session (kept for callers that poke
    # the state directly, e.g. the benches' equivalence gates)
    @property
    def _ret_state(self):
        return self.retrieval.state

    @property
    def _maint(self):
        return self.retrieval.maint

    @property
    def _coord(self):
        return self.retrieval.coord

    # ---------------------------------------------------------- retrieval
    def attach_retrieval(self, state, lookup_fn=None,
                         max_locs: int = 4, n: int = 3,
                         batch_pad: int = 64, fused: bool = False) -> None:
        """Fuse CFT retrieval into the engine — see
        :meth:`RetrievalSession.attach`."""
        self.retrieval.attach(state, lookup_fn=lookup_fn,
                              max_locs=max_locs, n=n, batch_pad=batch_pad,
                              fused=fused)

    def retrieve(self, tree_ids: Sequence[int],
                 hashes: Sequence[int]) -> DeviceRetrieval:
        """Serve one ``(tree_id, hash)`` query batch (padded to a
        multiple of ``batch_pad`` — one compilation per geometry, like
        the token scheduler)."""
        return self.retrieval.retrieve(tree_ids, hashes)

    # -------------------------------------------------------- maintenance
    def attach_maintenance(self, maint, forest) -> None:
        """Attach a host-side maintenance engine (``MaintenanceEngine`` or
        ``ShardedMaintenanceEngine``) over the bank backing the attached
        retrieval state — which must have just been staged from that bank
        (the engine's restage shadow is initialized to its content).
        ``retrieve`` then harvests temperature after every query batch,
        and :meth:`maintain` (called between batches, or by ``serve``
        automatically) applies queued insert/delete deltas, compacts,
        resorts, and splice-commits the device state whenever the bank
        mutated."""
        self.retrieval.attach_maintenance(maint, forest)

    def prepare_maintenance(self) -> Optional[MaintenanceReport]:
        """Phase one of the zero-pause restage (host maintenance pass +
        payload staging, overlappable with an in-flight batch) — see
        :meth:`RetrievalSession.prepare_maintenance`."""
        return self.retrieval.prepare_maintenance()

    def commit_maintenance(self) -> bool:
        """Phase two: O(changed-bytes) splice + atomic swap — see
        :meth:`RetrievalSession.commit_maintenance`."""
        return self.retrieval.commit_maintenance()

    def maintain(self) -> Optional[MaintenanceReport]:
        """Idle-time maintenance hook (between serving batches) — see
        :meth:`RetrievalSession.maintain`."""
        return self.retrieval.maintain()

    # ----------------------------------------------------------- generate
    def generate(self, batch: Dict[str, jax.Array], max_new_tokens: int
                 ) -> np.ndarray:
        """Greedy generation. batch['tokens']: (B, S) prompt ids.

        Two dispatches an answer: the prefill program returns the first
        token, which the host copies; then, for ``max_new_tokens > 1``,
        one decode program runs the other ``max_new_tokens - 1`` steps
        on the device and the host copies their tokens once."""
        n_steps = max_new_tokens - 1
        if n_steps > self.cache_size:
            raise ValueError(f"{max_new_tokens} new tokens do not fit a "
                             f"cache of {self.cache_size}")
        with self.tracer.span("serve.generate", steps=max_new_tokens,
                              dispatches=int(n_steps > 0)) as sp:
            with sp.stage("prefill"):          # to the first token on host
                tok, state = self._prefill(self.params, batch)
                out = [np.asarray(tok)]
            with sp.stage("decode"):           # one program, one copy
                if n_steps > 0:
                    toks, _ = self._decode(self.params, tok, state,
                                           np.int32(n_steps))
                    out.append(np.asarray(toks)[:, :n_steps])
                    self._c_dispatches.inc()
        return np.concatenate(out, axis=1)            # (B, new)

    # ---------------------------------------------------------- scheduler
    def serve(self, requests: Sequence[Request]) -> List[Request]:
        """Continuous-lite: group requests into fixed batches, pad, run."""
        pending = list(requests)
        done: List[Request] = []
        while pending:
            group = pending[:self.batch_size]
            pending = pending[self.batch_size:]
            max_new = max(r.max_new_tokens for r in group)
            # context-window truncation: keep the prompt tail (query end)
            budget = self.cache_size - max_new
            for r in group:
                if len(r.prompt_ids) > budget:
                    r.prompt_ids = r.prompt_ids[-budget:]
            max_len = max(len(r.prompt_ids) for r in group)
            toks = np.full((self.batch_size, max_len), HashTokenizer.PAD,
                           np.int32)
            for i, r in enumerate(group):     # left-pad to align last token
                toks[i, max_len - len(r.prompt_ids):] = r.prompt_ids
            out = self.generate({"tokens": jnp.asarray(toks)}, max_new)
            for i, r in enumerate(group):
                r.out_ids = out[i, :r.max_new_tokens].tolist()
                done.append(r)
            if self._maint is not None:
                self.maintain()    # idle window between batches: apply
                #                    pending deltas, resort, restage
        return done


def _prefill_token(cfg: ModelConfig, cache_size: int, params,
                   batch: Dict[str, jax.Array]):
    """The prompt's forward pass to its first greedy token and the
    decode state."""
    logits, state = lm.prefill(cfg, params, batch, cache_size)
    return lm.greedy_token(logits), state


def _decode_loop(cfg: ModelConfig, cache_size: int, params, tok: jax.Array,
                 state, n_steps: jax.Array):
    """``n_steps`` greedy decode steps from ``tok`` (B, 1), on the device:
    step ``i``'s token lands in column ``i`` of a (B, cache_size) int32
    buffer.  Returns the buffer and the final state (which aliases the
    donated one)."""
    def step(i, carry):
        tok, state, toks = carry
        logits, state = lm.decode_step(cfg, params, tok, state)
        tok = lm.greedy_token(logits)
        return tok, state, jax.lax.dynamic_update_slice(toks, tok, (0, i))

    toks = jnp.zeros((tok.shape[0], cache_size), jnp.int32)
    _, state, toks = jax.lax.fori_loop(0, n_steps, step, (tok, state, toks))
    return toks, state


def kv_cache_bytes(cfg: ModelConfig, batch: int, cache_size: int) -> int:
    """Sizing helper (used by roofline + admission control): the decode
    state's bytes.  The hybrid holds a KV cache per shared-block call,
    its heads padded to whole 128-lane rows as the TPU holds them
    (``attention.stacked_width``), and conv and SSM state per layer."""
    hd = cfg.resolved_head_dim
    bpe = 2 if cfg.dtype == "bfloat16" else 4
    if cfg.family == "rwkv":
        return cfg.n_layers * batch * cfg.n_heads * hd * hd * 4
    if cfg.family == "mamba_hybrid":
        conv = (cfg.ssm_conv - 1) * (cfg.d_inner + 2 * cfg.ssm_groups
                                     * cfg.ssm_state) * bpe
        ssm = cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4
        kv = 2 * len(cfg.hybrid_layers) * cfg.n_kv_heads * cache_size \
            * stacked_width(hd) * bpe
        return batch * (kv + cfg.n_layers * (conv + ssm))
    return 2 * cfg.n_layers * batch * cfg.n_kv_heads * cache_size * hd * bpe


# decode-state leaves by kind: attention caches, recurrent state, and the
# short input windows of convolutions and token shifts
STATE_KINDS = {"k": "kv", "v": "kv", "ssm": "ssm", "wkv": "ssm",
               "conv": "conv", "time_x": "conv", "chan_x": "conv"}


def decode_state_bytes(cfg: ModelConfig, cache_size: int) -> Dict[str, int]:
    """Bytes of one sequence's decode state by kind (``kv``, ``ssm``,
    ``conv``), from the shapes ``lm.init_decode_state`` gives: nothing
    is allocated."""
    if cfg.family == "encdec":                 # its state needs the audio
        return {}
    shapes = jax.eval_shape(
        lambda: lm.init_decode_state(cfg, None, 1, cache_size))
    out: Dict[str, int] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        kind = STATE_KINDS.get(getattr(path[-1], "key", None))
        if kind:
            out[kind] = out.get(kind, 0) + leaf.size * leaf.dtype.itemsize
    return out
