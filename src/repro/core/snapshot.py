"""Atomic bank/state snapshots — the crash-recovery half of the
fault-tolerant serving story.

A snapshot is one directory (``snap_<step>``) holding a ``.npy`` file per
array leaf plus a ``manifest.json`` naming them, written with the same
tmp-then-``os.rename`` discipline as ``repro.training.checkpoint``: a
crash (or an injected ``snapshot-write`` fault) at any point leaves at
worst a stale ``tmp.*`` directory — the previous snapshot stays intact
and ``latest_snapshot`` never sees a half-written one.

What gets captured (always as host numpy, ``jax.device_get``-gathered —
works unchanged for sharded global arrays):

* the **host bank** (:class:`FilterBank` or :class:`ShardedBank`) — the
  source of truth every restage rebuilds from;
* the **maintenance bookkeeping** (``row_alive``/``row_hash`` per
  engine) — ``MaintenanceEngine.__init__`` cannot reconstruct tombstoned
  rows from the slots alone, so without it a restored bank would
  resurrect dead CSR rows;
* optionally the **device state** (:class:`CFTDeviceState` or
  :class:`ShardedBankState`) leaf-for-leaf, so restore is bit-identical
  to what was serving at snapshot time (including temperature) rather
  than a re-staged approximation.

Restore is elastic the same way checkpoint restore is: a sharded state
re-lands on any mesh whose axis matches the saved shard count via
``device_put`` with explicit shardings, and :func:`merge_sharded_bank`
flattens a sharded bank so it can be re-``shard()``-ed onto a different
device count (placement-preserving: ``shard`` slices, never rebuilds).
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import get_registry
from . import hashing
from .bank import ColdTenant, FilterBank, ShardedBank
from .cuckoo import NULL
from .distributed import ShardedBankState, auto_axes
from .trag import CFTDeviceState

_SNAP_FMT = "snap_%08d"
_TMP_PREFIX = "tmp."
#: packed-arena leaves of a sharded state — placed P(axis, None); the
#: rest replicate
_PACKED_LEAVES = frozenset(("fingerprints", "temperature", "heads"))


def _jsonable(v):
    return v.item() if isinstance(v, np.generic) else v


def _bank_array_fields() -> List[str]:
    return [f.name for f in dataclasses.fields(FilterBank)
            if f.name not in ("num_trees", "slots", "build_stats")]


def _collect_bank(bank: FilterBank, prefix: str,
                  arrays: Dict[str, np.ndarray]) -> Dict:
    for name in _bank_array_fields():
        arrays[prefix + name] = np.asarray(getattr(bank, name))
    return {"num_trees": int(bank.num_trees), "slots": int(bank.slots),
            "build_stats": {k: _jsonable(v)
                            for k, v in bank.build_stats.items()}}


def _state_leaf_names(state) -> tuple:
    if isinstance(state, ShardedBankState):
        return ShardedBankState._LEAVES
    return tuple(f.name for f in dataclasses.fields(CFTDeviceState))


# ------------------------------------------------------------------ save

def save_snapshot(snap_dir: str, step: int, bank, state=None, maint=None,
                  extra: Optional[Dict] = None,
                  fault_hook: Optional[Callable[[str], None]] = None
                  ) -> str:
    """Write one atomic snapshot; returns the final directory path.

    ``fault_hook("snapshot-write")`` fires after every leaf and the
    manifest are on disk but *before* the rename — the injectable crash
    window that proves atomicity (the previous snapshot survives, the
    aborted tmp dir is swept).  A raise anywhere removes the tmp dir
    best-effort and propagates; the visible snapshot set is unchanged.
    """
    arrays: Dict[str, np.ndarray] = {}
    meta: Dict = {"extra": extra or {}}
    if isinstance(bank, ShardedBank):
        meta["kind"] = "sharded"
        meta["num_shards"] = bank.num_shards
        arrays["tree_starts"] = np.asarray(bank.tree_starts)
        meta["banks"] = [_collect_bank(b, f"bank{d}/", arrays)
                         for d, b in enumerate(bank.banks)]
    elif isinstance(bank, FilterBank):
        meta["kind"] = "flat"
        meta["banks"] = [_collect_bank(bank, "bank0/", arrays)]
    else:
        raise TypeError(f"cannot snapshot bank of type {type(bank)}")
    if state is not None:
        if isinstance(state, ShardedBankState):
            meta["state"] = {"layout": "sharded", "axis": state.axis,
                             "num_shards": state.num_shards}
        else:
            meta["state"] = {"layout": "replicated"}
        for n in _state_leaf_names(state):
            arrays[f"state/{n}"] = np.asarray(
                jax.device_get(getattr(state, n)))
    if maint is not None:
        engines = getattr(maint, "engines", None)
        if engines is None:
            engines = [maint]
        meta["maint_engines"] = len(engines)
        for d, e in enumerate(engines):
            arrays[f"maint{d}/row_alive"] = np.asarray(e.row_alive)
            arrays[f"maint{d}/row_hash"] = np.asarray(e.row_hash)

    os.makedirs(snap_dir, exist_ok=True)
    final = os.path.join(snap_dir, _SNAP_FMT % int(step))
    tmp = os.path.join(snap_dir, f"{_TMP_PREFIX}{int(step)}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        leaves = []
        for name, arr in arrays.items():
            fn = name.replace("/", "__") + ".npy"
            np.save(os.path.join(tmp, fn), np.ascontiguousarray(arr))
            leaves.append({"name": name, "file": fn,
                           "dtype": str(arr.dtype),
                           "shape": list(arr.shape)})
        manifest = {"step": int(step), "leaves": leaves, "meta": meta}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if fault_hook is not None:
            fault_hook("snapshot-write")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    get_registry().counter("snapshot.saved",
                           "bank/state snapshots written").inc()
    return final


def list_snapshots(snap_dir: str) -> List[int]:
    if not os.path.isdir(snap_dir):
        return []
    steps = []
    for d in os.listdir(snap_dir):
        if d.startswith("snap_"):
            try:
                steps.append(int(d.split("_", 1)[1]))
            except ValueError:
                pass
    return sorted(steps)


def latest_snapshot(snap_dir: str) -> Optional[int]:
    steps = list_snapshots(snap_dir)
    return steps[-1] if steps else None


_TENANT_PREFIX = "tenant_"


def save_tenant(snap_dir: str, cold: ColdTenant,
                fault_hook: Optional[Callable[[str], None]] = None
                ) -> str:
    """Persist one evicted/offboarded tenant's :class:`ColdTenant`
    atomically (same tmp-then-rename discipline as :func:`save_snapshot`,
    same ``snapshot-write`` fault window) — the durable half of
    offboarding: ``offboard_tenant`` → ``save_tenant`` now,
    ``load_tenant`` → ``onboard_tenant`` later, possibly in another
    process.  The ``tenant_<name>`` directory sits beside the ``snap_*``
    ones; :func:`list_snapshots` never confuses the two, and
    :func:`cleanup_snapshots`' tmp sweep covers crashed tenant writes
    too."""
    os.makedirs(snap_dir, exist_ok=True)
    final = os.path.join(snap_dir, _TENANT_PREFIX + cold.name)
    tmp = os.path.join(snap_dir, f"{_TMP_PREFIX}tenant.{cold.name}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        arrays = {"tree_nb": cold.tree_nb, "num_items": cold.num_items}
        arrays.update({f"tables/{k}": v for k, v in cold.tables.items()})
        leaves = []
        for name, arr in arrays.items():
            fn = name.replace("/", "__") + ".npy"
            np.save(os.path.join(tmp, fn), np.ascontiguousarray(arr))
            leaves.append({"name": name, "file": fn})
        manifest = {"tenant": cold.name, "lo": int(cold.lo),
                    "hi": int(cold.hi), "leaves": leaves}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if fault_hook is not None:
            fault_hook("snapshot-write")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    get_registry().counter("snapshot.tenants_saved",
                           "per-tenant cold snapshots written").inc(
                               tenant=cold.name)
    return final


def load_tenant(snap_dir: str, name: str) -> ColdTenant:
    """Load a :func:`save_tenant` snapshot back to a host
    :class:`ColdTenant`, ready for ``onboard_tenant`` /
    ``TenantRegistry.reload``."""
    path = os.path.join(snap_dir, _TENANT_PREFIX + name)
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    arrays = {l["name"]: np.load(os.path.join(path, l["file"]))
              for l in manifest["leaves"]}
    tables = {n.split("/", 1)[1]: a for n, a in arrays.items()
              if n.startswith("tables/")}
    return ColdTenant(name=manifest["tenant"], lo=int(manifest["lo"]),
                      hi=int(manifest["hi"]),
                      tree_nb=arrays["tree_nb"].astype(np.int32),
                      num_items=arrays["num_items"].astype(np.int32),
                      tables=tables)


def list_tenants(snap_dir: str) -> List[str]:
    """Names with a persisted :func:`save_tenant` snapshot."""
    if not os.path.isdir(snap_dir):
        return []
    return sorted(d[len(_TENANT_PREFIX):] for d in os.listdir(snap_dir)
                  if d.startswith(_TENANT_PREFIX))


def cleanup_snapshots(snap_dir: str, keep_last: int = 3) -> None:
    """Prune old snapshots and sweep stale ``tmp.*`` dirs left by a
    crashed (or fault-injected) write."""
    steps = list_snapshots(snap_dir)
    drop = steps[:-keep_last] if keep_last > 0 else steps
    for s in drop:
        shutil.rmtree(os.path.join(snap_dir, _SNAP_FMT % s),
                      ignore_errors=True)
    if os.path.isdir(snap_dir):
        for d in os.listdir(snap_dir):
            if d.startswith(_TMP_PREFIX):
                shutil.rmtree(os.path.join(snap_dir, d),
                              ignore_errors=True)


# --------------------------------------------------------------- restore

@dataclasses.dataclass
class RestoredSnapshot:
    """Host-side view of one snapshot: the restored bank, the per-engine
    maintenance bookkeeping, and the raw device-state leaves (rebuilt
    into a device state by :func:`restore_state`)."""
    step: int
    path: str
    bank: object                       # FilterBank | ShardedBank
    row_alive: List[np.ndarray]
    row_hash: List[np.ndarray]
    state_leaves: Dict[str, np.ndarray]
    state_meta: Dict
    meta: Dict


def restore_snapshot(snap_dir: str,
                     step: Optional[int] = None) -> RestoredSnapshot:
    """Load a snapshot (latest by default) back to host numpy."""
    if step is None:
        step = latest_snapshot(snap_dir)
        if step is None:
            raise FileNotFoundError(f"no snapshots under {snap_dir}")
    path = os.path.join(snap_dir, _SNAP_FMT % int(step))
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    arrays = {l["name"]: np.load(os.path.join(path, l["file"]))
              for l in manifest["leaves"]}
    meta = manifest["meta"]
    field_names = _bank_array_fields()
    banks = []
    for d, aux in enumerate(meta["banks"]):
        kw = {n: arrays[f"bank{d}/{n}"] for n in field_names}
        banks.append(FilterBank(num_trees=int(aux["num_trees"]),
                                slots=int(aux["slots"]),
                                build_stats=dict(aux["build_stats"]), **kw))
    if meta["kind"] == "sharded":
        bank: object = ShardedBank(tree_starts=arrays["tree_starts"],
                                   banks=banks)
    else:
        bank = banks[0]
    n_eng = int(meta.get("maint_engines", 0))
    return RestoredSnapshot(
        step=int(manifest["step"]), path=path, bank=bank,
        row_alive=[arrays[f"maint{d}/row_alive"] for d in range(n_eng)],
        row_hash=[arrays[f"maint{d}/row_hash"] for d in range(n_eng)],
        state_leaves={n.split("/", 1)[1]: a for n, a in arrays.items()
                      if n.startswith("state/")},
        state_meta=meta.get("state", {}), meta=meta)


def restore_state(snap: RestoredSnapshot, mesh=None,
                  axis: Optional[str] = None):
    """Rebuild the snapshot's device state bit-identically.

    Replicated snapshots land as a fresh :class:`CFTDeviceState`.
    Sharded snapshots need a mesh whose ``axis`` size equals the saved
    shard count; leaves re-land via ``device_put`` with explicit
    shardings (the checkpoint-restore elastic pattern — any mesh of the
    right axis size works, not just the one that wrote the snapshot).
    For a *different* shard count, restage from the bank instead:
    ``merge_sharded_bank(snap.bank).shard(D')``.
    """
    if not snap.state_meta:
        raise ValueError("snapshot carries no device state")
    if snap.state_meta["layout"] == "replicated":
        # copy: the leaves stay visible on the RestoredSnapshot, and a
        # zero-copy wrap would alias them into the serving state
        return CFTDeviceState(**{n: jnp.array(a, copy=True)
                                 for n, a in snap.state_leaves.items()})
    axis = axis or snap.state_meta["axis"]
    if mesh is None:
        raise ValueError("restoring a sharded state needs a mesh")
    mesh = auto_axes(mesh)
    d = int(mesh.shape[axis])
    if d != int(snap.state_meta["num_shards"]):
        raise ValueError(
            f"mesh axis {axis!r} has {d} devices but the snapshot was "
            f"taken over {snap.state_meta['num_shards']} shards; "
            f"re-shard elastically from the bank instead "
            f"(merge_sharded_bank(snap.bank).shard({d}))")
    blk = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    leaves = {n: jax.device_put(jnp.asarray(a),
                                blk if n in _PACKED_LEAVES else rep)
              for n, a in snap.state_leaves.items()}
    return ShardedBankState(**leaves, mesh=mesh, axis=axis)


def apply_maint_bookkeeping(maint, snap: RestoredSnapshot) -> None:
    """Overwrite a freshly constructed maintenance engine's liveness
    bookkeeping with the snapshot's — required after restore because
    ``__init__`` marks every CSR row alive (it cannot see tombstones)."""
    engines = getattr(maint, "engines", None)
    if engines is None:
        engines = [maint]
    if len(engines) != len(snap.row_alive):
        raise ValueError(f"snapshot has bookkeeping for "
                         f"{len(snap.row_alive)} engines, got "
                         f"{len(engines)}")
    for e, alive, hs in zip(engines, snap.row_alive, snap.row_hash):
        if alive.shape[0] != e.bank.num_rows:
            raise ValueError("bookkeeping row count does not match bank")
        e.row_alive = alive.astype(bool).copy()
        e.row_hash = hs.astype(np.uint32).copy()


def merge_sharded_bank(sbank: ShardedBank) -> FilterBank:
    """Flatten a sharded bank back to one global :class:`FilterBank` —
    the elastic re-shard path (``merge(...).shard(D')`` moves a snapshot
    between device counts).  The exact inverse of ``FilterBank.shard``:
    arenas concatenate with offset shifts, local CSR row ids lift to the
    canonical merged (shard-major) numbering, slot placement is copied
    byte-for-byte — so a restage of the merged bank answers identically
    to the sharded original.
    """
    banks = sbank.banks
    abase = np.cumsum([0] + [b.total_buckets for b in banks])
    rbase = np.cumsum([0] + [b.num_rows for b in banks])
    bucket_offsets = np.concatenate(
        [b.bucket_offsets[:-1].astype(np.int64) + abase[d]
         for d, b in enumerate(banks)]
        + [np.asarray([abase[-1]], np.int64)])
    heads = np.concatenate(
        [np.where(b.fingerprints != hashing.EMPTY_FP,
                  b.heads + np.int32(rbase[d]),
                  NULL).astype(np.int32) for d, b in enumerate(banks)])
    off = np.zeros(int(rbase[-1]) + 1, np.int32)
    pos = 1
    for b in banks:
        lens = np.diff(b.csr_offsets.astype(np.int64))
        off[pos:pos + lens.size] = lens
        pos += lens.size
    np.cumsum(off, out=off)
    return FilterBank(
        num_trees=sbank.num_trees,
        tree_nb=np.concatenate([b.tree_nb for b in banks]),
        bucket_offsets=bucket_offsets,
        slots=sbank.slots,
        fingerprints=np.concatenate([b.fingerprints for b in banks]),
        temperature=np.concatenate([b.temperature for b in banks]),
        heads=heads,
        entity_ids=np.concatenate([b.entity_ids for b in banks]),
        stored_hash=np.concatenate([b.stored_hash for b in banks]),
        csr_offsets=off,
        csr_nodes=np.concatenate(
            [b.csr_nodes for b in banks]).astype(np.int32),
        row_tree=np.concatenate(
            [b.row_tree + np.int32(sbank.tree_starts[d])
             for d, b in enumerate(banks)]).astype(np.int32),
        row_entity=np.concatenate([b.row_entity for b in banks]),
        num_items=np.concatenate([b.num_items for b in banks]),
        build_stats=dict(banks[0].build_stats))


# ---------------------------------------------------------------- writer

class SnapshotWriter:
    """Commit-driven snapshot cadence for a serving session.

    ``note_commit(state, maint)`` is called by the session after every
    *applied* maintenance commit — the one moment bank and device state
    are guaranteed in sync, so a restore that rebuilds the maintenance
    engine over the restored bank starts from a consistent shadow.
    Every ``every``-th commit writes a snapshot and prunes to
    ``keep_last``.  Writes are synchronous (host copies + ``.npy``
    writes) but a write *failure* never propagates into serving: it is
    counted (``snapshot.failures``), latched on ``last_error``, and the
    commit that triggered it still stands.
    """

    def __init__(self, snap_dir: str, every: int = 1, keep_last: int = 3,
                 fault_hook: Optional[Callable[[str], None]] = None):
        if every < 1:
            raise ValueError("snapshot cadence must be >= 1 commit")
        self.snap_dir = snap_dir
        self.every = every
        self.keep_last = keep_last
        self._fault = fault_hook
        self.commits = 0
        self.saved = 0
        self.last_path: Optional[str] = None
        self.last_error: Optional[BaseException] = None
        m = get_registry()
        self._c_failures = m.counter(
            "snapshot.failures", "snapshot writes that raised (by error)")

    def note_commit(self, state, maint) -> Optional[str]:
        self.commits += 1
        if self.commits % self.every:
            return None
        bank = getattr(maint, "sbank", None)
        if bank is None:
            bank = maint.bank
        try:
            path = save_snapshot(self.snap_dir, self.commits, bank,
                                 state=state, maint=maint,
                                 fault_hook=self._fault)
        except Exception as exc:      # serving must outlive a bad disk
            self.last_error = exc
            self._c_failures.inc(error=type(exc).__name__)
            return None
        self.saved += 1
        self.last_path = path
        if self.keep_last:
            cleanup_snapshots(self.snap_dir, self.keep_last)
        return path
