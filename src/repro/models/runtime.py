"""Ambient distribution context for model code.

Drivers (dryrun / train / serve) set the mesh + axis roles once; model
modules that need explicit shard_map regions (the MoE expert-parallel
block) read it here.  When unset (CPU tests, single device), models take
their plain single-device paths.
"""
from __future__ import annotations

from typing import Optional, Tuple

_MESH = None
_DP_AXES: Tuple[str, ...] = ("data",)
_TP_AXIS: str = "model"


def set_mesh(mesh, dp_axes: Tuple[str, ...] = ("data",),
             tp_axis: str = "model") -> None:
    global _MESH, _DP_AXES, _TP_AXIS
    from ..core.distributed import auto_axes
    _MESH = auto_axes(mesh)
    _DP_AXES = tuple(dp_axes)
    _TP_AXIS = tp_axis


def clear_mesh() -> None:
    global _MESH
    _MESH = None


def get_mesh():
    return _MESH


def dp_axes() -> Tuple[str, ...]:
    return _DP_AXES


def tp_axis() -> str:
    return _TP_AXIS


def constrain_batch(x):
    """Pin dim-0 (batch) to the data axes at layer boundaries.

    GSPMD occasionally drifts into batch replication inside scanned layer
    bodies (observed on rwkv/zamba: every device computing all 16 samples);
    a with_sharding_constraint at the residual stream stops the drift."""
    if _MESH is None:
        return x
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(_DP_AXES, *(None,) * (x.ndim - 1)))


def constrain_seq(x):
    """Sequence parallelism: (B, L, D) -> batch over data, SEQ over model.

    For prefill, head-count TP fragments (no assigned arch has q/kv heads
    divisible by 16), and GSPMD then all-reduces full score tensors.  With
    the sequence dim sharded, scores stay seq-sharded and only the (small)
    kv chunks are gathered.  No-op when seq doesn't divide the model axis."""
    if _MESH is None or x.ndim < 3:
        return x
    if x.shape[1] % _MESH.shape[_TP_AXIS] != 0:
        return constrain_batch(x)
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(_DP_AXES, _TP_AXIS, *(None,) * (x.ndim - 2)))
