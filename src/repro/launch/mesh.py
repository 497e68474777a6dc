"""Mesh construction (FUNCTIONS only — importing this module must not touch
jax device state; the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax

from ..core.distributed import auto_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Production meshes: one v5e pod (16x16=256 chips) or two (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_axes(jax.make_mesh(shape, axes))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small host-device meshes for CI-scale distribution tests."""
    if pod:
        return auto_axes(jax.make_mesh((pod, data, model),
                                       ("pod", "data", "model")))
    return auto_axes(jax.make_mesh((data, model), ("data", "model")))


def data_axes(mesh) -> tuple:
    """Batch/FSDP axes: ('pod','data') when a pod axis exists."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
