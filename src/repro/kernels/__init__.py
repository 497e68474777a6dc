"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel lives in its own subpackage with the mandated trio:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, dtype plumbing, interpret switch)
  ref.py    — pure-jnp oracle the kernel is validated against (the probe's
              is the core reference, ``repro.core.lookup.lookup_arena``)

On this CPU container kernels execute under ``interpret=True``; model code
selects kernel vs. reference implementation via config (TPU -> kernel).
"""
from . import (cuckoo_lookup, decode_attention, flash_attention,
               linear_scan, vmem)

__all__ = ["cuckoo_lookup", "decode_attention", "flash_attention",
           "linear_scan", "vmem"]
