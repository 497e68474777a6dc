"""The benchmark's own tests run on the CPU, from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

The benchmark's modules import each other by their top-level names, as
``run.py`` arranges; the same path is set here.
"""
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(ROOT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
