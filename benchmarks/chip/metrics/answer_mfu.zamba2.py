"""``answer_mfu`` in the Zamba2 cell: the generator's share of the chip's
bf16 peak over the window, the FLOPs of every answer's real prompt and
served tokens counted from the hybrid's shapes
(``counts_zamba2.answer_flops``; no padding rows or positions)."""
import os

from harness import HERE, load_module

read = load_module(os.path.join(HERE, "metrics", "answer_mfu.py")).read
