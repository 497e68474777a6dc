"""``answer_decode_step_ms`` in the Zamba2 cell: the median decode step
of the window's ``serve.generate`` spans, each step through every layer's
conv and SSM state and every call's KV cache, synced to the host."""
import os

from harness import HERE, load_module

read = load_module(os.path.join(HERE, "metrics",
                                "answer_decode_step_ms.py")).read
