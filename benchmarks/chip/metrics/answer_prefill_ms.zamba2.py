"""``answer_prefill_ms`` in the Zamba2 cell: the median ``prefill`` stage
of the window's ``serve.generate`` spans, the prompt's forward pass
through Mamba2 layers and shared-block calls up to the first token."""
import os

from harness import HERE, load_module

read = load_module(os.path.join(HERE, "metrics", "answer_prefill_ms.py")).read
