"""``retrieve_mfu``, read the same way in the fan-out cell, where it moves
``fanout_p50_ms``."""
import os

from harness import HERE, load_module

read = load_module(os.path.join(HERE, "metrics", "retrieve_mfu.py")).read
