"""Median, over the window's ``rag.retrieve`` spans (one a call), of the
backend compile seconds JAX reported inside the call (the span's
``compile_s``): the XLA compiles of the programs each call builds
afresh."""
from program_spans import median_ms, window


def read(r):
    spans = window("rag.retrieve", r.window.attempted)
    if spans is None:
        return None
    return median_ms(s["attrs"].get("compile_s", 0.0) for s in spans)
