"""Median, over the window's ``rag.retrieve`` spans (one a call), of the
seconds JAX spent tracing to jaxprs and lowering to MLIR inside the call
(the span's ``trace_s + lower_s``)."""
from program_spans import median_ms, window


def read(r):
    spans = window("rag.retrieve", r.window.attempted)
    if spans is None:
        return None
    return median_ms(s["attrs"].get("trace_s", 0.0)
                     + s["attrs"].get("lower_s", 0.0) for s in spans)
