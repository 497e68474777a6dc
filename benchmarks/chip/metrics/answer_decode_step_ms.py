"""Median, over the window's ``serve.generate`` spans (one an answer), of
one decode step: the ``decode`` stage over the ``steps - 1`` tokens it
produced, each synced to the host."""
from program_spans import median_ms, stage_s, window


def read(r):
    spans = window("serve.generate", r.window.attempted)
    if spans is None:
        return None
    return median_ms(stage_s(s, "decode") / (s["attrs"]["steps"] - 1)
                     for s in spans if s["attrs"].get("steps", 0) > 1)
