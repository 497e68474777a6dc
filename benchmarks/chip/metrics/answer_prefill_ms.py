"""Median, over the window's ``serve.generate`` spans (one an answer), of
the ``prefill`` stage: the prompt's forward pass up to the first token
on the host."""
from program_spans import median_ms, stage_s, window


def read(r):
    spans = window("serve.generate", r.window.attempted)
    if spans is None:
        return None
    return median_ms(stage_s(s, "prefill") for s in spans)
