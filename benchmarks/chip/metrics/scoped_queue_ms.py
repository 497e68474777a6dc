"""Median, over the window's ``serve.batch`` spans (one a batch), of the
``coalesce`` stage: how long the batch's oldest request waited in the
queue before the batch launched."""
from program_spans import median_ms, stage_s, window


def read(r):
    spans = window("serve.batch", r.window.stats.get("serve.batches", 0))
    if spans is None:
        return None
    return median_ms(stage_s(s, "coalesce") for s in spans)
