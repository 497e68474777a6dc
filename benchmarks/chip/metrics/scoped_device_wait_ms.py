"""Median, over the window's ``serve.batch`` spans (one a batch), of the
``device_lookup`` stage: the scheduler blocked on the batch's results
coming to the host."""
from program_spans import median_ms, stage_s, window


def read(r):
    spans = window("serve.batch", r.window.stats.get("serve.batches", 0))
    if spans is None:
        return None
    return median_ms(stage_s(s, "device_lookup") for s in spans)
