"""Median, over the window's ``rag.retrieve`` spans (one a call), of the
host's own stages: entity recognition and the fan-out batch
(``recognise``), the temperature harvest (``harvest``) and merging and
rendering the context (``render``)."""
from program_spans import median_ms, stage_s, window


def read(r):
    spans = window("rag.retrieve", r.window.attempted)
    if spans is None:
        return None
    return median_ms(stage_s(s, "recognise", "harvest", "render")
                     for s in spans)
