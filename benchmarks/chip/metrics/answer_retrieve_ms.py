"""Median host time of ``RAGPipeline.retrieve`` inside an answer: entity
recognition, the fan-out over every tree, the device step and the
rendered context."""


def read(r):
    s = r.window.stats.get("answer_retrieve_s")
    return None if s is None else s * 1e3
