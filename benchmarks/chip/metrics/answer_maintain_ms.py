"""Median host time of the maintenance pass ``RAGPipeline.answer`` runs
after each answer (``RAGPipeline.maintain``: prepare and commit)."""


def read(r):
    s = r.window.stats.get("answer_maintain_s")
    return None if s is None else s * 1e3
