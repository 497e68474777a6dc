"""Median host time of ``ServeEngine.serve`` inside an answer: prefill,
the decode steps and the tokens brought to the host."""


def read(r):
    s = r.window.stats.get("answer_serve_s")
    return None if s is None else s * 1e3
