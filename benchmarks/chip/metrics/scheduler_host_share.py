"""Share of the window the scheduler thread spent on the host work of its
batches: the ``pad``, ``dispatch``, ``prepare`` and ``route_back``
stages summed over the window's ``serve.batch`` spans, over the window,
in per cent.  The scheduler is one thread, so this is how much of it the
batches' host work takes."""
from program_spans import stage_s, window


def read(r):
    spans = window("serve.batch", r.window.stats.get("serve.batches", 0))
    if spans is None or r.window.seconds <= 0:
        return None
    busy = sum(stage_s(s, "pad", "dispatch", "prepare", "route_back")
               for s in spans)
    return 100.0 * busy / r.window.seconds
