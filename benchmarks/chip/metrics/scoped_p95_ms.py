"""95th percentile of every request of the window, timed from its
scheduled arrival as ``retrieve_p50_ms`` is: a shed, failed or never
answered request counts at the close of the wait for answers."""


def read(r):
    return r.window.stats.get("retrieve_p95_ms")
