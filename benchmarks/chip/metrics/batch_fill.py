"""Share of the dispatched batch slots that carried a real query:
``serve.queries / (serve.queries + serve.padded_queries)`` over the
window (the async engine pads every batch to a power-of-two bucket)."""


def read(r):
    s = r.window.stats
    q, pad = s.get("serve.queries", 0.0), s.get("serve.padded_queries", 0.0)
    if q + pad <= 0:
        return None
    return 100.0 * q / (q + pad)
