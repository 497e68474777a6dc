"""Backend compilations inside the window: the ``xla.compiles`` counter
(the program's compile listener) across it.  Set-up warms every bucket
of the async engine, so this should stay 0."""


def read(r):
    v = r.window.stats.get("xla.compiles")
    return None if v is None else float(v)
