"""Backend compilations inside the window: the ``xla.compiles`` counter
(the program's compile listener) across it.  ``RAGPipeline.retrieve``
builds fresh closures in every call, so each call compiles its programs
again (JAX counts a load from the persistent cache as one too)."""


def read(r):
    v = r.window.stats.get("xla.compiles")
    return None if v is None else float(v)
