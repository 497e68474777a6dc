"""One sequence's decode state at the engine's cache, in MB (1e6 bytes):
the program's ``serve.state_bytes`` gauge summed over its kinds (KV
caches of the shared-block calls, SSM state and conv windows), which
``ServeEngine`` sets from the state's shapes.  A program without the
gauge gives nothing."""


def read(r):
    from repro.obs import get_registry
    reg = get_registry()
    if "serve.state_bytes" not in reg.names():
        return None
    cells = reg.gauge("serve.state_bytes").cells()
    return sum(cells.values()) / 1e6 if cells else None
