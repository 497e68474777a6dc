"""Share of the traced window in which no operation ran on the device:
``1 - busy / window``, busy being the union of the device's op intervals
(``trace_reduce.idle_percent``)."""

from trace_reduce import idle_percent


def read(r):
    return idle_percent(r.trace, r.window_s)
