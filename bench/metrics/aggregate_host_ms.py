"""Host milliseconds per round on the wire and in aggregation: self time
of the program's spans ``repro.payload``, ``.comm``, ``.aggregate`` and
``.aggregate.finite``."""
from hostspans import host_ms

KINDS = ("payload", "comm", "aggregate", "aggregate.finite")


def read(view):
    return host_ms(view, KINDS)
