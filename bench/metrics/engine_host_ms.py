"""Host milliseconds per round in the round engine and scheduler's own
code: self time of the program's spans ``repro.round``, ``.sample``,
``.batch``, ``.client-update`` and ``.cohort-group``."""
from hostspans import host_ms

KINDS = ("round", "sample", "batch", "client-update", "cohort-group")


def read(view):
    return host_ms(view, KINDS)
