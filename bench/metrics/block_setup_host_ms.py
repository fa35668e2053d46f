"""Host milliseconds per round in a block's set-up and merge: self time
of the program's spans ``repro.block``, ``.block.setup`` (split, anchor,
donation copies, zero velocity) and ``.block.merge``."""
from hostspans import host_ms

KINDS = ("block", "block.setup", "block.merge")


def read(view):
    return host_ms(view, KINDS)
