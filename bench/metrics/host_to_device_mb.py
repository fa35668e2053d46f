"""Megabytes per round of host (numpy) arrays handed to device programs:
the ``host_bytes`` of the program's spans ``repro.block.steps`` and
``repro.prefix``, the batch copies of each step and prefix forward."""
from hostspans import host_bytes

KINDS = ("block.steps", "prefix")


def read(view):
    n = host_bytes(view, KINDS)
    return None if n is None else n / 1e6
