"""Roofline share of the Pallas chunked cross-entropy
(``kernels/chunked_ce.py``)."""
from roofline import share

OP_NAMES = ("_ce_kernel",)


def read(view):
    return share(view, "ce", OP_NAMES)
