"""Roofline share of the Pallas SSD scan (``kernels/mamba2_ssd.py``)."""
from roofline import share

OP_NAMES = ("_ssd_kernel",)


def read(view):
    return share(view, "ssd", OP_NAMES)
