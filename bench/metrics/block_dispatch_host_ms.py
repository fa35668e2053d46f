"""Host milliseconds per round spent dispatching block steps: self time
of the program's span ``repro.block.steps``, the SGD-step loop."""
from hostspans import host_ms

KINDS = ("block.steps",)


def read(view):
    return host_ms(view, KINDS)
