"""Host milliseconds per round in the prefix cache: self time of the
program's span ``repro.prefix`` (buffer, advance or re-buffer)."""
from hostspans import host_ms

KINDS = ("prefix",)


def read(view):
    return host_ms(view, KINDS)
