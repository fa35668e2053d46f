"""Device milliseconds per round in the prefix cache's programs: the
from-scratch prefix forward (``jit_fwd``) and the incremental advance
(``jit_adv``)."""

PROGRAMS = ("jit_fwd", "jit_adv")


def read(view):
    s = view.trace.program_seconds()
    if not any(p in s for p in PROGRAMS):
        return None
    return 1e3 * sum(s.get(p, 0.0) for p in PROGRAMS) / view.rounds
