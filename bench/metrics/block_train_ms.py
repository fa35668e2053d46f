"""Device milliseconds per round in block-training programs: the block
step (``jit_step``) or a vectorized group update that holds it
(``jit_one_client``)."""

PROGRAMS = ("jit_step", "jit_one_client")


def read(view):
    s = view.trace.program_seconds()
    if not any(p in s for p in PROGRAMS):
        return None
    return 1e3 * sum(s.get(p, 0.0) for p in PROGRAMS) / view.rounds
