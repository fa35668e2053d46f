"""Device program executions in the window, per round (round engine and
scheduler: how many dispatches a round's host path issues)."""


def read(view):
    n = len(view.trace.modules())
    return n / view.rounds if n else None
