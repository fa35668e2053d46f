"""Share of the traced window in which no operation ran on the device."""


def read(view):
    return 100.0 * (1.0 - view.trace.busy_s() / view.window_s)
