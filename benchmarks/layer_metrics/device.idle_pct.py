"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.busy_s <= 0:
        return None
    return 100.0 * trace.idle_share
