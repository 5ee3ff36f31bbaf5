"""Device busy time per thousand rows completed in the traced window:
what the compiled programs cost, whatever the host does around them."""


def read(ctx):
    trace, rows = ctx["trace"], ctx["window"].rows
    if trace is None or not rows or trace.busy_s <= 0:
        return None
    return 1e3 * trace.busy_s * ctx["chips"] / (rows / 1e3)
