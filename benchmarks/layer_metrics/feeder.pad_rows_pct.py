"""Share of the rows the feeder dispatched in the window that only filled
a batch, from the program's own counters."""


def read(ctx):
    c = ctx["counters"]
    rows, pad = c.get("feeder.rows", 0), c.get("feeder.pad_rows", 0)
    if rows + pad <= 0:
        return None
    return 100.0 * pad / (rows + pad)
