"""Share of the tokens dispatched in the window that padded a row to its
bucket's edge, from the program's own counters."""


def read(ctx):
    c = ctx["counters"]
    real, pad = c.get("text.tokens", 0), c.get("text.pad_tokens", 0)
    if real + pad <= 0:
        return None
    return 100.0 * pad / (real + pad)
