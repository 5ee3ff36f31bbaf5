"""The (query, key) pairs attention read, as a share of what dense causal
attention would have read: the program's counter `dsa.pairs_selected`
(counted on the device from the selection itself, over each row's real
queries and all the layers, and read back with the row) over
`dsa.pairs_causal` (host arithmetic from the rows' real lengths). 100
would mean the selection never bound. The first counts the rows that
came back in the window and the second the rows dispatched in it: the
two differ by the one batch in flight at either edge."""


def read(ctx):
    counters = ctx["counters"]
    causal = counters.get("dsa.pairs_causal", 0)
    selected = counters.get("dsa.pairs_selected", 0)
    if causal <= 0 or selected <= 0:
        return None
    return 100.0 * selected / causal
