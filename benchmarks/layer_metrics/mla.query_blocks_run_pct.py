"""The query blocks the latent-attention kernel ran, as a share of those
its calls were dispatched: the program's counters `mla.query_blocks_run`
(rows x layers x the query blocks that hold a real token, where the
attention takes its rows' lengths; all of them where it takes none) over
`mla.query_blocks` (rows x layers x the bucket's query blocks), both host
arithmetic at dispatch. 100 means every block of padding alone was run;
the traffic's own floor is the share of blocks that hold a real token.
Nothing where either counter is missing: a program that counts no query
blocks.

Host arithmetic, not a reading of the device: once the kernel takes
lengths the share restates the traffic and the bucket edges, and moves
only when those do. A fault in the kernel shows in
`mla.attention_ms_per_ktoken`, not here."""


def read(ctx):
    counters = ctx["counters"]
    blocks = counters.get("mla.query_blocks", 0)
    run = counters.get("mla.query_blocks_run", 0)
    if blocks <= 0 or run <= 0:
        return None
    return 100.0 * run / blocks
