"""The whole program's share of the chip's peak: the forward operations
that the rows completed in the traced window needed (the benchmark's own
count, `benchmarks/counts/`: for text, what grows with a row's tokens at
the padded length dispatched, what grows with its pairs at its real
length, and the routed experts at the slots the program measured), over
the window, the chips and the peak bf16 rate. Nothing where the real
lengths could not be squared with the program's counters. Bounds every
kernel's roofline share: a kernel taken off the path leaves its own
metric silent, and this one still has to move."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    flops = ctx["counts"].forward_flops(ctx["cell"].config, ctx["work"])
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (trace.window_s * peak)
