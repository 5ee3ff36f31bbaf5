"""The `hc_post` kernel's share of its roofline: the least time the chip
could take for the work its calls were needed for (`counts/xing4_0.py`:
the residual and post mixes; the stream and the sublayer's output F read
and the new stream written, a dispatched token and hyper-connection),
the larger of operations over the peak bf16 rate and bytes over the
memory bandwidth, over the summed device time of the kernel's events in
the trace. Says which of the two bounds.

An event is the kernel's by its OWN name, `%hc_post[.N]`, as
`dsa_indexer_roofline` reads its kernel: an event also names its
operands' producers, and the other half's kernel feeds this one."""

from benchmarks.layer_metrics.mhc_pre_roofline import own_roofline

KERNEL = "hc_post"


def read(ctx):
    return own_roofline(ctx, KERNEL)
