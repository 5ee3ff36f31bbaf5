"""The `hc_pre` kernel's share of its roofline: the least time the chip
could take for the work its calls were needed for (`counts/xing4_0.py`:
phi's product, the sum of squares, the Sinkhorn steps and the pre-mix;
the stream read once, u and the two mixes written, a dispatched token
and hyper-connection), the larger of
operations over the peak bf16 rate and bytes over the memory bandwidth,
over the summed device time of the kernel's events in the trace. Says
which of the two bounds.

An event is the kernel's by its OWN name, `%hc_pre[.N]`, as
`dsa_indexer_roofline` reads its kernel: an event also names its
operands' producers, and the other half's kernel feeds this one."""

from benchmarks.layer_metrics.dsa_indexer_roofline import own_kernel_seconds

KERNEL = "hc_pre"


def own_roofline(ctx, kernel: str):
    """{value, bound_by, kernel_s} of the kernel named `kernel`, or None
    where the trace holds no event of it or the family counts none."""
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = own_kernel_seconds(trace, kernel)
    counted = ctx["counts"].kernel_work(ctx["cell"].config, kernel, ctx["work"])
    if not counted or seconds <= 0:
        return None
    flops, bytes_ = counted
    t_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    t_bytes = bytes_ / ctx["peaks"]["hbm_bytes_per_s"]
    return {
        "value": 100.0 * max(t_flops, t_bytes) / (seconds * ctx["chips"]),
        "bound_by": "operations" if t_flops >= t_bytes else "bytes",
        "kernel_s": seconds,
    }


def read(ctx):
    return own_roofline(ctx, KERNEL)
