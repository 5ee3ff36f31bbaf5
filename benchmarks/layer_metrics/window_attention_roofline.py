"""The sliding-window flash kernel's share of its roofline: the least
time the chip could take for the work its calls were needed for (the
sliding layers' two products over the (query, key) pairs within the
window of the rows' real lengths, every query head; q, k, v and the
result moved once over the dispatched tokens), the larger of operations
over the peak bf16 rate and bytes over the memory bandwidth, over the
summed device time of the kernel's events in the trace. Says which of
the two bounds.

An event is the kernel's by its OWN name, `%flash_attention_window[.N]`,
as `dsa_indexer_roofline` reads its kernel: the full layers' causal
kernel is `%flash_attention[.N]`, and an event also names its operands'
producers."""

from benchmarks.layer_metrics.dsa_indexer_roofline import own_kernel_seconds

KERNEL = "flash_attention_window"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = own_kernel_seconds(trace, KERNEL)
    counted = ctx["counts"].kernel_work(ctx["cell"].config, KERNEL, ctx["work"])
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
