"""The index-scores kernel's share of its roofline: the least time the
chip could take for the work its calls were needed for (every causal
(query, key) pair of the rows' real tokens x the index heads x their
size, two operations a multiply-add; the index queries, keys and weights
of the dispatched tokens in and one float32 score a causal pair out),
the larger of operations over the peak bf16
rate and bytes over the memory bandwidth, over the summed device time of
the kernel's events in the trace. Says which of the two bounds.

An event is the kernel's by its OWN name, the text before ` = `: the
selection kernel's events name `%dsa_index_scores.N` among their
operands, as the attention kernel's name `%dsa_select.N`."""

KERNEL = "dsa_index_scores"
TARGET = 'custom_call_target="tpu_custom_call"'


def own_kernel_seconds(trace, kernel: str) -> float:
    """Device seconds of the Mosaic calls named `%<kernel>` or
    `%<kernel>.N`."""
    total = 0.0
    for name, seconds in trace.op_s.items():
        own = name.split(" = ", 1)[0].strip().lstrip("%")
        if TARGET in name and (own == kernel or own.startswith(kernel + ".")):
            total += seconds
    return total


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
