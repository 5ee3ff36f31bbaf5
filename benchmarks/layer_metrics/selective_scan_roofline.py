"""The selective-scan kernel's share of its roofline: the least time the
chip could take for the work its calls were needed for, the larger of
operations over the peak bf16 rate and bytes over the memory bandwidth,
over the summed device time of the kernel's events in the trace. Says
which of the two bounds. The recurrence runs on the vector unit, which
has no published peak, so the share is low by construction: it is there
to be compared from PR to PR."""

#: Both marks: the custom call's target alone would count every Mosaic
#: kernel of a program that has two (this family has flash attention too).
EVENT_NAME_PARTS = ("%selective_scan", 'custom_call_target="tpu_custom_call"')
KERNEL = "selective_scan"


def kernel_seconds(trace) -> float:
    return sum(
        s
        for name, s in trace.op_s.items()
        if all(part in name for part in EVENT_NAME_PARTS)
    )


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = kernel_seconds(trace)
    work = ctx["counts"].kernel_work(ctx["cell"].config, KERNEL, ctx["work"])
    if not work or seconds <= 0:
        return None
    flops, bytes_ = work
    t_flops = flops / ctx["peaks"]["bf16_flops_per_s"]
    t_bytes = bytes_ / ctx["peaks"]["hbm_bytes_per_s"]
    return {
        "value": 100.0 * max(t_flops, t_bytes) / (seconds * ctx["chips"]),
        "bound_by": "operations" if t_flops >= t_bytes else "bytes",
        "kernel_s": seconds,
    }
