"""The latent-attention flash kernel's share of its roofline: the least
time the chip could take for the work its calls were needed for (the
two products over the pairs of the rows' real tokens, half the square
or the selection, over keys of nope + rope and values of their own size;
q, k, v and the result moved once over the dispatched length), the larger of
operations over the peak bf16 rate and bytes over the memory bandwidth,
over the summed device time of the kernel's events in the trace. Says
which of the two bounds."""

#: Both marks: the custom call's target alone would count the grouped
#: product's events with the attention's (this family has both kernels),
#: which is why `flash_attention_roofline`'s reader cannot serve here.
EVENT_NAME_PARTS = ("%flash_attention", 'custom_call_target="tpu_custom_call"')
KERNEL = "flash_attention"


def kernel_seconds(trace, parts=EVENT_NAME_PARTS) -> float:
    return sum(
        s for name, s in trace.op_s.items() if all(part in name for part in parts)
    )


def roofline(ctx, kernel, parts, work):
    """{value, bound_by, kernel_s} of `kernel` for `work`, or None where
    the trace holds no event of it or the family counts no such kernel."""
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = kernel_seconds(trace, parts)
    counted = ctx["counts"].kernel_work(ctx["cell"].config, kernel, work)
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
    return roofline(ctx, KERNEL, EVENT_NAME_PARTS, ctx["work"])
