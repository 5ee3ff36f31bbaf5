"""The flash-attention kernel's share of its roofline: the least time the
chip could take for the work its calls were needed for (the pairs of
the rows' real tokens; q, k, v and the result moved once over the
dispatched length), the larger of operations over the peak bf16 rate
and bytes over the memory bandwidth, over the summed device time of the
kernel's events in the trace. Says which of the two bounds."""

#: What marks the kernel's events in the trace: the custom call's target.
#: The program names the call (`%flash_attention.N = f32[...]
#: custom-call(...)`), but the name is the program's to change and the
#: target is not; bert-base has no other Mosaic kernel. A family that has
#: two reads by both marks (`mla_attention_roofline.py`).
EVENT_NAME_PART = 'custom_call_target="tpu_custom_call"'
KERNEL = "flash_attention"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = trace.kernel_s(EVENT_NAME_PART)
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
