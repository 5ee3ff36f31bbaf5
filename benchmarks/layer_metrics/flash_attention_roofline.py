"""The flash-attention kernel's share of its roofline: the least time the
chip could take for the work its calls were needed for, the larger of
operations over the peak bf16 rate and bytes over the memory bandwidth,
over the summed device time of the kernel's events in the trace. Says
which of the two bounds."""

#: What marks the kernel's events in the trace. The Pallas call has no
#: `name=`, so the trace names each after its HLO instruction
#: (`%attention.12 = f32[...] custom-call(...)`, from the wrapper function's
#: name) and the only stable mark is the custom call's target. bert-base
#: has no other Mosaic kernel.
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
